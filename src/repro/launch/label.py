"""MCAL labeling-campaign launcher — the paper's end-to-end system.

Live mode (real training on this host):
    PYTHONPATH=src python -m repro.launch.label --live --pool 4000 \
        --classes 10 --difficulty 0.3 --eps 0.05 --service amazon

Replay mode (paper-scale emulated learning curves):
    PYTHONPATH=src python -m repro.launch.label --dataset cifar10 \
        --arch resnet18 --service amazon

Noisy annotation service (repeated labeling, aggregated on device):
    PYTHONPATH=src python -m repro.launch.label --dataset cifar10 \
        --annotator-noise 0.2 --label-repeats 3 --annotator-aggregate ds \
        --adaptive-repeats --max-repeats 5

``--annotator-noise > 0`` (or ``--label-repeats > 1``) replaces the
perfect oracle with a seeded noisy-annotator pool: every human label is
an aggregation (majority vote or Dawid-Skene EM, jit-compiled on device)
over per-worker votes, every vote is charged at the service rate, and
the campaign folds the residual aggregated-label error into its accuracy
target (``MCALConfig.label_quality``).

Campaign state (ledger, pool bitmap, per-theta history, fitted power
laws, engine pack-shape cache keys) checkpoints to ``--state`` after
every iteration, so a preempted campaign resumes mid-loop — and during
the commit sweep a resumable ``SweepCheckpoint`` cursor is embedded
every ``--sweep-ckpt-pages`` pages, so even a mid-pool L(.) sweep
survives a restart.  ``--iters-per-run`` bounds how many iterations one
invocation runs (preemptible-worker style): when the campaign is not
done yet the invocation saves state and exits with a resumable report.
"""
from __future__ import annotations

import argparse
import json
import os


# every selection-module metric plus the paper's random baseline
# (supported by select_for_training but previously missing from the CLI).
# A literal, not `selection.METRICS`: importing repro.core pulls in jax,
# and the launcher must stay cheap until parsing succeeds (--help never
# pays for it).  tests/test_label_launcher.py asserts the sets match, so
# drift fails CI.
METRIC_CHOICES = ("margin", "entropy", "least_confidence", "kcenter",
                  "random")

# annotation.service.AGGREGATORS, duplicated as a literal for the same
# reason as METRIC_CHOICES (parsing must not import jax); the launcher
# tests assert the sets match.
AGGREGATE_CHOICES = ("majority", "ds")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--live", action="store_true")
    ap.add_argument("--dataset", default="cifar10",
                    choices=("fashion", "cifar10", "cifar100", "imagenet"))
    ap.add_argument("--arch", default="resnet18")
    ap.add_argument("--pool", type=int, default=4000)
    ap.add_argument("--classes", type=int, default=10)
    ap.add_argument("--difficulty", type=float, default=0.3)
    ap.add_argument("--eps", type=float, default=0.05)
    ap.add_argument("--budget", type=float, default=None)
    ap.add_argument("--metric", default="margin", choices=METRIC_CHOICES)
    ap.add_argument("--service", default="amazon",
                    choices=("amazon", "satyam"))
    ap.add_argument("--sweep-page", type=int, default=8192,
                    help="pool-sweep runtime page rows (the paged, "
                         "double-buffered L(.)/M(.) pool passes)")
    ap.add_argument("--sweep-async", action="store_true",
                    help="overlap each iteration's M(.) sweep with the "
                         "host-side power-law fits + joint search")
    ap.add_argument("--fit-fused", dest="fit_fused", action="store_true",
                    default=True,
                    help="fused-scan retrain engine: the whole fixed-epoch "
                         "retrain as one device program (default)")
    ap.add_argument("--no-fit-fused", dest="fit_fused", action="store_false",
                    help="per-step host training loop (the exact-agreement "
                         "oracle path)")
    ap.add_argument("--fit-async", action="store_true",
                    help="defer each retrain + its measurement sweep onto "
                         "the fit-engine worker thread (overlaps the "
                         "retrain dispatch; iteration records are "
                         "identical to the synchronous campaign)")
    ap.add_argument("--fit-resident", action="store_true",
                    help="keep the labeled set device-resident across "
                         "iterations; only newly bought labels upload")
    ap.add_argument("--state", default="",
                    help="campaign state file: saved every iteration (and "
                         "every --sweep-ckpt-pages pages of the commit "
                         "sweep); an existing file is resumed")
    ap.add_argument("--autosave", default="", metavar="PATH",
                    help="crash-safe sidecar: on any unhandled fault past "
                         "bootstrap the campaign flushes its trace and "
                         "writes state_dict here (atomic rename); the "
                         "next invocation resumes from it bit-identically "
                         "(--state, when present, wins)")
    ap.add_argument("--sweep-timeout", type=float, default=None,
                    metavar="SECONDS",
                    help="straggler wall budget for the async M(.) sweep "
                         "fold: a hung sweep job raises StragglerTimeout "
                         "instead of blocking forever (default: wait "
                         "forever)")
    ap.add_argument("--fit-timeout", type=float, default=None,
                    metavar="SECONDS",
                    help="straggler wall budget for the async retrain "
                         "fold (default: wait forever)")
    ap.add_argument("--chaos", action="store_true",
                    help="demo fault injection: run under the standard "
                         "transient FaultPlan (flaky annotation backend, "
                         "one broker-job crash per engine, one torn trace "
                         "write) with the default RetryPolicy — the "
                         "campaign must complete and its trace must diff "
                         "clean against a fault-free sibling")
    ap.add_argument("--chaos-seed", type=int, default=None,
                    help="fault-plan seed (default: --seed)")
    ap.add_argument("--sweep-ckpt-pages", type=int, default=0,
                    help="cut a resumable commit-sweep cursor into --state "
                         "every N pages (0 disables)")
    ap.add_argument("--iters-per-run", type=int, default=0,
                    help="run at most N iterations this invocation, then "
                         "save --state and exit resumable (0 = run to "
                         "completion)")
    ap.add_argument("--mesh", default="",
                    help="host/device mesh spec, e.g. 'data=4': the "
                         "scoring sweep and the fused-fit program shard "
                         "over it (live mode; smoke-testable under "
                         "--xla_force_host_platform_device_count)")
    # -- annotation service (noisy multi-annotator oracle) -----------------
    ap.add_argument("--annotator-noise", type=float, default=0.0,
                    help="per-vote error rate of the noisy annotator "
                         "pool (0 = the paper's perfect-oracle "
                         "assumption, no service attached)")
    ap.add_argument("--annotator-workers", type=int, default=5,
                    help="annotator pool size (each worker votes at most "
                         "once per item)")
    ap.add_argument("--annotator-spammers", type=float, default=0.0,
                    help="fraction of workers answering uniformly at "
                         "random")
    ap.add_argument("--annotator-aggregate", default="majority",
                    choices=AGGREGATE_CHOICES,
                    help="vote aggregation: device majority vote or "
                         "Dawid-Skene EM")
    ap.add_argument("--label-repeats", type=int, default=1,
                    help="votes bought per human label (repeated "
                         "labeling; each vote is charged at the service "
                         "rate)")
    ap.add_argument("--max-repeats", type=int, default=0,
                    help="adaptive-repeats vote cap (0 = --label-repeats, "
                         "no top-up)")
    ap.add_argument("--adaptive-repeats", action="store_true",
                    help="stop buying votes for an item once its "
                         "aggregated posterior confidence clears "
                         "--repeat-confidence (Liao et al.)")
    ap.add_argument("--repeat-confidence", type=float, default=0.9,
                    help="adaptive-repeats confidence threshold")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="")
    # -- campaign trace (event bus) -----------------------------------------
    ap.add_argument("--trace", default="",
                    help="append-only campaign trace (JSONL): every "
                         "decision/charge/measurement event; a campaign "
                         "resumed via --state appends to it at the "
                         "checkpointed cursor.  Watch it live with "
                         "python -m repro.launch.report")
    ap.add_argument("--trace-replay", default="", metavar="TRACE",
                    help="reconstruct a campaign report from its trace "
                         "alone (no engines, zero recompute) and exit")
    ap.add_argument("--trace-diff", nargs=2, default=None,
                    metavar=("TRACE_A", "TRACE_B"),
                    help="first-divergence analysis between two sibling "
                         "campaign traces, then exit")
    # -- runtime metrics & profiling (repro.obs) ----------------------------
    ap.add_argument("--metrics", default="", metavar="PATH",
                    help="record runtime telemetry (spans, counters, "
                         "compile-cache hits) as metric events at PATH; "
                         "pass the --trace path to interleave them into "
                         "the campaign trace (replay/diff ignore them).  "
                         "View with python -m repro.launch.report "
                         "--metrics")
    ap.add_argument("--slo", default="", metavar="SPEC.json",
                    help="streaming health engine: judge the campaign "
                         "against the declarative SLO spec (cost per "
                         "committed label, iteration-latency p95, "
                         "projected quality) plus the detector suite "
                         "(budget burn ETA, annotator drift, fit "
                         "quality, cache storms, queue saturation, "
                         "fault pressure) at every iteration boundary; "
                         "hysteresis-gated alert events interleave into "
                         "--trace (observability kinds — replay/diff "
                         "ignore them).  Render with python -m "
                         "repro.launch.report --health")
    ap.add_argument("--prom", default="", metavar="PATH",
                    help="write a Prometheus textfile snapshot of the "
                         "metrics registry at campaign teardown")
    ap.add_argument("--profile", default="", metavar="DIR",
                    help="bracket one iteration (see --profile-iter) with "
                         "jax.profiler.trace into DIR")
    ap.add_argument("--profile-iter", type=int, default=1,
                    help="which iteration --profile brackets (1-based, "
                         "default: the first)")
    return ap


def build_mesh(spec: str):
    """``--mesh data=4`` -> a host mesh with those axes (None for '')."""
    if not spec:
        return None
    from repro import compat
    axes, shape = [], []
    for part in spec.split(","):
        name, _, n = part.partition("=")
        axes.append(name.strip())
        shape.append(int(n))
    return compat.make_mesh(tuple(shape), tuple(axes), axis_types=True)


def build_annotation(args, num_classes: int, service):
    """The campaign's annotation-service runtime from the CLI flags —
    None when the flags describe the perfect oracle (no noise, single
    vote, no adaptive policy)."""
    if args.annotator_noise <= 0 and args.label_repeats <= 1 \
            and not args.adaptive_repeats:
        return None
    from repro.annotation import make_annotation_service
    return make_annotation_service(
        num_classes, n_workers=args.annotator_workers,
        noise=args.annotator_noise, spammer_frac=args.annotator_spammers,
        repeats=args.label_repeats,
        max_repeats=args.max_repeats or None,
        adaptive=args.adaptive_repeats,
        confidence=args.repeat_confidence,
        aggregator=args.annotator_aggregate,
        pricing=service, seed=args.seed)


def _save_state(path: str, campaign=None, cursor=None, campaign_blob=None):
    """Atomic-ish state write: campaign loop state + optional mid-sweep
    cursor (the cursor is only meaningful for the commit sweep cut against
    the saved loop state).  Pass ``campaign_blob`` to reuse an already
    serialized campaign dict — cursor cuts fire every few pages and the
    loop state is frozen for the whole commit sweep, so re-serializing
    the O(pool) label list per cut would dominate the sweep itself."""
    blob = {"campaign": campaign_blob if campaign_blob is not None
            else campaign.state_dict()}
    if cursor is not None:
        blob["sweep_cursor"] = cursor.to_json()
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(blob, f)
    os.replace(tmp, path)


def run_campaign(task, service, cfg, *, state_path: str = "",
                 sweep_ckpt_pages: int = 0, iters_per_run: int = 0,
                 trace_path: str = "", campaign_id: str = "campaign",
                 metrics_path: str = "", prom_path: str = "",
                 profile_dir: str = "", profile_iter: int = 1,
                 autosave_path: str = "", sweep_timeout=None,
                 fit_timeout=None, faults=None, retry=None,
                 slo_path: str = ""):
    """Drive one campaign with optional ``--state`` fault tolerance and
    an optional ``--trace`` event log.  Returns (MCALResult | None,
    campaign) — result is None when ``iters_per_run`` preempted the loop
    before completion.  A resumed campaign whose state checkpoint embeds
    a trace cursor APPENDS to its existing trace (no gaps, no duplicate
    sequence numbers); otherwise the trace starts fresh.

    ``metrics_path``/``prom_path``/``profile_dir`` wire the runtime
    observability layer (``repro.obs``): any of them builds a
    ``MetricsRegistry`` and attaches it to the campaign.  When
    ``metrics_path`` names the same file as ``trace_path`` the metric
    events interleave into the campaign trace (they are observability
    kinds — replay and diff ignore them); a distinct path gets its own
    store.  ``profile_dir`` brackets iteration ``profile_iter`` with
    ``jax.profiler.trace``."""
    from repro.core import MCALCampaign
    from repro.serving.sweep import SweepCheckpoint

    camp = MCALCampaign(task, service, cfg)
    camp.sweep_timeout = sweep_timeout
    camp.fit_timeout = fit_timeout
    blob = None
    if state_path and os.path.exists(state_path):
        with open(state_path) as f:
            blob = json.load(f)
    elif autosave_path and os.path.exists(autosave_path):
        # a prior invocation died past bootstrap and left its crash-safe
        # sidecar: resume from it (an explicit --state blob wins above —
        # it is at least as recent, saved every iteration)
        with open(autosave_path) as f:
            blob = json.load(f)

    trace = None
    if trace_path:
        from repro.trace import TraceStore
        cursor = blob["campaign"].get("trace") if blob is not None else None
        if cursor and os.path.exists(trace_path):
            trace = TraceStore.resume(trace_path, cursor["next_seq"])
        else:
            trace = TraceStore(trace_path, campaign_id)
        # attach BEFORE bootstrap/load so the trace opens with the
        # campaign's first event (campaign_begin or the resume marker)
        camp.attach_trace(trace)

    metrics = None
    metrics_store = None     # owned here iff metrics get their own file
    if metrics_path or prom_path or profile_dir:
        from repro.obs import MetricsRegistry
        metrics = MetricsRegistry()
        if (metrics_path and trace is not None
                and os.path.abspath(metrics_path)
                == os.path.abspath(trace_path)):
            metrics.attach_trace(trace)
        elif metrics_path:
            from repro.trace import TraceStore
            metrics_store = TraceStore(metrics_path, campaign_id)
            metrics.attach_trace(metrics_store)
        camp.attach_metrics(metrics)

    if slo_path:
        # after attach_trace/attach_metrics: the health engine inherits
        # whatever surfaces the campaign already observes with
        from repro.obs import HealthEngine, SLOSpec
        camp.attach_health(HealthEngine(SLOSpec.load(slo_path)))

    if faults is not None:
        # after attach_trace/attach_metrics: the injector mirrors its
        # events into whatever the campaign already observes with
        camp.attach_faults(faults, retry)

    bootstrapped = False
    try:
        if blob is not None:
            camp.load_state_dict(blob["campaign"])
            if "sweep_cursor" in blob:
                camp.resume_sweep_checkpoint = SweepCheckpoint.from_json(
                    blob["sweep_cursor"])
        else:
            camp.bootstrap()
            if state_path:
                _save_state(state_path, camp)
        bootstrapped = True

        if state_path and sweep_ckpt_pages:
            camp.sweep_checkpoint_every = sweep_ckpt_pages
            frozen = {}   # campaign blob serialized once at the first cut

            def save_cursor(ck):
                if "blob" not in frozen:
                    frozen["blob"] = camp.state_dict()
                _save_state(state_path, cursor=ck,
                            campaign_blob=frozen["blob"])

            camp.on_sweep_checkpoint = save_cursor

        try:
            ran = 0
            while not camp.done:
                if profile_dir and ran + 1 == profile_iter:
                    from repro.obs import profile_block
                    with profile_block(profile_dir):
                        camp.iteration()
                else:
                    camp.iteration()
                ran += 1
                if state_path:
                    _save_state(state_path, camp)
                if iters_per_run and ran >= iters_per_run and not camp.done:
                    return None, camp
            res = camp.commit()
        except BaseException:
            # crash-safe autosave: anything that unwinds past bootstrap —
            # including an injected kill — leaves a resumable sidecar.
            # Best-effort by design: the original exception always wins.
            if autosave_path and bootstrapped:
                try:
                    if trace is not None:
                        trace.emit("autosave", path=autosave_path,
                                   iterations=len(camp.history))
                    _save_state(autosave_path, camp)
                except Exception:
                    pass
            raise
        if state_path and os.path.exists(state_path):
            os.remove(state_path)   # campaign complete: the state is spent
        if autosave_path and os.path.exists(autosave_path):
            os.remove(autosave_path)
        return res, camp
    finally:
        # teardown order matters: close the campaign first (joins the
        # sweep/fit/annotation broker threads, so nothing can emit), then
        # the final metrics snapshot (it writes through the still-open
        # stores), then the stores.  A partial run (iters_per_run) exits
        # the process after this anyway — resume rebuilds the brokers
        # lazily.
        camp.close()
        if metrics is not None:
            metrics.emit_snapshot(scope="campaign")
            if prom_path:
                metrics.write_prometheus(prom_path)
        if metrics_store is not None:
            metrics_store.close()
        if trace is not None:
            trace.close()


def build_campaign(args):
    """The campaign ``main()`` runs for parsed ``args``: ``(task,
    service, cfg, annotation)`` — a :class:`~repro.core.task.LiveTask`
    over a seeded synthetic pool (``--live``) or an emulated replay,
    with the annotation runtime the flags describe (None for the perfect
    oracle)."""
    from repro.core import (MCALConfig, SERVICES, LiveTask,
                            make_emulated_task)
    from repro.data.synth import make_classification

    service = SERVICES[args.service]
    if args.live:
        num_classes = args.classes
    else:
        from repro.core.emulator import DATASETS
        num_classes = DATASETS[args.dataset]["classes"]
    annotation = build_annotation(args, num_classes, service)
    cfg = MCALConfig(eps_target=args.eps, metric=args.metric,
                     budget=args.budget, seed=args.seed,
                     sweep_async=args.sweep_async,
                     fit_async=args.fit_async,
                     # measured (calibration-batch) quality: what DS +
                     # adaptive repeats actually deliver, deterministic
                     # per seed so resumed runs rebuild the same config
                     label_quality=(annotation.calibrate()
                                    if annotation is not None else None))
    if args.live:
        x, y = make_classification(args.pool, num_classes=args.classes,
                                   difficulty=args.difficulty,
                                   seed=args.seed)
        task = LiveTask(features=x, groundtruth=y, num_classes=args.classes,
                        seed=args.seed, sweep_page=args.sweep_page,
                        fit_fused=args.fit_fused,
                        fit_resident=args.fit_resident,
                        mesh=build_mesh(args.mesh), annotation=annotation)
    else:
        task = make_emulated_task(args.dataset, args.arch, seed=args.seed,
                                  sweep_page=args.sweep_page)
        task.annotation = annotation
    return task, service, cfg, annotation


def main():
    args = build_parser().parse_args()

    # trace analysis modes exit before any task/engine construction:
    # they read event files, not devices
    if args.trace_diff is not None:
        from repro.trace import diff
        d = diff(*args.trace_diff)
        if d is None:
            print(json.dumps({"identical": True}))
        else:
            print(json.dumps({"identical": False,
                              "divergence": d.describe(),
                              "index": d.index, "kind_a": d.kind_a,
                              "kind_b": d.kind_b, "fields": d.fields},
                             indent=2))
        return
    if args.trace_replay:
        from repro.trace import replay
        rp = replay(args.trace_replay)
        report = {
            "campaign": rp.campaign, "replayed_from": args.trace_replay,
            "decision": rp.decision, "done_reason": rp.done_reason,
            "iterations": len(rp.history), "cost": rp.total_cost,
            "ledger": rp.ledger, "votes": rp.votes,
            "config": rp.config, "runtime": rp.runtime,
        }
        if rp.result is not None:
            report.update(theta_final=rp.result.theta_final,
                          measured_error=rp.result.measured_error,
                          B_size=rp.result.B_size,
                          S_size=rp.result.S_size)
        print(json.dumps(report, indent=2))
        if args.out:
            with open(args.out, "w") as f:
                json.dump(report, f)
        return

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    task, service, cfg, annotation = build_campaign(args)

    faults = retry = None
    if args.chaos:
        from repro.faults import FaultInjector, FaultPlan, RetryPolicy
        chaos_seed = (args.seed if args.chaos_seed is None
                      else args.chaos_seed)
        faults = FaultInjector(FaultPlan.standard_transient(chaos_seed))
        retry = RetryPolicy(seed=chaos_seed)

    campaign_id = (f"{'live' if args.live else args.dataset}-"
                   f"{args.arch}-s{args.seed}")
    res, camp = run_campaign(task, service, cfg, state_path=args.state,
                             sweep_ckpt_pages=args.sweep_ckpt_pages,
                             iters_per_run=args.iters_per_run,
                             trace_path=args.trace,
                             campaign_id=campaign_id,
                             metrics_path=args.metrics,
                             prom_path=args.prom,
                             profile_dir=args.profile,
                             profile_iter=args.profile_iter,
                             autosave_path=args.autosave,
                             sweep_timeout=args.sweep_timeout,
                             fit_timeout=args.fit_timeout,
                             faults=faults, retry=retry,
                             slo_path=args.slo)
    if res is None:
        report = {"resumable": True, "state": args.state,
                  "iterations": len(camp.history),
                  "B_size": len(camp.pool.B_idx)}
        if args.trace:
            report["trace"] = args.trace
        print(json.dumps(report, indent=2))
        return
    X = task.pool_size
    human_all = X * service.price_per_label
    if annotation is not None:   # the honest baseline pays repeats too
        human_all *= cfg.label_quality.avg_repeats
    report = {
        "decision": res.decision,
        "B_frac": res.B_size / X,
        "S_frac": res.S_size / X,
        "theta_final": res.theta_final,
        "measured_error": res.measured_error,
        "cost": res.total_cost,
        "human_all_cost": human_all,
        "savings": 1.0 - res.total_cost / human_all,
        "ledger": res.ledger,
        "iterations": len(res.history),
    }
    if args.trace:
        report["trace"] = args.trace
    if args.metrics:
        report["metrics"] = args.metrics
    if faults is not None:
        report["chaos"] = {"faults_injected": faults.fired,
                           "sites_ticked": faults.counters()}
    if args.slo and camp.health is not None:
        report["health"] = camp.health.counts()
    if annotation is not None:
        report["annotation"] = {
            "votes": annotation.votes_bought,
            "avg_repeats": annotation.avg_repeats(),
            "residual_error_est": annotation.estimated_residual_error(),
            "worker_accuracy": annotation.worker_accuracy().tolist(),
        }
    print(json.dumps(report, indent=2))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f)


if __name__ == "__main__":
    main()
