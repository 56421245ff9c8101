#!/usr/bin/env python3
"""Bring-up smoke test: a live MCAL labeling campaign on one TPU chip.

    python chip_smoke.py              # phases 1-7 on one chip
    python chip_smoke.py --chips 4    # the --mesh data=4 campaign vs one chip

Everything runs in this one process: a chip belongs to one process at a
time.  The campaign goes through the launcher's own entry points
(``repro.launch.label.build_parser`` -> ``build_campaign`` ->
``run_campaign``) at the paper's CIFAR-10 pool shape, 50,000 rows x 10
classes generated from ``--seed``, with the ``LiveTask`` MLP labeler at its
defaults (depth 2, hidden 64, input 32).

Phases (one line each: wall seconds, backend-compile seconds, persistent
compilation-cache hits and writes, then the checks):

1. device      a TPU is attached (never falls back to the CPU);
2. engines     every engine against the oracle its tests use;
3. solo        noisy-crowd campaign commits, its ledger adds up, and the
               replayed trace reproduces the committed result;
4. async       the same campaign with --sweep-async --fit-async: its trace
               diffs clean against phase 3;
5. kcenter     a --metric kcenter campaign (the Pallas pairwise_dist
               kernel inside the acquisition loop);
6. fleet       two tenants on shared engines, concurrent then serial: each
               tenant's trace diffs clean between the two runs;
7. profile     one iteration under jax.profiler leaves an .xplane.pb.

The last line of standard output is ``{"ok": true, "device": {...}}``.
Without a TPU, or when any phase fails, the script prints the reason and
exits non-zero with no result line.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import threading
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

POOL, CLASSES, SEED = 50_000, 10, 0
# the noisy crowd of phases 3, 4 and 6: five workers, a fifth of them
# spammers, Dawid-Skene aggregation, 2 votes topped up to 4 below 0.9
# confidence.  Its aggregated labels keep ~6% residual error, so the
# default 5% target would bail out to human labels after a few
# iterations; a 10% target drives the campaign to a hybrid commit.
CROWD = ["--annotator-noise", "0.2", "--annotator-workers", "5",
         "--annotator-spammers", "0.2", "--annotator-aggregate", "ds",
         "--adaptive-repeats", "--label-repeats", "2", "--max-repeats", "4",
         "--eps", "0.1"]

# -- chip tolerances ----------------------------------------------------------
# Exact on the chip, as on the CPU: the fused retrain vs the per-step host
# loop over the same permutations (per step both run the same arithmetic;
# measured bit-identical on a TPU v5e), majority vote, pairwise
# distances and k-center picks on integer-valued features.  The scoring
# engine vs the seed host loop keeps the CPU contract of
# tests/test_scoring.py: stats and features within SCORE_ATOL, top1
# identical (measured bit-identical on a TPU v5e).
SCORE_ATOL = 1e-5
# TPU f32 matmuls run at DEFAULT precision: the MXU rounds each f32 operand
# to bf16 (8 mantissa bits), which float64 oracles do not.
#
# margin_head kernel vs the jnp reference, on bf16-exact inputs (a 1/8
# grid) so every product and partial sum is exact at any matmul
# precision; only the exp/log implementations differ (Mosaic vs XLA).
KERNEL_TOL = 1e-4
# Dawid-Skene EM vs the float64 host EM: the device M-step gemm rounds the
# posteriors to bf16, and 12 EM iterations propagate it.  Posteriors within
# DS_TOL; argmax identical wherever the host's top-2 posterior gap exceeds
# DS_TOL.  Every disagreement is counted and printed (on a TPU v5e, 195 of
# 50,000 items at max |d posterior| 0.0147).
DS_TOL = 5e-2
# --mesh data=4 vs one chip: the scoring sweep shards each microbatch over
# the mesh, which may change how XLA tiles a row's matmuls.  Decisions and
# the labeled-set trajectory must match; costs and errors within MESH_TOL
# (measured identical on four v5e chips).
MESH_TOL = 1e-2


class PhaseError(Exception):
    """A failed phase check; the message is printed before exiting."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseError(msg)


class CompileStats:
    """Backend-compile seconds and persistent-cache hits/writes, from
    JAX's monitoring events (compiles on broker threads included)."""

    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self.compile_s = 0.0
        self.hits = 0
        self.writes = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.compile_s += duration

    def _event(self, event: str, **_kw) -> None:
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.writes += 1

    def snapshot(self):
        with self._lock:
            return self.compile_s, self.hits, self.writes


def run_phase(label: str, fn, stats: CompileStats | None):
    """Run one phase, print its line, re-raise its failure."""
    t0 = time.perf_counter()
    c0 = stats.snapshot() if stats else (0.0, 0, 0)
    try:
        detail = fn()
        status = "ok"
    except Exception as e:
        detail, status = f"{type(e).__name__}: {e}", "FAIL"
        raise
    finally:
        wall = time.perf_counter() - t0
        c1 = stats.snapshot() if stats else (0.0, 0, 0)
        print(f"[{label}] {status} wall={wall:.3f}s "
              f"compile={c1[0] - c0[0]:.3f}s cache_hits={c1[1] - c0[1]} "
              f"cache_writes={c1[2] - c0[2]} | {detail}", flush=True)
    return detail


# ---------------------------------------------------------------------------
# phase 1: device
# ---------------------------------------------------------------------------


def phase_device(chips: int):
    import jax
    devs = jax.devices()
    d = devs[0]
    check(d.platform == "tpu",
          f"no TPU attached: jax.devices()[0].platform={d.platform!r}")
    check(len(devs) >= chips,
          f"{chips} chips asked for, {len(devs)} attached")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs), "jax": jax.__version__}


# ---------------------------------------------------------------------------
# phase 2: engine oracles
# ---------------------------------------------------------------------------


def _max_abs(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b))) if a.size else 0.0


def check_scoring(engines, params, x) -> str:
    import numpy as np
    from repro.core.scoring import score_pool_reference
    stats, feats = engines.scoring.score_host(params, x)
    ref, ref_feats = score_pool_reference(engines.model, params, x)
    worst = _max_abs(feats, ref_feats)
    for f in ("margin", "entropy", "max_logprob"):
        worst = max(worst, _max_abs(getattr(stats, f), getattr(ref, f)))
    check(worst <= SCORE_ATOL, f"scoring off the host loop by {worst:.3g}")
    check(np.array_equal(stats.top1, ref.top1), "scoring top1 differs")
    return f"scoring {len(x)} rows: max|d|={worst:.3g}, top1 identical"


def check_fit(engines, x, y, seed: int) -> str:
    import jax
    import numpy as np
    from repro import compat
    fit = engines.fit
    rng = jax.random.key(seed)
    p1, l1 = fit.fit(rng, x, y)
    pr, lr = fit.fit_reference(rng, x, y)
    check(np.all(np.isfinite(np.asarray(l1))), "fused retrain loss not finite")
    check(np.array_equal(np.asarray(l1), np.asarray(lr)),
          f"fused vs host-loop losses off by {_max_abs(l1, lr):.3g}")
    for a, b in zip(compat.tree_leaves(p1), compat.tree_leaves(pr)):
        check(np.array_equal(np.asarray(a), np.asarray(b)),
              f"fused vs host-loop params off by {_max_abs(a, b):.3g}")
    return (f"fit n={len(x)} steps={len(np.asarray(l1))} identical to the "
            f"host loop")


def check_kcenter(n: int, seed: int) -> str:
    import numpy as np
    import jax.numpy as jnp
    from repro.core.selection import k_center_greedy
    from repro.core.selection_device import k_center_greedy_device
    from repro.kernels import ops, ref
    from repro.kernels.pairwise_dist import pairwise_sqdist
    # integer-valued features: every squared distance is exact in f32 and
    # every product exact in bf16, so both paths must agree bit for bit
    rng = np.random.default_rng(seed)
    x = rng.integers(-4, 5, (n, 64)).astype(np.float32)
    a = rng.integers(-4, 5, (min(512, n), 64)).astype(np.float32)
    got = pairwise_sqdist(jnp.asarray(x), jnp.asarray(a),
                          interpret=ops._interpret())
    want = ref.pairwise_sqdist_ref(jnp.asarray(x), jnp.asarray(a))
    check(np.array_equal(np.asarray(got), np.asarray(want)),
          f"pairwise_sqdist kernel off by {_max_abs(got, want):.3g}")
    k = min(64, n)
    dev = k_center_greedy_device(x, k, anchors=a)
    host = k_center_greedy(x, k, anchors=a)
    check(np.array_equal(dev, host), "device k-center picks differ from host")
    return f"pairwise {x.shape}x{a.shape} exact, kcenter k={k} identical"


def check_margin_head(classes: int, seed: int) -> str:
    import numpy as np
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    from repro.kernels.margin_head import margin_head
    rng = np.random.default_rng(seed)
    out = []
    for V in sorted({classes, 1000}):
        h = jnp.asarray(rng.integers(-8, 9, (2048, 64)) / 8.0, jnp.float32)
        w = jnp.asarray(rng.integers(-8, 9, (64, V)) / 64.0, jnp.float32)
        got = margin_head(h, w, interpret=ops._interpret())
        want = ref.margin_head_ref(h, w)
        worst = 0.0
        for name, a, b in zip(("margin", "entropy", "max_logprob"),
                              got[:3], want[:3]):
            err = _max_abs(a, b)
            check(err <= KERNEL_TOL * (1.0 + float(np.max(np.abs(b)))),
                  f"margin_head V={V} {name} off by {err:.3g}")
            worst = max(worst, err)
        check(np.array_equal(np.asarray(got[3]), np.asarray(want[3])),
              f"margin_head V={V} top1 differs")
        out.append(f"V={V} max|d|={worst:.3g}")
    return "margin_head T=2048 D=64 " + ", ".join(out) + ", top1 identical"


def check_votes(n: int, classes: int, seed: int) -> str:
    import numpy as np
    from repro.annotation import (VoteAggregator, dawid_skene_host,
                                  majority_vote_host, make_annotator_pool)
    pool = make_annotator_pool(5, classes, noise=0.2, spammer_frac=0.2,
                               seed=seed)
    gt = np.random.default_rng(seed + 1).integers(0, classes, n)
    votes = pool.vote_matrix(np.arange(n), gt, 3)
    agg = VoteAggregator(classes)
    lh, ch = majority_vote_host(votes, classes)
    ld, cd = agg.majority(votes)
    check(np.array_equal(lh, ld), "majority vote labels differ from host")
    check(bool(np.all(np.abs(ch - cd) <= 1e-7)),
          "majority vote confidence differs from host")
    host = dawid_skene_host(votes, classes)
    dev = agg.dawid_skene(votes)
    post = np.sort(host.posterior, axis=1)
    gap = post[:, -1] - post[:, -2]
    flips = dev.labels != host.labels
    d_post = _max_abs(dev.posterior, host.posterior)
    check(d_post <= DS_TOL, f"DS posteriors off by {d_post:.3g}")
    check(not bool(np.any(flips & (gap > DS_TOL))),
          "DS argmax differs on an item with a clear posterior gap")
    return (f"votes n={n}: majority exact, DS max|d post|={d_post:.3g} "
            f"argmax flips={int(flips.sum())}")


def phase_engines(pool: int, classes: int, seed: int) -> str:
    import jax
    from repro.data.synth import make_classification
    from repro.launch.orchestrator import SharedEngines
    x, y = make_classification(pool, num_classes=classes, seed=seed)
    # the campaign's own engine construction (LiveTask defaults)
    engines = SharedEngines.build(x.shape[1], classes)
    try:
        params = engines.model.init(jax.random.key(seed))
        parts = [check_scoring(engines, params, x)]
        n_fit = min(pool, 4096)
        parts.append(check_fit(engines, x[:n_fit], y[:n_fit], seed))
    finally:
        engines.close()
    parts.append(check_kcenter(pool, seed))
    parts.append(check_margin_head(classes, seed))
    parts.append(check_votes(pool, classes, seed))
    return "; ".join(parts)


# ---------------------------------------------------------------------------
# phases 3-7: campaigns through the launcher
# ---------------------------------------------------------------------------


def campaign_argv(pool: int, classes: int, seed: int, *extra: str):
    return ["--live", "--pool", str(pool), "--classes", str(classes),
            "--seed", str(seed), *extra]


def run_launcher_campaign(argv, **run_kw):
    """parse -> build_campaign -> run_campaign, as ``launch.label.main``."""
    from repro.launch.label import build_campaign, build_parser, run_campaign
    args = build_parser().parse_args(argv)
    task, service, cfg, _ann = build_campaign(args)
    res, camp = run_campaign(task, service, cfg, trace_path=args.trace,
                             campaign_id=f"live-{args.arch}-s{args.seed}",
                             **run_kw)
    return args, res, camp


def check_committed(args, res) -> str:
    import numpy as np
    from repro.trace import replay
    check(res is not None, "campaign did not commit")
    if res.decision == "hybrid":
        check(res.measured_error <= args.eps,
              f"committed error {res.measured_error:.4f} > eps {args.eps}")
    led = res.ledger
    check(abs(led["total"] - (led["human"] + led["training"]))
          <= 1e-9 * max(1.0, led["total"]), "ledger total != human+training")
    X = len(res.labels)
    check(led["human_labels"] == X - res.S_size,
          f"{led['human_labels']} human labels for {X - res.S_size} rows")
    check(led["human_votes"] >= led["human_labels"], "fewer votes than labels")
    check(int(res.machine_mask.sum()) == res.S_size, "machine mask != S_size")
    check(bool(np.all(res.labels >= 0)), "unlabeled rows after commit")
    rp = replay(args.trace)
    check(rp.result is not None and rp.result.to_dict(with_history=False)
          == res.to_dict(with_history=False),
          "replayed commit differs from the live result")
    check([r.to_dict() for r in rp.history]
          == [r.to_dict() for r in res.history],
          "replayed iteration records differ from the live ones")
    check(rp.total_cost == res.total_cost, "replayed ledger differs")
    return (f"decision={res.decision} iters={len(res.history)} "
            f"B={res.B_size} S={res.S_size} err={res.measured_error:.4f} "
            f"cost={res.total_cost:.4f} votes={led['human_votes']}")


def phase_solo(work: str, pool: int, classes: int, seed: int, *extra):
    argv = campaign_argv(pool, classes, seed, *CROWD, *extra,
                         "--trace", os.path.join(work, "solo.jsonl"))
    args, res, _ = run_launcher_campaign(argv)
    return check_committed(args, res)


def phase_async(work: str, pool: int, classes: int, seed: int):
    from repro.trace import diff
    argv = campaign_argv(pool, classes, seed, *CROWD, "--sweep-async",
                         "--fit-async",
                         "--trace", os.path.join(work, "async.jsonl"))
    args, res, _ = run_launcher_campaign(argv)
    out = check_committed(args, res)
    d = diff(os.path.join(work, "solo.jsonl"), args.trace)
    check(d is None, f"async trace diverges from sync: {d and d.describe()}")
    return out + "; trace diff vs sync clean"


def phase_kcenter(work: str, pool: int, classes: int, seed: int):
    from repro.kernels import ops
    argv = campaign_argv(pool, classes, seed, "--metric", "kcenter",
                         "--trace", os.path.join(work, "kcenter.jsonl"))
    args, res, _ = run_launcher_campaign(argv)
    out = check_committed(args, res)
    check(len(res.history) >= 2,
          f"kcenter campaign ran {len(res.history)} iteration(s)")
    return out + f"; pairwise kernel {'on' if ops.use_pallas() else 'off'}"


def phase_fleet(work: str, pool: int, classes: int, seed: int):
    from repro.core import MCALConfig, SERVICES
    from repro.core.tenant import TenantSpec
    from repro.data.synth import make_classification
    from repro.launch.label import build_annotation, build_parser
    from repro.launch.orchestrator import build_fleet
    from repro.trace import diff
    args = build_parser().parse_args(
        campaign_argv(pool, classes, seed, *CROWD))
    service = SERVICES[args.service]
    x, y = make_classification(pool, num_classes=classes,
                               difficulty=args.difficulty, seed=seed)
    runs = {}
    for mode in ("concurrent", "serial"):
        ann = build_annotation(args, classes, service)
        quality = ann.calibrate()
        specs = [TenantSpec(tenant_id=f"t{i}", seed=seed + i,
                            cfg=MCALConfig(eps_target=args.eps,
                                           seed=seed + i,
                                           label_quality=quality))
                 for i in range(2)]
        trace_dir = os.path.join(work, f"fleet-{mode}")
        orch = build_fleet(x, y, specs, service=service,
                           trace_dir=trace_dir,
                           concurrent=(mode == "concurrent"),
                           annotation_service=ann)
        try:
            runs[mode] = (orch.run(), trace_dir)
        finally:
            orch.close()
    out = []
    for tid in ("t0", "t1"):
        res_c = runs["concurrent"][0][tid]
        res_s = runs["serial"][0][tid]
        d = diff(os.path.join(runs["concurrent"][1], f"{tid}.jsonl"),
                 os.path.join(runs["serial"][1], f"{tid}.jsonl"))
        check(d is None, f"tenant {tid} concurrent vs serial: "
                         f"{d and d.describe()}")
        check(res_c.total_cost == res_s.total_cost,
              f"tenant {tid} cost differs between modes")
        out.append(f"{tid}: {res_c.decision} iters={len(res_c.history)} "
                   f"cost={res_c.total_cost:.4f}")
    return "; ".join(out) + "; traces concurrent vs serial clean"


def phase_profile(work: str, pool: int, classes: int, seed: int):
    prof = os.path.join(work, "profile")
    argv = campaign_argv(pool, classes, seed, "--trace",
                         os.path.join(work, "profile.jsonl"))
    _args, res, camp = run_launcher_campaign(
        argv, profile_dir=prof, profile_iter=1, iters_per_run=1)
    check(len(camp.history) == 1, "profiled run did not stop after one "
                                  "iteration")
    found = glob.glob(os.path.join(prof, "**", "*.xplane.pb"),
                      recursive=True)
    check(bool(found), f"no .xplane.pb under {prof}")
    return f"{len(found)} xplane file(s), {os.path.getsize(found[0])} bytes"


def phase_mesh(work: str, pool: int, classes: int, seed: int, chips: int):
    """The --mesh data=N campaign against the same campaign on one chip."""
    runs = {}
    for name, extra in (("one", ()), ("mesh", ("--mesh", f"data={chips}"))):
        argv = campaign_argv(pool, classes, seed, *CROWD, *extra, "--trace",
                             os.path.join(work, f"{name}.jsonl"))
        args, res, _ = run_launcher_campaign(argv)
        runs[name] = (check_committed(args, res), res)
    one, mesh = runs["one"][1], runs["mesh"][1]
    check(mesh.decision == one.decision, "decision differs")
    check([r.B_size for r in mesh.history] == [r.B_size for r in one.history],
          "labeled-set trajectory differs")
    check((mesh.B_size, mesh.S_size) == (one.B_size, one.S_size),
          "committed B/S sizes differ")
    d_cost = abs(mesh.total_cost - one.total_cost) / max(one.total_cost, 1e-9)
    d_err = abs(mesh.measured_error - one.measured_error)
    check(d_cost <= MESH_TOL and d_err <= MESH_TOL,
          f"cost rel diff {d_cost:.3g}, error diff {d_err:.3g}")
    same_labels = int((mesh.labels == one.labels).sum())
    return (f"one chip: {runs['one'][0]}; mesh data={chips}: "
            f"{runs['mesh'][0]}; rel d cost={d_cost:.3g} "
            f"d err={d_err:.3g} labels equal {same_labels}/{len(one.labels)}")


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the --mesh data=4 campaign and the "
                         "same campaign on one chip, and compare them")
    ap.add_argument("--workdir", default=os.path.join(ROOT, ".chip_smoke"),
                    help="traces and the profile land here (emptied first)")
    return ap


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    from repro.launch.cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    stats = CompileStats()
    try:
        dev = run_phase("1 device", lambda: phase_device(args.chips), stats)
        print(f"# device {dev['kind']} x{dev['count']}, jax {dev['jax']}, "
              f"compile cache {cache_dir}", flush=True)
        work = args.workdir
        shutil.rmtree(work, ignore_errors=True)
        os.makedirs(work)
        shape = (POOL, CLASSES, SEED)
        if args.chips > 1:
            run_phase(f"mesh data={args.chips}",
                      lambda: phase_mesh(work, *shape, args.chips), stats)
        else:
            run_phase("2 engines", lambda: phase_engines(*shape), stats)
            run_phase("3 solo", lambda: phase_solo(work, *shape), stats)
            run_phase("4 async", lambda: phase_async(work, *shape), stats)
            run_phase("5 kcenter", lambda: phase_kcenter(work, *shape), stats)
            run_phase("6 fleet", lambda: phase_fleet(work, *shape), stats)
            run_phase("7 profile", lambda: phase_profile(work, *shape),
                      stats)
    except Exception as e:   # every failure ends the run with its reason
        traceback.print_exc()
        print(f"chip_smoke FAILED: {type(e).__name__}: {e}", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
