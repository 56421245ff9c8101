# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark aggregator — one module per paper table/figure:

  bench_powerlaw_fit       Fig. 2 / Fig. 3 / Appendix F
  bench_delta_sensitivity  Fig. 4
  bench_selection          Fig. 5 / Fig. 6 / Fig. 11
  bench_table1             Tbl. 1 / Fig. 7 (+ arch selection)
  bench_al_sweep           Figs. 8-10 / Fig. 12
  bench_al_gains           §5.2 / Figs. 14-15 (live AL vs random)
  bench_table2             Tbl. 2 (oracle AL)
  bench_subset_sweep       Fig. 13
  bench_table3             Tbl. 3 (eps = 10%)
  bench_imagenet_bailout   §5.1 ImageNet
  bench_kernels            margin_head scoring structure
  bench_sweep              streaming pool-sweep runtime (>= 2x gate)
  bench_fit                fused retrain engine (>= 2x gate, exact params)
  bench_annotation         device Dawid-Skene EM (>= 2x gate, exact argmax)
  bench_trace              campaign event bus (<= 5% overhead gate +
                           replay-equals-live; smoke leaves its trace
                           under artifacts/ as a CI artifact)
  bench_orchestrator       multi-tenant fleet (0-new-compiles-after-
                           tenant-1 gate + <= 0.75x fresh-serial wall)
  bench_obs                runtime metrics layer (<= 3% overhead gate +
                           metrics-on/off trace diff clean; smoke drops
                           a Prometheus snapshot under artifacts/ and
                           its registry snapshot lands in BENCH_*.json)
  bench_faults             fault-injection harness (chaos run diff-clean
                           vs fault-free sibling + <= 5% idle-injector
                           overhead gate; smoke leaves its chaos trace
                           under artifacts/)
  bench_health             campaign health engine (<= 3% overhead gate:
                           health-monitored noisy campaign vs its
                           monitor-off sibling, decision streams diff
                           clean, same total cost)

Run all:  PYTHONPATH=src python -m benchmarks.run
One:      PYTHONPATH=src python -m benchmarks.run --only table1
CI smoke: PYTHONPATH=src python -m benchmarks.run --smoke
          (small-shape fit + sweep + scoring + k-center + annotation +
          orchestrator engine legs, speedup gates enforced — the CI
          matrix runs this on both jax legs)
History:  PYTHONPATH=src python -m benchmarks.run --check-history
          (the regression observatory: judge every gate's trend across
          benchmarks/history/ and fail on a >30% drop vs the rolling
          baseline — no jax import, see benchmarks/regress.py)

Every invocation additionally writes a machine-readable
``BENCH_<run>.json`` into ``benchmarks/history/`` (``--json`` overrides
the path, ``--run-id`` the stable orderable run name): per-row
us_per_call + parsed per-gate speedups + pool sizes + the jax
version/backend, so the perf trajectory is tracked across PRs — CI
uploads it as a workflow artifact, and the cross-PR trajectory lives
in-tree, not just in CI retention.  The smoke leg ends with a warn-only
observatory pass over that history so drift shows up in every CI log.
"""
from __future__ import annotations

import argparse
import importlib
import inspect
import json
import os
import sys
import time
import traceback

MODULES = (
    "bench_powerlaw_fit",
    "bench_delta_sensitivity",
    "bench_selection",
    "bench_table1",
    "bench_al_sweep",
    "bench_al_gains",
    "bench_table2",
    "bench_subset_sweep",
    "bench_table3",
    "bench_imagenet_bailout",
    "bench_kernels",
    "bench_sweep",
    "bench_fit",
    "bench_annotation",
    "bench_trace",
    "bench_orchestrator",
    "bench_obs",
    "bench_faults",
    "bench_health",
)

HISTORY_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "history")


def write_bench_json(path: str, run_id: str, mode: str, rows, errors) -> None:
    """The cross-PR perf-trajectory record: one JSON per benchmark run."""
    import jax

    blob = {
        "run": run_id,
        "mode": mode,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "rows": [r.record() for r in rows],
        "gates": {r.name: r.record()["speedup"] for r in rows
                  if "speedup" in r.record()},
        "errors": errors,
    }
    # the run's telemetry rides along: whatever registry bench_obs (or
    # any other module) installed as the process default
    try:
        from repro.obs import get_registry
        snap = get_registry().snapshot()
        if any(snap.values()):
            blob["metrics"] = snap
    except Exception:
        pass
    with open(path, "w") as f:
        json.dump(blob, f, indent=2)
    print(f"# wrote {path}", file=sys.stderr)


def run_smoke():
    """The CI smoke leg: small-shape fit-engine + sweep-runtime + engine
    benchmarks with their speedup gates ENFORCED (a gate miss fails the
    job).  Returns (status, rows, errors)."""
    from benchmarks import (bench_annotation, bench_faults, bench_fit,
                            bench_health, bench_obs, bench_orchestrator,
                            bench_selection, bench_sweep, bench_trace)

    print("name,us_per_call,derived")
    status, rows, errors = 0, [], []
    for name, fn in (
        ("bench_fit[smoke]", bench_fit.run_smoke),
        ("bench_sweep[smoke]", bench_sweep.run_smoke),
        ("bench_selection[scoring]",
         lambda: bench_selection.run_scoring(enforce=True)),
        ("bench_selection[kcenter]",
         lambda: bench_selection.run_kcenter(enforce=True)),
        ("bench_annotation[smoke]", bench_annotation.run_smoke),
        ("bench_trace[smoke]", bench_trace.run_smoke),
        ("bench_orchestrator[smoke]", bench_orchestrator.run_smoke),
        ("bench_obs[smoke]", bench_obs.run_smoke),
        ("bench_faults[smoke]", bench_faults.run_smoke),
        ("bench_health[smoke]", bench_health.run_smoke),
    ):
        try:
            for row in fn():
                rows.append(row)
                print(row.csv(), flush=True)
        except Exception as e:
            status = 1
            errors.append(f"{name}:{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
            print(f"{name},0.0,ERROR:{type(e).__name__}", flush=True)
    # warn-only observatory pass: history drift belongs in every smoke
    # log, but must never fail a PR that didn't touch perf
    try:
        from benchmarks import regress
        report = regress.evaluate(regress.load_history())
        print(regress.render(report), file=sys.stderr)
    except Exception as e:
        print(f"# regress observatory skipped: {e}", file=sys.stderr)
    return status, rows, errors


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: fit + sweep + scoring + k-center + "
                         "annotation + orchestrator legs at small "
                         "shapes, speedup gates enforced")
    ap.add_argument("--run-id", default="",
                    help="run name for the BENCH_<run>.json record "
                         "(default: the mode + jax version)")
    ap.add_argument("--json", default="",
                    help="path for the machine-readable record "
                         "(default: benchmarks/history/BENCH_<run>.json)")
    ap.add_argument("--check-history", action="store_true",
                    help="run the regression observatory over "
                         "benchmarks/history/ and exit (no benchmarks "
                         "run, no jax import)")
    ap.add_argument("--from-trace", default="", metavar="DIR",
                    help="reproduce paper-table campaign cells from "
                         "stored traces in DIR when present (modules "
                         "that support it replay instead of re-running; "
                         "live cells record their trace there)")
    args = ap.parse_args()

    if args.check_history:
        # the observatory is jax-free by design: judging history must
        # work on a box that can't even import the benchmarks
        from benchmarks import regress
        sys.exit(regress.main([]))

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

    def finish(mode: str, status: int, rows, errors):
        import jax
        run_id = args.run_id or f"{mode}-jax{jax.__version__}"
        # records ALWAYS land in benchmarks/history/ (stable, orderable
        # run id in the name) — the in-tree trajectory only works if
        # every run contributes to it, not just runs started from the
        # right CWD
        if args.json:
            path = args.json
        else:
            os.makedirs(HISTORY_DIR, exist_ok=True)
            path = os.path.join(HISTORY_DIR, f"BENCH_{run_id}.json")
        write_bench_json(path, run_id, mode, rows, errors)
        sys.exit(status)

    if args.smoke:
        finish("smoke", *run_smoke())

    print("name,us_per_call,derived")
    rows, errors = [], []
    for name in MODULES:
        if args.only and args.only not in name:
            continue
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            kw = {}
            if args.from_trace and \
                    "trace_dir" in inspect.signature(mod.run).parameters:
                kw["trace_dir"] = args.from_trace
            for row in mod.run(**kw):
                rows.append(row)
                print(row.csv(), flush=True)
        except Exception as e:
            errors.append(f"{name}:{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
            print(f"{name},0.0,ERROR:{type(e).__name__}", flush=True)
    finish(args.only or "full", 1 if errors else 0, rows, errors)


if __name__ == "__main__":
    main()
