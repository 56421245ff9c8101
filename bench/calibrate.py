#!/usr/bin/env python3
"""Readings that the limits of ``bench/limits`` are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 101,102,... \
        --seconds 10 [--control 3] [--look 1] [--faults answer,half_batch] \
        [--fault-seeds 201,202] [--out FILE]

One process.  For each seed it generates that seed's pool, runs a window
of ``--seconds`` as ``bench/run.py`` does, and reads every check number
of the cell.  ``--control N`` also reads the control (the reference in
bfloat16 in the program's place) on the first N seeds, and ``--look 1``
each campaign's own readings.  ``--faults``
then plants each of :data:`bench.faults.FAULTS` named in turn, on a
fresh engine bundle, and reads the same seeds' windows under it.  One
JSON line per seed and fault goes to standard output and to ``--out``.
The benchmark's own runs never run this.
"""
import argparse
import contextlib
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# reference margins above which the look counts label disagreements
LOOK_MARGINS = (0.0, 1.0, 2.0, 4.0)


def label_look(refs, runs, dtype=None):
    """``label_gap`` over the rows whose reference margin is at least
    each of :data:`LOOK_MARGINS`."""
    worst = [0.0] * len(LOOK_MARGINS)
    for run in runs:
        if not run.committed or not run.machine_mask.any():
            continue
        rows = np.nonzero(run.machine_mask)[0]
        n = run.train_sizes[-1]
        want, margin = refs.top1_margin(run, n, rows)
        got = run.labels[rows] if dtype is None else refs.top1_margin(
            run, n, rows, dtype)[0]
        for i, t in enumerate(LOOK_MARGINS):
            keep = margin >= t
            if keep.any():
                worst[i] = max(worst[i], float(np.mean(
                    got[keep] != want[keep])))
    return worst


def campaign_look(refs, runs):
    """Per committed campaign: its pool error and label gap, its last
    retrain's losses (the last epoch's mean and highest, the lowest of
    the retrain) and labeled-set size, and the error on the
    machine-labeled rows of the reference's retrain at float32 and at
    bfloat16."""
    from bench import checks
    out = []
    for run in runs:
        if not run.committed:
            continue
        rows = np.nonzero(run.machine_mask)[0]
        n = run.train_sizes[-1]
        truth = refs.y[rows]
        f32, _ = refs.top1_margin(run, n, rows)
        bf16, _ = refs.top1_margin(run, n, rows, "bfloat16")
        losses = np.asarray(run.losses[-1], np.float64)
        last = losses[-max(len(losses) // refs.cell.config["labeler"][
            "epochs"], 1):]
        out.append({
            "seed": run.seed,
            "pool_error": float(np.mean(run.labels != refs.y)),
            "label_gap": checks.label_gap(refs, [run]),
            "loss_last_epoch": [float(last.mean()), float(last.max())],
            "loss_min": float(losses.min()),
            "labeled": int(n),
            "machine_error": [float(np.mean(run.labels[rows] != truth)),
                              float(np.mean(f32 != truth)),
                              float(np.mean(bf16 != truth))]})
    return out


def read_seeds(harness, cell, seeds, seconds, control, fault, out,
               look=False):
    from bench import checks
    env = harness.Env(cell, seeds[0], trace=False)
    driver = harness.load_module("drivers", cell.traffic["driver"])
    env.warm()
    try:
        for i, seed in enumerate(seeds):
            env.set_pool(seed)
            t0 = time.perf_counter()
            runs = driver.run_window(env, harness.window_seeds(seed),
                                     seconds)
            line = {"workload": cell.name, "seed": seed, "fault": fault,
                    "campaigns": len(runs),
                    "window_s": time.perf_counter() - t0,
                    "decisions": [r.decision or r.error for r in runs]}
            refs = checks.Retrains(cell, env.x, env.y)
            line["readings"] = checks.readings(cell, env.x, env.y, runs,
                                               refs)
            line["look"] = {
                "fit_loss_gaps": checks.fit_loss_gaps(
                    cell, env.x, env.y, runs).tolist(),
                "label_gap_by_margin": label_look(refs, runs)}
            if look:
                line["look"]["campaigns"] = campaign_look(refs, runs)
            if i < control:
                line["control"] = checks.control_readings(
                    cell, env.x, env.y, runs, refs=refs)
                line["look"]["control_fit_loss_gaps"] = \
                    checks.fit_loss_gaps(cell, env.x, env.y, runs,
                                         dtype="bfloat16").tolist()
                line["look"]["control_label_gap_by_margin"] = \
                    label_look(refs, runs, "bfloat16")
            print(json.dumps(line), flush=True)
            if out is not None:
                out.write(json.dumps(line) + "\n")
                out.flush()
    finally:
        env.close()


def main(argv=None) -> int:
    from bench import harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--look", type=int, default=0,
                    help="also read each campaign of the sound windows")
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", default="",
                    help="the seeds of the fault readings (default --seeds)")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    cell = harness.load_cell(args.workload)
    try:
        harness.device_check(cell.chips)
    except harness.NoDevice as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 3
    import jax
    from repro.launch.cache import enable_compile_cache
    from bench import faults
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    seeds = [int(s) for s in args.seeds.split(",")]
    fault_seeds = [int(s) for s in
                   (args.fault_seeds or args.seeds).split(",")]
    out = open(args.out, "a") if args.out else contextlib.nullcontext()
    with out:
        sink = out if args.out else None
        read_seeds(harness, cell, seeds, args.seconds, args.control, "",
                   sink, look=bool(args.look))
        for fault in filter(None, args.faults.split(",")):
            with faults.plant(fault):
                read_seeds(harness, cell, fault_seeds, args.seconds, 0,
                           fault, sink)
    return 0


if __name__ == "__main__":
    sys.exit(main())
