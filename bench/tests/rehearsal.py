"""A cell's set-up and one window campaign, driven as ``bench/run.py``
drives them, with the look for a chip left out."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import checks, harness, layers  # noqa: E402
from bench.compile_stats import CompileStats  # noqa: E402

SEED = 2 ** 31 + 77


def rehearse(workload: str) -> None:
    cell = harness.load_cell(workload)
    cell.config["labeler"]["epochs"] = 4
    stats = CompileStats()
    env = harness.Env(cell, SEED, trace=True)
    try:
        seeds = harness.window_seeds(SEED)
        env.warm()
        warm = env.run_campaign(next(seeds))
        before = stats.snapshot()
        driver = harness.load_module("drivers", cell.traffic["driver"])
        runs = driver.run_window(env, seeds, 0.0)
        after = stats.snapshot()
        found = checks.run_checks(cell, env.x, env.y, runs)
    finally:
        env.close()
        stats.close()
    assert [r.decision for r in [warm] + runs] == ["hybrid"] * (1 + len(runs))
    assert len({r.seed for r in [warm] + runs}) == 1 + len(runs)
    # nothing is built inside the window
    assert after[1] == before[1], after[1] - before[1]
    for r in runs:
        wall = r.t1 - r.t0
        loop = sum(r.spans.get(n, 0.0) for n in layers.LOOP_SPANS)
        inner = sum(r.spans.get(n, 0.0) for n in layers.LAYER_SPANS)
        # the loop spans cover the campaign, and the layer spans lie
        # inside them
        assert 0.95 * wall <= loop <= wall, (loop, wall)
        assert 0.0 < inner <= loop
        assert abs(sum(r.steps_s) - loop) <= 0.05 * wall
    assert all(v["value"] <= v["limit"] for v in found.values()), found
