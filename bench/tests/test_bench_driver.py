"""The solo driver's window: distinct campaign seeds, and no campaign
started once the window's seconds have passed."""
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402


class FakeEnv:
    def __init__(self, seconds_each):
        self.seconds_each = seconds_each
        self.started = []

    def run_campaign(self, seed):
        self.started.append(time.perf_counter())
        time.sleep(self.seconds_each)
        run = harness.CampaignRun(seed=seed)
        run.t1 = time.perf_counter()
        return run


def test_window_runs_until_its_seconds_then_finishes_in_flight():
    driver = harness.load_module("drivers", "solo")
    env = FakeEnv(0.02)
    t0 = time.perf_counter()
    runs = driver.run_window(env, harness.window_seeds(7), 0.1)
    assert len(runs) >= 4
    # every campaign but the last started before the window's end
    assert all(t - t0 < 0.1 for t in env.started)
    assert runs[-1].t1 - t0 >= 0.1


def test_window_seeds_are_distinct_for_large_seeds():
    for seed in (0, 2 ** 31 - 2, 2 ** 31 + 5, 3 * 2 ** 31):
        gen = harness.window_seeds(seed)
        seeds = [next(gen) for _ in range(1000)]
        assert len(set(seeds)) == len(seeds)
        assert all(0 <= s < 2 ** 31 - 1 for s in seeds)


def test_traced_window_profiles_only_the_first_campaign():
    driver = harness.load_module("drivers", "solo")
    entered = []

    class Ctx:
        def __enter__(self):
            entered.append(1)

        def __exit__(self, *exc):
            return False

    runs = driver.run_window(FakeEnv(0.01), harness.window_seeds(1), 0.05,
                             traced=Ctx)
    assert len(runs) >= 2 and entered == [1]
