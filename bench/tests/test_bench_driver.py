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


def test_pinned_malloc_reuses_freed_host_pages():
    """After ``pin_malloc`` a freed 16 MiB host array's pages serve the
    next one, in a fresh process whose malloc thresholds have not risen
    yet; left dynamic, the second array maps pages of its own and faults
    each one in."""
    import subprocess
    import textwrap
    code = textwrap.dedent(f"""
        import resource, sys
        import numpy as np
        sys.path.insert(0, {ROOT!r})
        from bench import harness
        harness.pin_malloc()
        a = np.ones(4 << 20, np.float32)   # 16 MiB
        del a
        f = resource.getrusage(resource.RUSAGE_SELF).ru_minflt
        b = np.ones(4 << 20, np.float32)
        print(resource.getrusage(resource.RUSAGE_SELF).ru_minflt - f)
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, check=True, timeout=120)
    assert int(out.stdout.split()[-1]) < 64, out.stdout
