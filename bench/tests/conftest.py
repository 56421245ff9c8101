"""Shared by the benchmark's tests: the small cell that the fault and
control tests run on the CPU."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import harness  # noqa: E402

REAL_LOAD = harness.load_cell


def small_cell(name, root=harness.ROOT):
    cell = REAL_LOAD(name, root)
    # an easy 32-wide pool, so that a sound campaign this small stays well
    # inside the accuracy target that `pool_error` holds it to
    cell.config.update(pool=4000, features=32, difficulty=0.3)
    cell.config["labeler"].update(hidden=64, epochs=4)
    return cell


def fake_device(chips):
    return {"platform": "cpu", "kind": "TPU v5 lite", "count": chips}


@pytest.fixture
def small(monkeypatch):
    import repro.launch.cache
    monkeypatch.setattr(harness, "load_cell", small_cell)
    monkeypatch.setattr(repro.launch.cache, "enable_compile_cache",
                        lambda: "")
    # the warm-up campaign compiles what the window uses; warming every
    # bucket ahead of it only makes the test longer
    monkeypatch.setattr(harness.Env, "warm", lambda self: None)


