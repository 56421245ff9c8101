"""The control, the reference computed in bfloat16 in the program's
place, reads above the cell's limit on the window's own retrains (on the
CPU at the small size of
``test_bench_faults.py``)."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import checks, harness  # noqa: E402
from conftest import small_cell  # noqa: E402


@pytest.mark.parametrize("workload", ["cifar10-r18feat.margin"])
def test_control_reads_above_the_limits(small, workload):
    cell = small_cell(workload)
    env = harness.Env(cell, 17, trace=False)
    try:
        env.warm()
        runs = [env.run_campaign(s) for s in (101, 102)]
        assert all(r.committed for r in runs)
        control = checks.control_readings(cell, env.x, env.y, runs)
    finally:
        env.close()
    limits = cell.limits["numbers"]
    # the control fails the retrain's first loss; its commit sweep reads
    # in the range of sound runs (``PERF.md``)
    assert control["fit_step1_loss_gap"] > limits["fit_step1_loss_gap"], \
        (control, limits)
