"""A run with a fault planted beneath it reads ``correct`` false, and the
control (the reference in bfloat16 in the program's place) reads above
the limits.  The harness runs as on the chip, on the CPU at a small size
(a 4,000-row pool of 32-wide features at difficulty 0.3, a 64-wide
labeler trained for 4 epochs); the look for a chip is stepped past here, in the test, and so is
the warming of every bucket ahead of the warm-up campaign."""
import contextlib
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import faults, harness  # noqa: E402
from conftest import fake_device  # noqa: E402

def run(capsys, workload, seed, fault=None):
    planted = faults.plant(fault) if fault else contextlib.nullcontext()
    with planted:
        rc = harness.main(["--workload", workload, "--seed", str(seed),
                           "--seconds", "0", "--trace", "0"],
                          check_device=fake_device)
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


@pytest.mark.parametrize("workload", ["cifar10-r18feat.margin"])
def test_sound_run_is_correct(small, capsys, workload):
    result = run(capsys, workload, 2 ** 31 + 11)
    assert result["correct"], result["checks"]
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("workload,fault,number", [
    ("cifar10-r18feat.margin", "unchanged", "label_gap"),
    ("cifar10-r18feat.margin", "half_batch", "fit_step1_loss_gap"),
    ("cifar10-r18feat.margin", "answer", "pool_error"),
    ("cifar10-r18feat.margin", "answer", "label_gap"),
    ("cifar10-r18feat.margin", "last_page", "pool_error"),
])
def test_planted_fault_reads_not_correct(small, capsys, workload, fault,
                                         number):
    result = run(capsys, workload, 2 ** 31 + 11, fault)
    assert not result["correct"]
    got = result["checks"][number]
    assert got["value"] > got["limit"], result["checks"]
