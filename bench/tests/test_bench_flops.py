"""Operation and byte counts on hand-worked shapes, and the peaks table."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import flops, harness  # noqa: E402


def _mlp_config(dim, hidden, depth, classes):
    return {"features": dim, "classes": classes,
            "labeler": {"hidden": hidden, "depth": depth}}


def test_mlp_macs_per_row():
    macs = harness.load_module("labelers", "mlp").macs_per_row
    # 512 -> 512 input projection, two 512 x 512 blocks, 512 -> 10 head
    assert macs(_mlp_config(512, 512, 2, 10)) == \
        512 * 512 + 2 * 512 * 512 + 512 * 10 == 791_552
    # 3 -> 4, one 4 x 4 block, 4 -> 2: 12 + 16 + 8
    assert macs(_mlp_config(3, 4, 1, 2)) == 36


def test_fit_and_score_flops():
    # 100 rows, 2 epochs, 36 MACs a row: forward 2 + backward 4 per MAC
    assert flops.fit_flops(100, 2, 36) == 6 * 36 * 100 * 2 == 43_200
    assert flops.score_flops(100, 36) == 2 * 36 * 100 == 7_200


def test_roofline_seconds():
    # 48 FLOPs and 104 bytes: compute-bound on a chip whose compute is slow
    # next to its memory, memory-bound on a v5e
    t, bound = flops.roofline_seconds(
        48.0, 104.0, {"bf16_flops": 10.0, "hbm_bytes_per_s": 1000.0})
    assert bound == "compute" and t == pytest.approx(4.8)
    t, bound = flops.roofline_seconds(1024.0, 4100.0,
                                      flops.device_peak("TPU v5 lite"))
    assert bound == "memory" and t == pytest.approx(4100.0 / 819e9)


def test_peaks_known_device():
    peak = flops.device_peak("TPU v5 lite")
    assert peak["bf16_flops"] == 197e12
    assert peak["int8_ops"] == 393e12
    assert peak["hbm_bytes_per_s"] == 819e9
    assert peak["hbm_bytes"] == 16e9


def test_peaks_unknown_device_is_an_error():
    with pytest.raises(flops.UnknownDevice):
        flops.device_peak("TPU v99")
    with pytest.raises(flops.UnknownDevice):
        flops.device_peak("cpu")
