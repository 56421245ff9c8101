"""Operations and bytes of the benchmark's work, from shapes alone.

A labeler's multiply-accumulates per row come from its module in
``bench/labelers`` (``macs_per_row``).  A retrain counts 6 x MACs per
row per epoch (forward 2, backward 4); scoring counts 2 x MACs per row.
Only unpadded rows count, so padding that a program adds shows as lower
utilization, not as work.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Tuple


def fit_flops(rows: int, epochs: int, macs_per_row: int) -> float:
    """Model FLOPs of one retrain over ``rows`` labeled rows."""
    return 6.0 * macs_per_row * rows * epochs


def score_flops(rows: int, macs_per_row: int) -> float:
    """Model FLOPs of one scoring pass over ``rows`` rows."""
    return 2.0 * macs_per_row * rows


def roofline_seconds(flops: float, nbytes: float,
                     peak: Dict[str, float]) -> Tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_compute = flops / peak["bf16_flops"]
    t_memory = nbytes / peak["hbm_bytes_per_s"]
    if t_compute >= t_memory:
        return t_compute, "compute"
    return t_memory, "memory"


PEAKS_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


class UnknownDevice(KeyError):
    """A device kind that the table of peaks does not hold."""


def device_peak(device_kind: str, path: str = PEAKS_PATH) -> Dict[str, float]:
    """The peaks of ``device_kind`` (as JAX reports it).  A device that is
    not in the table is an error, never a default."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r} in {path}; "
            f"known: {sorted(table)}")
    return {k: float(v) for k, v in table[device_kind].items()}
