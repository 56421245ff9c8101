"""The reduction from trace events to busy time, idle gaps and kernel
time."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import trace_reduce as tr  # noqa: E402

# a window of 100 ns: three ops, two of them overlapping, one crossing
# the window's end; host annotations nest fit inside iteration
OPS = [("fusion.1", 10.0, 20.0), ("pairwise_sqdist.3", 25.0, 15.0),
       ("fusion.1", 60.0, 10.0), ("copy.2", 95.0, 20.0)]
HOST = [("bench:campaign", 0.0, 100.0), ("bench:iteration", 0.0, 70.0),
        ("bench:fit", 5.0, 50.0), ("bench:search", 72.0, 20.0)]


def test_union_merges_overlaps():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]


def test_busy_and_idle_share():
    # busy [10, 40) + [60, 70) + [95, 100) = 45 ns of 100
    assert tr.busy_ns(OPS, 0.0, 100.0) == 45.0
    assert tr.idle_gaps(OPS, 0.0, 100.0) == [
        (0.0, 10.0), (40.0, 60.0), (70.0, 95.0)]


def test_longest_gaps_are_labelled_by_host_activity():
    gaps = tr.longest_gaps(OPS, HOST, 0.0, 100.0, top=2)
    assert [g[0] for g in gaps] == ["search", "fit"]
    assert gaps[0][1] == pytest.approx(25e-9)
    assert gaps[1][1] == pytest.approx(20e-9)


def test_top_ops_and_kernel_time():
    top = tr.top_ops(OPS, 0.0, 100.0)
    assert top[0] == ["fusion.1", pytest.approx(30e-9)]
    assert tr.op_seconds(OPS, "pairwise_sqdist", 0.0, 100.0) == \
        pytest.approx(15e-9)
    # only the part inside the window counts
    assert tr.op_seconds(OPS, "copy", 0.0, 100.0) == pytest.approx(5e-9)


def test_window_of_names_the_annotation():
    assert tr.window_of(HOST, "bench:campaign") == (0.0, 100.0)
    with pytest.raises(KeyError):
        tr.window_of(HOST, "bench:nothing")


# -- a recorded trace ----------------------------------------------------------
# 4 ms of a traced k-center campaign (the margin cell's configuration with
# the k-center traffic) on one TPU v5 lite, around its second anchor-
# distance kernel: the device programs and operations of the chip, and
# the harness's host annotations, as load_xplane returns them

EXCERPT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "trace_excerpt.json")


def _brute_busy(events, lo, hi, step=100.0):
    """Busy nanoseconds by marking a 100 ns grid, independent of union()."""
    import numpy as np
    grid = np.zeros(int((hi - lo) / step) + 1, bool)
    for _, s, d in events:
        a = max(int(np.ceil((s - lo) / step)), 0)
        b = min(int(np.floor((s + d - lo) / step)), len(grid) - 1)
        if b >= a:
            grid[a:b + 1] = True
    return grid.sum() * step


def test_recorded_trace_reduces_like_brute_force():
    import json
    with open(EXCERPT) as f:
        rec = json.load(f)
    lo, hi = rec["window"]
    programs = [tuple(e) for e in rec["programs"]]
    ops = [tuple(e) for e in rec["ops"]]
    host = [tuple(e) for e in rec["host"]]
    busy = tr.busy_ns(programs, lo, hi)
    assert 0 < busy < hi - lo
    # agrees with the brute-force grid to its resolution, per event edge
    assert abs(busy - _brute_busy(programs, lo, hi)) <= 200.0 * (
        len(programs) + 1)
    gaps = tr.idle_gaps(programs, lo, hi)
    assert sum(e - s for s, e in gaps) == pytest.approx(hi - lo - busy)
    # the pairwise kernel runs inside a program, never outside one
    kernel = tr.op_seconds(ops, "pairwise_sqdist", lo, hi)
    assert 0 < kernel * 1e9 <= busy
    labels = {g[0] for g in tr.longest_gaps(programs, host, lo, hi)}
    assert labels <= {"kcenter", "sweep", "iteration", "campaign",
                      "search", "fit", "score", "outside"}
