"""LiveTask's paged sweeps over a :class:`RowView` of their rows.

Every pool pass that goes through the sweep runner (top-k M(.), the
L(.)/commit rank with its checkpoint cuts, k-center features, anchors and
the async submits) gathers its rows a page at a time from a view instead
of a materialised ``features[idx]``.  Each must be bit-equal to the same
runner over the materialised rows, and no sweep may gather more than one
page of rows at once.
"""
import numpy as np
import pytest

from repro.core import LiveTask
from repro.core.selection_device import k_center_greedy_device
from repro.core.task import RowView
from repro.data.synth import make_classification
from repro.serving.sweep import (FeatureSink, RankTop1Sink, SweepCheckpoint,
                                 TopKSink)

POOL = 1500
LABELED = np.arange(200)
K = 24


def _task(sweep_page: int) -> LiveTask:
    x, y = make_classification(POOL, num_classes=10, dim=16,
                               difficulty=0.3, seed=5)
    task = LiveTask(features=x, groundtruth=y, num_classes=10, epochs=2,
                    seed=5, sweep_page=sweep_page, score_microbatch=64)
    task.train(LABELED, y[LABELED])
    return task


@pytest.fixture(scope="module", params=[64, 256, 1000])
def paged_task(request):
    task = _task(request.param)
    yield task
    task.close()


def _candidates(order: str) -> np.ndarray:
    cand = np.arange(len(LABELED), POOL)
    if order == "shuffled":
        cand = np.random.default_rng(11).permutation(cand)[:1100]
    return cand


def _materialised(task, idx, sink):
    """The same runner over the rows gathered whole, as before views."""
    rows = task.features[np.asarray(idx, np.int64)].astype(np.float32)
    return task._sweep.run(task._params, rows, sink)


def _kcenter_oracle(task, cand, anchors):
    feats = _materialised(task, cand, FeatureSink())
    rows = k_center_greedy_device(feats, K, anchors=anchors)
    return cand[rows], np.asarray(feats[np.asarray(rows)], np.float32)


def _assert_equal(got, want):
    if isinstance(want, tuple):
        assert len(got) == len(want)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    else:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


PASSES = ("topk", "machine_label", "anchors", "kcenter", "submit_margin",
          "submit_kcenter")


@pytest.mark.parametrize("order", ["sorted", "shuffled"])
@pytest.mark.parametrize("sweep_pass", PASSES)
def test_paged_pass_matches_materialised_rows(paged_task, sweep_pass, order):
    task = paged_task
    cand = _candidates(order)
    anchors = task.anchor_features(LABELED)
    if sweep_pass == "topk":
        got = task.topk_candidates("margin", K, cand)
        want = cand[_materialised(task, cand, TopKSink(K, "margin"))]
    elif sweep_pass == "machine_label":
        got = task.machine_label_sweep(cand, "margin")
        want = _materialised(task, cand, RankTop1Sink("margin"))
    elif sweep_pass == "anchors":
        got = task.anchor_features(cand)
        want = np.asarray(_materialised(task, cand, FeatureSink()),
                          np.float32)
    elif sweep_pass == "kcenter":
        got = task.kcenter_candidates(K, cand, anchors)
        want = _kcenter_oracle(task, cand, anchors)
    elif sweep_pass == "submit_margin":
        got = task.submit_candidates("margin", K, cand).result(timeout=120)
        want = cand[_materialised(task, cand, TopKSink(K, "margin"))]
    else:
        got = task.submit_candidates("kcenter", K, cand,
                                     anchors).result(timeout=120)
        want = _kcenter_oracle(task, cand, anchors)
    _assert_equal(got, want)


def test_submitted_sweep_keeps_its_own_candidates(paged_task):
    """The view copies ``idx``: overwriting the caller's array after the
    submit does not change the rows the worker sweeps."""
    task = paged_task
    cand = _candidates("shuffled")
    want = task.topk_candidates("margin", K, cand)
    mine = cand.copy()
    fut = task.submit_candidates("margin", K, mine)
    mine[:] = 0
    np.testing.assert_array_equal(fut.result(timeout=120), want)


@pytest.mark.parametrize("order", ["sorted", "shuffled"])
def test_machine_label_checkpoints_match_uninterrupted(order):
    """``checkpoint_every=1`` cuts a cursor after every page but the last;
    the cut sweep and a resume from each of its cuts equal one sweep."""
    task = _task(256)
    try:
        cand = _candidates(order)
        full = task.machine_label_sweep(cand, "margin")
        cuts = []
        cut = task.machine_label_sweep(
            cand, "margin", checkpoint_every=1,
            on_checkpoint=lambda c: cuts.append(c.to_json()))
        _assert_equal(cut, full)
        assert len(cuts) == -(-len(cand) // 256) - 1
        for blob in (cuts[0], cuts[len(cuts) // 2], cuts[-1]):
            resumed = task.machine_label_sweep(
                cand, "margin", checkpoint=SweepCheckpoint.from_json(blob))
            _assert_equal(resumed, full)
    finally:
        task.close()


def test_no_sweep_gathers_more_than_a_page(paged_task, monkeypatch):
    """A spy on the view's gather: every sweep reads its rows in slices of
    at most ``sweep_page``, covering the candidates exactly once, and
    never through the whole-set gather ``_rows``."""
    task = paged_task
    page = task.sweep_page
    cand = _candidates("shuffled")
    reads, whole = [], []
    gather = RowView.__getitem__

    def spy(view, rows):
        out = gather(view, rows)
        reads.append(len(out))
        return out

    monkeypatch.setattr(RowView, "__getitem__", spy)
    monkeypatch.setattr(task, "_rows",
                        lambda idx: whole.append(len(idx)) or None)
    calls = (lambda: task.topk_candidates("margin", K, cand),
             lambda: task.machine_label_sweep(cand, "margin"),
             lambda: task.anchor_features(cand),
             lambda: task.kcenter_candidates(K, cand),
             lambda: task.submit_candidates("margin", K, cand).result(
                 timeout=120))
    for call in calls:
        reads.clear()
        call()
        assert reads and max(reads) <= page, reads
        assert sum(reads) == len(cand), reads
    assert not whole, whole


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_row_view_matches_gathered_rows(dtype):
    x = np.random.default_rng(3).normal(size=(300, 7)).astype(dtype)
    idx = np.random.default_rng(4).permutation(300)[:170]
    view = RowView(x, idx)
    want = x[idx].astype(np.float32)
    assert view.shape == want.shape and len(view) == len(want)
    assert view.dtype == np.float32
    for lo, hi in ((0, 64), (64, 128), (128, 170), (150, 400), (0, 170)):
        got = view[lo:hi]
        assert got.dtype == np.float32
        np.testing.assert_array_equal(got, want[lo:hi])
    np.testing.assert_array_equal(np.asarray(view[:]), want)
