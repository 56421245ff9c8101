"""The program's ``gather`` and ``search`` spans in one traced campaign of
the small cell on the CPU: the readers of the new per-layer metrics find
them, they fit inside the campaign loop's self time, and no gather nests
under the ``fit`` or ``sweep`` spans whose extents the older metrics
read; the program's counters, which reach the readers per campaign
(``CampaignRun.counters``); and the seconds that ``mfu`` divides by."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import harness  # noqa: E402


def test_gather_and_search_spans_sit_in_the_loop(small, monkeypatch,
                                                 tmp_path):
    import repro.obs
    from repro.trace import TraceStore
    from repro.trace.store import read_trace

    stores = []

    class Registry(repro.obs.MetricsRegistry):
        def __init__(self, **kw):
            super().__init__(**kw)
            stores.append(TraceStore(str(tmp_path / f"{len(stores)}.jsonl")))
            self.attach_trace(stores[-1])

    monkeypatch.setattr(repro.obs, "MetricsRegistry", Registry)
    cell = harness.load_cell("cifar10-r18feat.margin")
    env = harness.Env(cell, 2 ** 31 + 23, trace=True)
    try:
        run = env.run_campaign(2 ** 31 + 23)
    finally:
        env.close()
    assert run.committed, run.error
    assert {"gather", "search"} <= set(run.spans)

    data = {"runs": [run], "profiled": run}
    read = {m: harness.load_module("metrics", m).read(data)
            for m in ("gather_s", "search_s", "loop_self_s")}
    assert read["gather_s"] > 0 and read["search_s"] > 0, read
    assert read["gather_s"] + read["search_s"] <= read["loop_self_s"], read

    store, = stores
    store.close()
    paths = [e.payload["path"] for e in read_trace(store.path)
             if e.kind == "metric_span"]
    gathers = [p for p in paths if p.endswith("gather")]
    assert {p.split("/")[0] for p in gathers} == {"bootstrap", "iteration",
                                                   "commit"}, gathers
    assert not [p for p in gathers
                if {"fit", "sweep"} & set(p.split("/"))], gathers
    assert all(p.endswith("iteration/search") for p in paths
               if p.endswith("search")), paths


def test_counters_reach_the_metric_readers(small, tmp_path):
    from repro.obs.export import snapshot_counter
    cell = harness.load_cell("cifar10-r18feat.margin")
    env = harness.Env(cell, 2 ** 31 + 29, trace=True)
    try:
        run = env.run_campaign(2 ** 31 + 29)
    finally:
        env.close()
    assert run.committed, run.error
    # one count per loop iteration: every step but the bootstrap and the
    # commit
    assert snapshot_counter({"counters": run.counters},
                            "campaign_iterations_total") == \
        len(run.steps_s) - 2
    assert len(run.steps_s) > 2

    # a reader that a later cell adds as a new file reads it
    (tmp_path / "bench" / "metrics").mkdir(parents=True)
    (tmp_path / "bench" / "metrics" / "iterations.py").write_text(
        "def read(data):\n"
        "    runs = data['runs']\n"
        "    return sum(c['value'] for r in runs for c in r.counters\n"
        "               if c['name'] == 'campaign_iterations_total')"
        " / len(runs)\n")
    reader = harness.load_module("metrics", "iterations", str(tmp_path))
    assert reader.read({"runs": [run], "profiled": run}) == \
        len(run.steps_s) - 2


def test_mfu_leaves_out_the_time_between_campaigns():
    """The profiler's stop after the first traced campaign lies outside
    every campaign: it moves the window's seconds, not ``mfu``."""
    cell = harness.load_cell("cifar10-r18feat.margin")
    run = harness.CampaignRun(seed=1, t0=0.0, t1=2.0,
                              T_idx=np.arange(2500), train_sizes=[500, 1000],
                              labels=np.zeros(1, np.int64))
    data = {"cell": cell, "runs": [run], "chips": 1, "window_s": 50.0,
            "peak": {"bf16_flops": 1e12}}
    reader = harness.load_module("metrics", "mfu")
    from bench.layers import campaign_flops
    assert reader.read(data) == pytest.approx(
        100.0 * campaign_flops(run, cell) / (2.0 * 1e12))
    data["window_s"] = 4.0
    assert reader.read(data) == pytest.approx(
        100.0 * campaign_flops(run, cell) / (2.0 * 1e12))
