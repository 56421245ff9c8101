"""The program's ``gather`` and ``search`` spans in one traced campaign of
the small cell on the CPU: the readers of the new per-layer metrics find
them, they fit inside the campaign loop's self time, and no gather nests
under the ``fit`` or ``sweep`` spans whose extents the older metrics
read."""
import os
import sys

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
