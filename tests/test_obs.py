"""Runtime metrics & profiling layer (src/repro/obs/).

Unit level: log-bucket histogram boundaries, registry thread safety
under concurrent rounds, span nesting + exception unwinding, bound
(per-tenant) label merging, Prometheus exposition format.

System level: a fully instrumented noisy emulated campaign must make
byte-identical decisions to its metrics-off sibling (``trace.diff``
clean — metric events are observability kinds), disabled mode
(``metrics=None``) is the identity on every instrumented site, and
``launch/report.py --metrics`` renders the per-engine panel for a solo
campaign AND an N=4 tenant fleet from recorded telemetry alone.
"""
import json
import math
import os
import threading
import time

import numpy as np
import pytest

from repro.obs import (DEFAULT_BUCKETS, MetricsRegistry, log_buckets,
                       prometheus_lines, profile_block, cache_hit_rates,
                       queue_stats, span_rollup)
from repro.obs.metrics import _Hist


# ---------------------------------------------------------------------------
# histogram buckets
# ---------------------------------------------------------------------------


def test_log_buckets_cover_range_log_spaced():
    b = log_buckets(1e-3, 10.0, per_decade=2)
    assert b[0] == pytest.approx(1e-3)
    assert b[-1] >= 10.0
    ratios = [b[i + 1] / b[i] for i in range(len(b) - 1)]
    assert all(r == pytest.approx(math.sqrt(10.0)) for r in ratios)
    with pytest.raises(ValueError):
        log_buckets(0.0, 1.0)
    with pytest.raises(ValueError):
        log_buckets(2.0, 1.0)


def test_histogram_bucket_boundaries():
    h = _Hist((1.0, 10.0, 100.0))
    # upper-edge inclusive: v <= bounds[i] lands in bucket i
    for v, slot in ((0.5, 0), (1.0, 0), (1.0001, 1), (10.0, 1),
                    (99.0, 2), (100.0, 2), (101.0, 3), (1e9, 3)):
        before = list(h.counts)
        h.observe(v)
        assert h.counts[slot] == before[slot] + 1, (v, slot)
    assert h.count == 8
    assert h.min == 0.5 and h.max == 1e9
    assert h.sum == pytest.approx(0.5 + 1.0 + 1.0001 + 10.0 + 99.0
                                  + 100.0 + 101.0 + 1e9)
    # bounded memory: bucket count never grows with observations
    assert len(h.counts) == 4


def test_histogram_empty_minmax_null():
    d = _Hist((1.0,)).to_dict()
    assert d["min"] is None and d["max"] is None and d["count"] == 0


# ---------------------------------------------------------------------------
# registry: counters/gauges/labels/thread safety
# ---------------------------------------------------------------------------


def test_counters_gauges_label_keyed():
    m = MetricsRegistry()
    m.inc("hits_total", engine="scoring")
    m.inc("hits_total", 2.0, engine="fit")
    m.inc("hits_total", engine="scoring")
    m.set_gauge("depth", 3.0, queue="ann")
    assert m.add_gauge("depth", -1.0, queue="ann") == 2.0
    snap = m.snapshot()
    vals = {tuple(sorted(c["labels"].items())): c["value"]
            for c in snap["counters"] if c["name"] == "hits_total"}
    assert vals[(("engine", "scoring"),)] == 2.0
    assert vals[(("engine", "fit"),)] == 2.0
    assert snap["gauges"][0]["value"] == 2.0


def test_label_name_cannot_collide_with_metric_params():
    # spans label their histogram rows name=<span name>; the registry's
    # positional-only params must not swallow such labels
    m = MetricsRegistry()
    m.inc("c_total", 1.0, name="x", value="y")
    m.observe("span_seconds", 0.5, name="sweep")
    snap = m.snapshot()
    assert snap["counters"][0]["labels"] == {"name": "x", "value": "y"}
    assert snap["histograms"][0]["labels"] == {"name": "sweep"}


def test_registry_thread_safety_under_concurrent_rounds():
    m = MetricsRegistry()
    threads, per, n = 8, 500, []

    def tenant_round(t):
        with m.bind(tenant=f"t{t}"):
            for i in range(per):
                m.inc("iters_total")
                m.observe("lat", i * 1e-4)
                m.add_gauge("depth", 1)
                m.add_gauge("depth", -1)

    ths = [threading.Thread(target=tenant_round, args=(t,))
           for t in range(threads)]
    for th in ths:
        th.start()
    for th in ths:
        th.join()
    snap = m.snapshot()
    counters = [c for c in snap["counters"] if c["name"] == "iters_total"]
    assert len(counters) == threads             # one series per tenant
    assert sum(c["value"] for c in counters) == threads * per
    hists = [h for h in snap["histograms"] if h["name"] == "lat"]
    assert sum(h["count"] for h in hists) == threads * per
    gauges = [g for g in snap["gauges"] if g["name"] == "depth"]
    assert all(g["value"] == 0.0 for g in gauges)   # balanced +1/-1


def test_bind_merges_and_explicit_labels_win():
    m = MetricsRegistry()
    with m.bind(tenant="t0", engine="fleet"):
        m.inc("x_total", engine="fit")   # explicit engine wins
    m.inc("x_total", engine="fit")       # outside bind: no tenant label
    snap = m.snapshot()
    labels = sorted(tuple(sorted(c["labels"].items()))
                    for c in snap["counters"])
    assert labels == [(("engine", "fit"),),
                      (("engine", "fit"), ("tenant", "t0"))]


# ---------------------------------------------------------------------------
# spans: nesting, exception unwinding, decorator
# ---------------------------------------------------------------------------


class _FakeTrace:
    def __init__(self):
        self.events = []

    def emit(self, kind, **payload):
        self.events.append({"kind": kind, "payload": payload})


def test_span_nesting_paths():
    m = MetricsRegistry()
    tr = _FakeTrace()
    m.attach_trace(tr)
    with m.span("round"):
        with m.span("iteration"):
            with m.span("sweep"):
                pass
        with m.span("fit"):
            pass
    paths = [e["payload"]["path"] for e in tr.events]
    assert paths == ["round/iteration/sweep", "round/iteration",
                     "round/fit", "round"]


def test_span_exception_unwinds_stack_and_reraises():
    m = MetricsRegistry()
    tr = _FakeTrace()
    m.attach_trace(tr)
    with pytest.raises(ValueError, match="boom"):
        with m.span("outer"):
            with m.span("inner"):
                raise ValueError("boom")
    assert [e["payload"]["status"] for e in tr.events] == ["error", "error"]
    snap = m.snapshot()
    errs = {c["labels"]["name"]: c["value"] for c in snap["counters"]
            if c["name"] == "span_errors_total"}
    assert errs == {"inner": 1.0, "outer": 1.0}
    # the stack unwound: a fresh span is top-level again
    with m.span("clean"):
        pass
    assert tr.events[-1]["payload"]["path"] == "clean"


def test_span_decorator_and_fence():
    import jax.numpy as jnp

    m = MetricsRegistry()

    @m.span("scored")
    def score(x):
        return x * 2

    assert score(3) == 6
    with m.span("fenced") as sp:
        sp.fence(jnp.arange(8) * 2.0)
    snap = m.snapshot()
    names = {h["labels"]["name"] for h in snap["histograms"]
             if h["name"] == "span_seconds"}
    assert names == {"scored", "fenced"}


def test_span_timing_is_wall_clock():
    m = MetricsRegistry()
    with m.span("nap"):
        time.sleep(0.02)
    h = m.snapshot()["histograms"][0]
    assert h["min"] >= 0.02


# ---------------------------------------------------------------------------
# exports: prometheus + profile_block
# ---------------------------------------------------------------------------


def test_prometheus_exposition_format(tmp_path):
    m = MetricsRegistry(buckets=(0.1, 1.0))
    m.inc("labels_total", 3.0, engine="fit")
    m.set_gauge("depth", 2.0)
    m.observe("lat_seconds", 0.05)
    m.observe("lat_seconds", 5.0)
    lines = prometheus_lines(m.snapshot())
    assert "# TYPE repro_labels_total counter" in lines
    assert 'repro_labels_total{engine="fit"} 3.0' in lines
    assert "repro_depth 2.0" in lines
    # cumulative buckets + overflow +Inf == count
    assert "repro_lat_seconds_bucket{le=\"0.1\"} 1" in lines
    assert "repro_lat_seconds_bucket{le=\"+Inf\"} 2" in lines
    assert "repro_lat_seconds_count 2" in lines
    p = tmp_path / "m.prom"
    m.write_prometheus(str(p))
    assert p.read_text().splitlines() == lines
    assert not os.path.exists(str(p) + ".tmp")   # atomic rename


def test_profile_block_disabled_and_exception_transparent(tmp_path):
    with profile_block("", enabled=True) as on:
        assert on is False
    with profile_block(str(tmp_path), enabled=False) as on:
        assert on is False
    with pytest.raises(RuntimeError, match="body"):
        with profile_block("", enabled=True):
            raise RuntimeError("body")


# ---------------------------------------------------------------------------
# campaign level: disabled-mode identity + replay diff stays clean
# ---------------------------------------------------------------------------


def _campaign_run(path, metrics=None):
    from repro.annotation import make_annotation_service
    from repro.core import AMAZON, MCALConfig, make_emulated_task
    from repro.core.mcal import MCALCampaign
    from repro.trace import TraceStore

    ann = make_annotation_service(
        10, noise=0.2, repeats=3, max_repeats=5, adaptive=True,
        aggregator="ds", pricing=AMAZON, seed=0)
    task = make_emulated_task("cifar10", "resnet18", seed=0,
                              pool_size=4000, sweep_page=512)
    task.annotation = ann
    cfg = MCALConfig(seed=0, label_quality=ann.expected_quality())
    camp = MCALCampaign(task, AMAZON, cfg)
    with TraceStore(str(path), "obs-noisy-s0") as tr:
        camp.attach_trace(tr)
        if metrics is not None:
            metrics.attach_trace(tr)
            camp.attach_metrics(metrics)
        res = camp.run()
        if metrics is not None:
            metrics.emit_snapshot(scope="test")
    return res


@pytest.fixture(scope="module")
def sibling_runs(tmp_path_factory):
    """The same noisy campaign twice: metrics off, then fully
    instrumented (metric events interleaved into the trace)."""
    d = tmp_path_factory.mktemp("obs")
    off, on = d / "off.jsonl", d / "on.jsonl"
    res_off = _campaign_run(off)
    m = MetricsRegistry()
    res_on = _campaign_run(on, m)
    return {"off": (str(off), res_off), "on": (str(on), res_on),
            "registry": m}


def test_metrics_do_not_change_decisions(sibling_runs):
    _, res_off = sibling_runs["off"]
    _, res_on = sibling_runs["on"]
    assert res_on.total_cost == res_off.total_cost
    assert res_on.decision == res_off.decision
    assert len(res_on.history) == len(res_off.history)
    for got, want in zip(res_on.history, res_off.history):
        assert got.to_dict() == want.to_dict()


def test_replay_diff_clean_between_instrumented_and_not(sibling_runs):
    from repro.trace import diff, replay
    p_off, _ = sibling_runs["off"]
    p_on, res_on = sibling_runs["on"]
    assert diff(p_off, p_on) is None
    # and the interleaved trace still replays to the live result
    rp = replay(p_on)
    assert rp.total_cost == res_on.total_cost
    assert len(rp.history) == len(res_on.history)


def test_metric_events_are_observability_kinds(sibling_runs):
    from repro.trace.replay import OBSERVABILITY_KINDS, REPLAY_KINDS
    from repro.trace.store import read_trace
    assert {"metric_span", "metric_snapshot"} <= OBSERVABILITY_KINDS
    assert not {"metric_span", "metric_snapshot"} & REPLAY_KINDS
    p_on, _ = sibling_runs["on"]
    kinds = {e.kind for e in read_trace(p_on)}
    assert {"metric_span", "metric_snapshot"} <= kinds


def test_registry_saw_every_campaign_site(sibling_runs):
    snap = sibling_runs["registry"].snapshot()
    counters = {c["name"] for c in snap["counters"]}
    assert {"annotation_labels_total", "annotation_votes_total",
            "annotation_agg_rounds_total", "campaign_iterations_total",
            "pack_cache_hits_total", "pack_cache_misses_total"} <= counters
    spans = {h["labels"]["name"] for h in snap["histograms"]
             if h["name"] == "span_seconds"}
    assert {"bootstrap", "iteration", "commit", "annotate"} <= spans


def test_disabled_mode_is_identity_on_engine_sites():
    # every instrumented site guards on `metrics is None`; spot-check the
    # device selection engine end to end (cheap) — same indices with and
    # without a registry
    from repro.core.selection_device import k_center_greedy_device
    rng = np.random.default_rng(0)
    X = rng.integers(0, 16, (128, 8)).astype(np.float32)
    m = MetricsRegistry()
    a = k_center_greedy_device(X, 10)
    b = k_center_greedy_device(X, 10, metrics=m)
    np.testing.assert_array_equal(a, b)
    spans = [h for h in m.snapshot()["histograms"]
             if h["name"] == "span_seconds"]
    assert spans and spans[0]["labels"]["name"] == "kcenter"


# ---------------------------------------------------------------------------
# spans on the profiler's clock
# ---------------------------------------------------------------------------


def test_spans_write_nested_profiler_annotations(tmp_path):
    import glob

    import jax
    from jax.profiler import ProfileData

    m = MetricsRegistry()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with m.span("outer"):
            with m.span("inner") as sp:
                sp.fence(jax.numpy.arange(8) * 2.0)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("repro:"):
                        host[e.name] = (e.start_ns,
                                        e.start_ns + e.duration_ns)
    assert set(host) == {"repro:outer", "repro:inner"}
    (o0, o1), (i0, i1) = host["repro:outer"], host["repro:inner"]
    assert o0 <= i0 < i1 <= o1


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: records what
    opens and closes."""

    def __init__(self):
        self.log = []

    def __call__(self, name):
        log = self.log

        class Annotation:
            def __enter__(self):
                log.append(("open", name))

            def __exit__(self, etype, *exc):
                log.append(("close", name, etype))

        return Annotation()


def test_span_that_raises_closes_its_annotation(monkeypatch):
    import jax

    ann = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", ann)
    m = MetricsRegistry()
    with pytest.raises(ValueError, match="boom"):
        with m.span("outer"):
            with m.span("inner"):
                raise ValueError("boom")
    assert ann.log == [("open", "repro:outer"), ("open", "repro:inner"),
                       ("close", "repro:inner", ValueError),
                       ("close", "repro:outer", ValueError)]


def test_disabled_mode_builds_no_annotation(monkeypatch):
    import jax
    from repro.core.selection_device import k_center_greedy_device

    def refuse(name):
        raise AssertionError(f"annotation {name} built with metrics off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    X = np.random.default_rng(0).integers(0, 16, (128, 8)).astype(np.float32)
    k_center_greedy_device(X, 10)
    # and the patch is live: with a registry the same site builds one
    with pytest.raises(AssertionError, match="repro:kcenter"):
        k_center_greedy_device(X, 10, metrics=MetricsRegistry())


# ---------------------------------------------------------------------------
# report --metrics: solo + fleet, from recorded telemetry alone
# ---------------------------------------------------------------------------


def test_report_metrics_panel_solo(sibling_runs, capsys):
    from repro.launch import report
    p_on, _ = sibling_runs["on"]
    report.main([p_on, "--metrics"])
    out = capsys.readouterr().out
    assert "== metrics ==" in out
    assert "iteration" in out and "annotate" in out
    assert "compile cache:" in out
    # and the JSON view carries the rollup + raw snapshot
    report.main([p_on, "--metrics", "--json"])
    blob = json.loads(capsys.readouterr().out)
    assert blob["metrics"]["spans"]
    assert blob["metrics"]["snapshot"]["counters"]


@pytest.fixture(scope="module")
def fleet_dir(tmp_path_factory):
    """An instrumented N=4 tenant fleet over shared engines: tenant
    traces + standalone metrics.jsonl + metrics.prom in one dir."""
    from repro.core import AMAZON, MCALConfig
    from repro.core.tenant import TenantSpec
    from repro.data.synth import make_classification
    from repro.launch.orchestrator import build_fleet

    d = str(tmp_path_factory.mktemp("fleet"))
    x, y = make_classification(400, num_classes=4, difficulty=0.3, seed=0)
    specs = [TenantSpec(f"t{i}", priority=i % 2, seed=i,
                        cfg=MCALConfig(seed=i, max_iters=2,
                                       delta0_frac=0.1, test_frac=0.2))
             for i in range(4)]
    m = MetricsRegistry()
    orch = build_fleet(x, y, specs, service=AMAZON, trace_dir=d,
                       concurrent=True, metrics=m,
                       engine_kw=dict(epochs=2, score_microbatch=128,
                                      sweep_page=128))
    try:
        orch.run()
    finally:
        m.write_prometheus(os.path.join(d, "metrics.prom"))
        orch.close()
    return d


def test_fleet_metrics_stream_separate_and_attributed(fleet_dir):
    from repro.trace.store import read_trace
    assert os.path.exists(os.path.join(fleet_dir, "metrics.jsonl"))
    events = read_trace(os.path.join(fleet_dir, "metrics.jsonl"))
    assert events and all(e.kind in ("metric_span", "metric_snapshot")
                          for e in events)
    roll = span_rollup(events)
    tenants = {t for (_, t) in roll if t}
    assert tenants == {"t0", "t1", "t2", "t3"}   # per-tenant attribution
    # every tenant's round + engine time shows up
    assert all(("round", f"t{i}") in roll for i in range(4))
    # the final fleet snapshot carries cache hits + compiled-program gauges
    snap = [e.payload["snapshot"] for e in events
            if e.kind == "metric_snapshot"][-1]
    rates = cache_hit_rates(snap)
    assert "scoring" in rates and rates["scoring"]["hits"] > 0
    gauges = {g["name"] for g in snap["gauges"]}
    assert "compiled_programs" in gauges


def test_tenant_decision_streams_stay_clean_under_metrics(fleet_dir,
                                                          tmp_path):
    # a metrics-off solo campaign with tenant t0's config must diff
    # clean against the instrumented fleet's t0 trace
    from repro.core import AMAZON, MCALConfig
    from repro.core.mcal import MCALCampaign
    from repro.core.task import LiveTask
    from repro.data.synth import make_classification
    from repro.trace import TraceStore, diff

    x, y = make_classification(400, num_classes=4, difficulty=0.3, seed=0)
    task = LiveTask(features=x, groundtruth=y, num_classes=4, seed=0,
                    epochs=2, score_microbatch=128, sweep_page=128)
    camp = MCALCampaign(task, AMAZON,
                        MCALConfig(seed=0, max_iters=2, delta0_frac=0.1,
                                   test_frac=0.2))
    solo = tmp_path / "solo.jsonl"
    with TraceStore(str(solo), "t0") as tr:
        camp.attach_trace(tr)
        camp.run()
    assert diff(str(solo), os.path.join(fleet_dir, "t0.jsonl")) is None


def test_report_metrics_panel_fleet(fleet_dir, capsys):
    from repro.launch import report
    report.main([fleet_dir, "--metrics"])
    out = capsys.readouterr().out
    for t in ("t0", "t1", "t2", "t3"):
        assert f"campaign {t}" in out
    assert "== metrics ==" in out
    assert "tenant" in out                      # per-tenant span rows
    assert "compile cache:" in out
    # the prom snapshot is scrapeable next to the traces
    prom = open(os.path.join(fleet_dir, "metrics.prom")).read()
    assert "# TYPE repro_span_seconds histogram" in prom


def test_report_watch_tolerates_vanished_trace(sibling_runs, tmp_path):
    # the watched file appears only after the first poll: the loop must
    # re-wait instead of raising (rotated/mid-restart traces)
    import shutil

    from repro.launch import report
    p_on, _ = sibling_runs["on"]
    target = tmp_path / "late.jsonl"
    done = []

    def watcher():
        report.main([str(target), "--watch", "0.05"])
        done.append(True)

    th = threading.Thread(target=watcher)
    th.start()
    time.sleep(0.15)                 # a few failing polls
    shutil.copy(p_on, target)        # trace "rotates" into place
    th.join(timeout=30.0)
    assert done, "watch loop did not recover after the trace appeared"


def test_report_non_watch_still_raises_on_missing(tmp_path):
    from repro.launch import report
    with pytest.raises(OSError):
        report.main([str(tmp_path / "nope.jsonl")])


def test_queue_stats_rollup():
    m = MetricsRegistry()
    m.add_gauge("queue_depth", 1, queue="annotation")
    m.observe("queue_wait_seconds", 0.2, queue="annotation")
    m.observe("queue_wait_seconds", 0.4, queue="annotation")
    st = queue_stats(m.snapshot())["annotation"]
    assert st["depth"] == 1.0 and st["waits"] == 2
    assert st["wait_mean"] == pytest.approx(0.3)
    assert st["wait_max"] == pytest.approx(0.4)
