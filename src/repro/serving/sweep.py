"""Streaming pool-sweep runtime: paged, double-buffered scoring over the
remaining pool with async overlap and resumable cursors.

MCAL's commit step and every L(.)/M(.) pass are one inference job over the
WHOLE remaining pool (millions of samples at paper scale).  The scoring
engine (``core.scoring``) made one pool pass a single jit-compiled program,
but it still device-materializes the entire pool buffer at once and hands
pool-wide statistics back to the host.  This module is the production
runtime around that program:

* the pool stays on host and streams through the jit'd scoring step as
  **pages** — each page padded/reshaped with the exact pow2 bucketing of
  ``PoolScoringEngine._pack`` (``scoring.pack_shape``), so pages reuse the
  engine's compile cache and per-row statistics are computed by the same
  compiled program as an unpaged sweep;
* pages are **double-buffered**: the host→device transfer of page i+1 is
  enqueued while page i's compute is in flight (JAX async dispatch), and
  each page buffer is released once its page is scored — peak device
  memory is O(page), not O(pool);
* each page folds into a pluggable **sink** that keeps its running state
  device-resident, so pool-wide statistics never materialize on the host:
    - :class:`TopKSink`       M(.): top-k uncertainty reservoir
                              (``lax.top_k`` over reservoir + page),
    - :class:`RankTop1Sink`   L(.)/commit: streaming confidence-rank +
                              top1 accumulator (one score field + the
                              machine label per row is ALL that reaches
                              the host),
    - :class:`FeatureSink`    k-center anchors: device-resident (N, D)
                              pooled-feature emitter,
    - :class:`StatsSink`      packed ScoreStats (the generic deliverable,
                              ``ServeEngine.score_pool``'s default);
* the sweep carries a **resumable cursor**: :meth:`PoolSweepRunner.run_until`
  stops mid-pool and returns a JSON-serializable :class:`SweepCheckpoint`
  (page index + folded sink state); :meth:`PoolSweepRunner.run` accepts it
  and continues bit-identically to an uninterrupted sweep — preempted
  paper-scale sweeps restart mid-pool instead of re-scoring from row 0;
* :meth:`PoolSweepRunner.submit` returns a :class:`SweepFuture` — the
  sweep runs on the runner's worker thread while the caller keeps
  dispatching other work (``MCALCampaign.iteration`` launches the M(.)
  sweep and overlaps the host-side power-law fits + joint search,
  synchronizing only when the acquisition is consumed).

Oracle-test contract (tests/test_sweep.py)
------------------------------------------

Every sink must agree EXACTLY with its host/engine oracle: the top-k
reservoir with ``PoolScoringEngine.top_k`` (``lax.top_k`` over the full
pool), the streaming rank with ``selection.rank_for_machine_labeling``
over full-pool stats, the feature emitter with
``PoolScoringEngine.pool_features`` — including ragged final pages and a
mid-pool checkpoint/resume.  Two conventions make that sound (the same
reasoning as the k-center engine's contract):

* pages pack with ``scoring.pack_shape`` so every row is computed inside a
  microbatch of the SAME shape as the unpaged engine sweep — the compiled
  per-microbatch program is identical, hence per-row statistics are
  bit-equal across pagings;
* ties break by FIRST global index on both sides: the reservoir
  concatenates its (lower-index) state ahead of the page before
  ``lax.top_k`` (which prefers earlier positions on equal values), and the
  rank sink's host fold is the same stable argsort as the oracle.
"""
from __future__ import annotations

import dataclasses
import functools
import json
from types import SimpleNamespace
from typing import Any, Callable, Dict, List, Optional, Tuple

from concurrent.futures import TimeoutError as FuturesTimeout

from repro.core.worker import SerialWorker

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import selection as sel
from repro.core.scoring import (next_pow2, pack_shape, uncertainty_from_stats)
from repro.models.layers import ScoreStats

# score field each L(.)/M(.) metric actually consumes — the ONLY per-row
# float the rank sink ships to the host
_METRIC_FIELD = {"margin": "margin", "entropy": "entropy",
                 "least_confidence": "max_logprob"}


# ---------------------------------------------------------------------------
# config / cursor / async handle
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SweepConfig:
    page_rows: int = 8192   # rows per page (keep a pow2 multiple of the
                            # engine microbatch so full pages share one
                            # compiled program)
    prefetch: int = 2       # pages in flight: 2 = double-buffered (the
                            # transfer of page i+1 overlaps page i compute)


@dataclasses.dataclass
class SweepCheckpoint:
    """Resumable sweep cursor: the next page to score + the folded sink
    state, JSON-serializable so campaign checkpoints can embed it."""

    next_page: int
    n: int                  # pool rows the cursor was cut against
    page_rows: int
    sink_kind: str
    sink_state: Dict

    def to_json(self) -> str:
        # strict JSON: sinks encode non-finite sentinels themselves (e.g.
        # TopKSink's None slots) — a NaN/inf reaching here is a sink bug
        return json.dumps(dataclasses.asdict(self), allow_nan=False)

    @classmethod
    def from_json(cls, blob: str) -> "SweepCheckpoint":
        return cls(**json.loads(blob))


class SweepFuture:
    """Async sweep handle (:meth:`PoolSweepRunner.submit`).  ``result()``
    is the synchronization point — the fold the caller eventually needs.

    This is the ONE worker-handle type every async runtime shares: the
    fit engine re-exports it as ``FitFuture`` and the annotation broker
    as ``AnnotationFuture`` — hardening (cancellation semantics, mapped
    results, timeout behaviour) lands here once for all three."""

    def __init__(self, future, map_result: Optional[Callable] = None,
                 label: str = ""):
        self._future = future
        self._map = map_result
        self._label = label
        self._done_value: Any = None
        self._mapped = False

    def done(self) -> bool:
        return self._future.done()

    def cancel(self) -> bool:
        return self._future.cancel()

    def result(self, timeout: Optional[float] = None):
        """The fold.  With a ``timeout`` (seconds) the wait is a wall
        budget: a job still running when it expires raises
        :class:`~repro.faults.errors.StragglerTimeout` — the straggler
        detection the campaign's ``sweep_timeout``/``fit_timeout``
        knobs (and the launchers' ``--sweep-timeout``/``--fit-timeout``)
        arm.  The job itself keeps running on its daemon worker; the
        future stays valid for a later (longer) wait."""
        if not self._mapped:
            try:
                out = self._future.result(timeout)
            except FuturesTimeout:
                from repro.faults.errors import StragglerTimeout
                raise StragglerTimeout(
                    f"{self._label or 'worker job'} still running after "
                    f"its {timeout:g}s wall budget") from None
            self._done_value = self._map(out) if self._map else out
            self._mapped = True
        return self._done_value


# ---------------------------------------------------------------------------
# sinks — device-resident page folds
# ---------------------------------------------------------------------------
#
# Sink contract: ``init(n) -> state``; ``fold(state, stats, feats, offset,
# nvalid) -> state`` consumes one page's PACKED statistics (padded rows
# beyond ``nvalid`` must be ignored; ``offset`` is the page's global row
# offset) without forcing a host sync; ``finalize(state, n)`` produces the
# deliverable; ``serialize``/``deserialize`` round-trip the folded state
# through JSON for the sweep cursor.


@functools.partial(jax.jit, static_argnames=("metric",))
def _topk_fold(scores, idx, stats, offset, nvalid, metric):
    page = uncertainty_from_stats(stats, metric).astype(jnp.float32)
    rows = jnp.arange(page.shape[0])
    page = jnp.where(rows < nvalid, page, -jnp.inf)
    gidx = (offset + rows).astype(jnp.int32)
    # reservoir state first: its (earlier) global indices keep winning ties,
    # matching full-pool lax.top_k's first-index preference
    vals, pos = jax.lax.top_k(jnp.concatenate([scores, page]),
                              scores.shape[0])
    return vals, jnp.concatenate([idx, gidx])[pos]


class TopKSink:
    """M(.) sink: device top-k uncertainty reservoir.  Finalizes to the
    (k,) global row indices, sorted most-uncertain-first — exactly
    ``PoolScoringEngine.top_k`` without ever materializing pool-wide
    scores."""

    kind = "topk"

    def __init__(self, k: int, metric: str = "margin"):
        if metric not in _METRIC_FIELD:
            raise ValueError(f"unknown uncertainty metric {metric!r}")
        self.k = k
        self.metric = metric

    def init(self, n: int):
        k = max(min(self.k, n), 0)
        return (jnp.full((k,), -jnp.inf, jnp.float32),
                jnp.zeros((k,), jnp.int32))

    def fold(self, state, stats, feats, offset: int, nvalid: int):
        return _topk_fold(state[0], state[1], stats, offset, nvalid,
                          self.metric)

    def finalize(self, state, n: int) -> np.ndarray:
        return np.asarray(state[1], np.int64)

    def serialize(self, state) -> Dict:
        # unfilled reservoir slots hold -inf sentinels; store them as None
        # so the cursor stays strict-JSON (RFC 8259 has no -Infinity)
        scores = [None if not np.isfinite(v) else float(v)
                  for v in np.asarray(state[0], np.float64)]
        return {"k": self.k, "metric": self.metric, "scores": scores,
                "idx": np.asarray(state[1], np.int64).tolist()}

    def deserialize(self, blob: Dict):
        if blob["metric"] != self.metric or blob["k"] != self.k:
            raise ValueError(
                f"checkpoint folded TopKSink(k={blob['k']}, "
                f"metric={blob['metric']!r}); cannot resume into "
                f"TopKSink(k={self.k}, metric={self.metric!r})")
        scores = np.asarray([-np.inf if v is None else v
                             for v in blob["scores"]], np.float32)
        return (jnp.asarray(scores),
                jnp.asarray(np.asarray(blob["idx"], np.int32)))


class RankTop1Sink:
    """L(.)/commit sink: streaming confidence rank + top1 accumulator.

    Folds keep per-page device slices (no host sync on the sweep's hot
    path); finalize ships ONE score field + the top1 label per row and
    runs the oracle's own stable argsort — the machine-labeling prefix and
    its labels from a single pool pass, with none of the other statistics
    or features ever leaving the device."""

    kind = "rank"

    def __init__(self, metric: str = "margin"):
        if metric not in _METRIC_FIELD:
            raise ValueError(f"unknown uncertainty metric {metric!r}")
        self.metric = metric
        self._field = _METRIC_FIELD[metric]

    def init(self, n: int) -> List:
        return []

    def fold(self, state, stats, feats, offset: int, nvalid: int):
        state.append((getattr(stats, self._field)[:nvalid],
                      stats.top1[:nvalid]))
        return state

    def finalize(self, state, n: int) -> Tuple[np.ndarray, np.ndarray]:
        if state:
            field = np.concatenate([np.asarray(f) for f, _ in state])
            top1 = np.concatenate([np.asarray(t, np.int64) for _, t in state])
        else:
            field = np.zeros((0,), np.float32)
            top1 = np.zeros((0,), np.int64)
        scores = sel.uncertainty_scores(
            self.metric, SimpleNamespace(**{self._field: field}))
        return np.argsort(scores, kind="stable"), top1

    def serialize(self, state) -> Dict:
        field = (np.concatenate([np.asarray(f) for f, _ in state])
                 if state else np.zeros((0,), np.float32))
        top1 = (np.concatenate([np.asarray(t, np.int64) for _, t in state])
                if state else np.zeros((0,), np.int64))
        return {"metric": self.metric,
                "field": np.asarray(field, np.float64).tolist(),
                "dtype": str(field.dtype),
                "top1": top1.tolist()}

    def deserialize(self, blob: Dict) -> List:
        if blob["metric"] != self.metric:
            raise ValueError(
                f"checkpoint folded RankTop1Sink({blob['metric']!r}); "
                f"cannot resume into RankTop1Sink({self.metric!r})")
        return [(np.asarray(blob["field"], np.dtype(blob["dtype"])),
                 np.asarray(blob["top1"], np.int64))]


class FeatureSink:
    """k-center sink: device-resident (N, D) pooled-feature emitter — the
    paged twin of ``PoolScoringEngine.pool_features`` (the greedy
    farthest-point engine consumes the result without a host trip).

    Cursor caveat: serializing this sink's state materializes every folded
    feature row into the JSON blob (O(rows_swept * D) host floats) — fine
    for anchor-scale sweeps (|B| rows), disproportionate mid-pool at paper
    scale; a binary sidecar for feature cursors is the roadmap follow-on.
    """

    kind = "features"

    def init(self, n: int) -> List:
        return []

    def fold(self, state, stats, feats, offset: int, nvalid: int):
        if feats is None or feats.shape[-1] == 0:
            raise ValueError(
                "sweep adapter emits no features; build the scoring engine "
                "with ScoringConfig(with_features=True)")
        state.append(feats[:nvalid])
        return state

    def finalize(self, state, n: int) -> jax.Array:
        if not state:
            return jnp.zeros((0, 0), jnp.float32)
        return jnp.concatenate(state, axis=0)

    def serialize(self, state) -> Dict:
        feats = (np.asarray(jnp.concatenate(state, axis=0), np.float64)
                 if state else np.zeros((0, 0)))
        return {"feats": feats.tolist()}

    def deserialize(self, blob: Dict) -> List:
        feats = np.asarray(blob["feats"], np.float32)
        return [jnp.asarray(feats)] if feats.size else []


class StatsSink:
    """Generic sink: packed :class:`ScoreStats` for the whole pool, pages
    concatenated device-side and trimmed to the true pool size
    (``ServeEngine.score_pool``'s default deliverable)."""

    kind = "stats"
    _FIELDS = ("margin", "entropy", "max_logprob", "top1")

    def init(self, n: int) -> List:
        return []

    def fold(self, state, stats, feats, offset: int, nvalid: int):
        state.append(ScoreStats(*(getattr(stats, f)[:nvalid]
                                  for f in self._FIELDS)))
        return state

    def finalize(self, state, n: int) -> ScoreStats:
        if not state:
            z = jnp.zeros((0,), jnp.float32)
            return ScoreStats(z, z, z, jnp.zeros((0,), jnp.int32))
        return ScoreStats(*(jnp.concatenate([getattr(s, f) for s in state])
                            for f in self._FIELDS))

    def serialize(self, state) -> Dict:
        packed = self.finalize(state, -1)
        return {f: np.asarray(getattr(packed, f), np.float64).tolist()
                for f in self._FIELDS}

    def deserialize(self, blob: Dict) -> List:
        if not blob["margin"]:
            return []
        return [ScoreStats(
            margin=jnp.asarray(np.asarray(blob["margin"], np.float32)),
            entropy=jnp.asarray(np.asarray(blob["entropy"], np.float32)),
            max_logprob=jnp.asarray(np.asarray(blob["max_logprob"],
                                               np.float32)),
            top1=jnp.asarray(np.asarray(blob["top1"], np.int32)))]


SINKS = {s.kind: s for s in (TopKSink, RankTop1Sink, FeatureSink, StatsSink)}


# ---------------------------------------------------------------------------
# adapters — how a page becomes device work
# ---------------------------------------------------------------------------


class EngineSweepAdapter:
    """Feeds pages through a :class:`~repro.core.scoring.PoolScoringEngine`'s
    jit-compiled packed scoring step.  Pages pad/reshape on HOST with the
    engine's own pow2 bucketing (``scoring.pack_shape``) before the async
    device transfer, so every page reuses the engine's compile cache and
    per-row statistics are bit-equal to an unpaged engine sweep."""

    def __init__(self, engine):
        self.engine = engine

    def length(self, pool) -> int:
        return int(pool.shape[0])

    def put(self, pool, lo: int, hi: int):
        page = np.asarray(pool[lo:hi])
        n = hi - lo
        n_mb, mb = pack_shape(n, self.engine.cfg.microbatch)
        pad = n_mb * mb - n
        if pad:
            page = np.concatenate(
                [page, np.zeros((pad,) + page.shape[1:], page.dtype)])
        return jax.device_put(
            page.reshape((n_mb, mb) + page.shape[1:])), n

    def score(self, params, page):
        return self.engine.score_pages(params, page)


class ServeSweepAdapter:
    """Feeds pages of a row-aligned token-batch dict (``tokens`` plus any
    per-row extras: ``audio_frames``, ``patch_embeds``) through a serving
    scoring step (``ServeEngine._score``).  Ragged tail pages pad to the
    next pow2 batch so the step compiles O(log page) programs."""

    def __init__(self, score_step):
        self._step = score_step

    def length(self, pool: Dict) -> int:
        return int(next(iter(pool.values())).shape[0])

    def put(self, pool: Dict, lo: int, hi: int):
        n = hi - lo
        b = max(next_pow2(n), 8)
        page = {}
        for key, v in pool.items():
            a = np.asarray(v[lo:hi])
            if b != n:
                a = np.concatenate(
                    [a, np.zeros((b - n,) + a.shape[1:], a.dtype)])
            page[key] = jax.device_put(a)
        return page, n

    def score(self, params, page):
        return self._step(params, page), None


class HostTaskAdapter:
    """Pages an arbitrary host ``score(idx_page) -> (stats, feats)``
    callable (e.g. ``EmulatedTask.score``) through the same runtime, so
    paper-scale emulated replays share the cursor/sink machinery without a
    device in the loop.  The "pool" is the global index array itself."""

    def __init__(self, score_fn: Callable):
        self._score = score_fn

    def length(self, pool) -> int:
        return int(len(pool))

    def put(self, pool, lo: int, hi: int):
        return pool[lo:hi], hi - lo

    def score(self, params, page):
        return self._score(page)


# ---------------------------------------------------------------------------
# the runner
# ---------------------------------------------------------------------------


class PoolSweepRunner:
    """Streams an arbitrary-size pool through a scoring step as paged,
    double-buffered, sink-folded device work (module docstring has the
    full design).  One runner per (adapter, page size); a runner is
    reusable across parameter sets and pools."""

    def __init__(self, adapter, cfg: SweepConfig = SweepConfig()):
        assert cfg.page_rows > 0
        self.adapter = adapter
        self.cfg = cfg
        self._exec: Optional[SerialWorker] = None
        # campaign event bus (observability only: page cursors + sink
        # finalizations; emits may come from the runner's worker thread)
        self.trace = None
        # runtime metrics (repro.obs.MetricsRegistry); None = free no-op
        self.metrics = None
        # resilience seam: chaos injector + broker re-dispatch policy,
        # handed to the lazy SerialWorker (site ``worker.pool-sweep``)
        self.faults = None
        self.retry = None

    def attach_faults(self, faults, retry=None) -> None:
        """Wire the fault injector (and optional re-dispatch policy)
        into the runner's broker: every submitted job ticks the
        ``worker.pool-sweep`` site, and transient crashes re-dispatch."""
        self.faults = faults
        if retry is not None:
            self.retry = retry
        if self._exec is not None:
            self._exec.attach_faults(faults, retry)

    def _emit(self, kind: str, **payload) -> None:
        if self.trace is not None:
            self.trace.emit(kind, **payload)

    def n_pages(self, n: int) -> int:
        return -(-n // self.cfg.page_rows)

    # -- synchronous sweeps -------------------------------------------------

    def run(self, params, pool, sink, *,
            checkpoint: Optional[SweepCheckpoint] = None,
            checkpoint_every: int = 0,
            on_checkpoint: Optional[Callable] = None):
        """Sweep the whole pool (resuming from ``checkpoint`` if given)
        and return the sink's finalized deliverable.  With
        ``checkpoint_every``/``on_checkpoint``, a resumable cursor is cut
        every N pages and handed to the callback before sweeping on —
        callers persist it so a preempted sweep restarts mid-pool.  The
        live sink state is threaded through the cuts (serialization
        happens only for the callback's cursor, never round-trips back),
        and no cursor is cut after the final page (there is nothing left
        to resume)."""
        if self.metrics is not None:
            with self.metrics.span("sweep", sink=sink.kind):
                return self._run_sync(params, pool, sink,
                                      checkpoint=checkpoint,
                                      checkpoint_every=checkpoint_every,
                                      on_checkpoint=on_checkpoint)
        return self._run_sync(params, pool, sink, checkpoint=checkpoint,
                              checkpoint_every=checkpoint_every,
                              on_checkpoint=on_checkpoint)

    def _run_sync(self, params, pool, sink, *,
                  checkpoint: Optional[SweepCheckpoint] = None,
                  checkpoint_every: int = 0,
                  on_checkpoint: Optional[Callable] = None):
        n = self.adapter.length(pool)
        n_pages = self.n_pages(n)
        start, state = self._restore(sink, n, checkpoint)
        if checkpoint_every and on_checkpoint is not None:
            page = start
            while page < n_pages:
                stop = min(page + checkpoint_every, n_pages)
                state = self._sweep(params, pool, sink, state, page,
                                    stop, n)
                page = stop
                if page < n_pages:
                    self._emit("sweep_cut", next_page=int(page),
                               n=int(n), sink=sink.kind)
                    on_checkpoint(SweepCheckpoint(
                        next_page=page, n=n,
                        page_rows=self.cfg.page_rows, sink_kind=sink.kind,
                        sink_state=sink.serialize(state)))
        else:
            state = self._sweep(params, pool, sink, state, start,
                                n_pages, n)
        self._emit("sweep_done", n=int(n), pages=int(n_pages),
                   resumed_from=int(start), sink=sink.kind)
        return sink.finalize(state, n)

    def run_until(self, params, pool, sink, stop_page: int, *,
                  checkpoint: Optional[SweepCheckpoint] = None
                  ) -> SweepCheckpoint:
        """Sweep up to (not including) ``stop_page`` and cut a resumable
        cursor.  Feeding it back into :meth:`run` continues bit-identically
        to an uninterrupted sweep."""
        n = self.adapter.length(pool)
        start, state = self._restore(sink, n, checkpoint)
        stop = min(stop_page, self.n_pages(n))
        state = self._sweep(params, pool, sink, state, start, stop, n)
        self._emit("sweep_cut", next_page=int(stop), n=int(n),
                   sink=sink.kind)
        return SweepCheckpoint(next_page=stop, n=n,
                               page_rows=self.cfg.page_rows,
                               sink_kind=sink.kind,
                               sink_state=sink.serialize(state))

    # -- async handle --------------------------------------------------------

    def submit(self, params, pool, sink, *,
               checkpoint: Optional[SweepCheckpoint] = None,
               map_result: Optional[Callable] = None) -> SweepFuture:
        """Launch :meth:`run` on the runner's worker thread; the caller
        overlaps its own (host or device) work and synchronizes at
        ``result()`` — the fold."""
        return SweepFuture(
            self._executor().submit(self.run, params, pool, sink,
                                    checkpoint=checkpoint),
            map_result, label=f"sweep[{sink.kind}]")

    def submit_call(self, fn: Callable, *args, **kw) -> SweepFuture:
        """Run an arbitrary callable on the sweep worker (composite jobs
        like feature-sweep + device k-center that end in a sweep)."""
        return SweepFuture(self._executor().submit(fn, *args, **kw),
                           label="sweep[call]")

    # -- lifecycle -----------------------------------------------------------

    def close(self) -> None:
        """Idempotent runner shutdown: join the sweep worker thread (a
        no-op if no sweep was ever submitted).  ``submit`` afterwards
        raises — synchronous ``run`` calls remain valid."""
        if self._exec is not None:
            self._exec.close()

    def __enter__(self) -> "PoolSweepRunner":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- internals -----------------------------------------------------------

    def _executor(self) -> SerialWorker:
        if self._exec is None:
            self._exec = SerialWorker("pool-sweep", retry=self.retry,
                                      faults=self.faults)
            self._exec.metrics = self.metrics
        return self._exec

    def _restore(self, sink, n: int,
                 ckpt: Optional[SweepCheckpoint]) -> Tuple[int, Any]:
        if ckpt is None:
            return 0, sink.init(n)
        if ckpt.sink_kind != sink.kind:
            raise ValueError(f"checkpoint folded a {ckpt.sink_kind!r} sink; "
                             f"cannot resume into {sink.kind!r}")
        if ckpt.n != n or ckpt.page_rows != self.cfg.page_rows:
            raise ValueError(
                f"checkpoint cursor (n={ckpt.n}, page_rows={ckpt.page_rows})"
                f" does not match this sweep (n={n}, "
                f"page_rows={self.cfg.page_rows})")
        return ckpt.next_page, sink.deserialize(ckpt.sink_state)

    def _sweep(self, params, pool, sink, state, start: int, stop: int,
               n: int):
        P = self.cfg.page_rows
        m = self.metrics
        queue: List = []
        nxt = start
        depth = max(self.cfg.prefetch, 1)

        def put_page(i: int):
            return self.adapter.put(pool, i * P, min((i + 1) * P, n))

        while nxt < stop and len(queue) < depth:
            queue.append(put_page(nxt))
            nxt += 1
        for p in range(start, stop):
            page, nvalid = queue.pop(0)
            stats, feats = self.adapter.score(params, page)  # async dispatch
            if nxt < stop:   # h2d of the next page overlaps this compute
                queue.append(put_page(nxt))
                nxt += 1
            state = sink.fold(state, stats, feats, p * P, nvalid)
            if m is not None:
                m.inc("sweep_rows_total", float(nvalid))
        return state
