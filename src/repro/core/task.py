"""Labeling-task abstraction consumed by the MCAL driver.

A task owns a pool of ``pool_size`` unlabeled items and exposes:

* ``human_label(idx)``   -> labels (the simulated annotation service);
* ``train(idx, labels)`` -> $ training cost (re-trains the classifier on the
  human-labeled set, fixed epochs per the paper);
* ``score(idx)``         -> (ScoreStats, features) from the current model;
* ``predict(idx)``       -> argmax machine labels;
* ``eval_correct(idx, labels)`` -> bool array (prediction == label).

:class:`LiveTask` is the real path: a JAX classifier trained with the
framework's own train loop, training cost profiled from the measured
step time x the instance price (the paper's c_u profiling), scoring via the
margin-head path.  The paper-scale replays in benchmarks use
:class:`repro.core.emulator.EmulatedTask` instead — same interface, same
driver.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, Optional, Protocol, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.selection import UNCERTAINTY_METRICS


class LabelingTask(Protocol):
    pool_size: int
    num_classes: int
    arch_name: str

    def human_label(self, idx: np.ndarray) -> np.ndarray: ...
    def train(self, idx: np.ndarray, labels: np.ndarray) -> float: ...
    def score(self, idx: np.ndarray): ...
    def predict(self, idx: np.ndarray) -> np.ndarray: ...
    def eval_correct(self, idx: np.ndarray, labels: np.ndarray) -> np.ndarray: ...


class RowView:
    """Read-only view of the float32 rows ``features[idx]`` that gathers
    only the slice asked for.  The paged sweep reads ``shape[0]`` and
    ``view[lo:hi]``, so a pool pass copies each row once, a page at a
    time, and the whole matrix of rows never exists on the host.  ``idx``
    is copied, so a sweep submitted to a worker thread reads the rows it
    was given even if the caller's index array changes."""

    dtype = np.dtype(np.float32)

    def __init__(self, features: np.ndarray, idx: np.ndarray):
        self.features = features
        self.idx = np.array(idx, np.int64)

    @property
    def shape(self) -> Tuple[int, ...]:
        return (len(self.idx),) + self.features.shape[1:]

    def __len__(self) -> int:
        return len(self.idx)

    def __getitem__(self, rows: slice) -> np.ndarray:
        return np.take(self.features, self.idx[rows], axis=0).astype(
            np.float32, copy=False)


@dataclasses.dataclass
class LiveTask:
    """MCAL over a real JAX classifier + feature dataset.

    ``features``: (N, d) float array; ``groundtruth``: (N,) int labels —
    human labels are simulated as groundtruth (the paper's assumption:
    human labels are perfect).
    """

    features: np.ndarray
    groundtruth: np.ndarray
    num_classes: int
    arch_name: str = "mlp"
    hidden: int = 64
    depth: int = 2
    epochs: int = 40
    batch_size: int = 256
    learning_rate: float = 1e-2
    price_per_hour: float = 3.6      # the paper's 4xK80 VM price
    seed: int = 0
    measured_cost: bool = False      # False -> cost = c_u_nominal * |B| (deterministic)
    c_u_nominal: float = 1e-4        # $/sample-iteration when not measuring
    score_microbatch: int = 2048     # pool-scoring engine microbatch
    sweep_page: int = 8192           # pool-sweep runtime page rows
    fit_fused: bool = True           # fused-scan retrain engine (False ->
                                     # the per-step host-loop oracle)
    fit_resident: bool = False       # keep the labeled set device-resident,
                                     # scatter in only newly bought labels
    mesh: Optional[object] = None    # host/device mesh: microbatch dim of
                                     # the scoring sweep + the fused-fit
                                     # program shard over its "data" axis
    annotation: Optional[object] = None  # AnnotationService (or a shared
                                     # service's AnnotationSession): route
                                     # human_label through a noisy multi-
                                     # annotator oracle (None = the
                                     # paper's perfect-label assumption)
    engines: Optional[object] = None  # launch.orchestrator.SharedEngines:
                                     # reuse a fleet's scoring/sweep/fit
                                     # engine families (and their pow2
                                     # compile caches) instead of building
                                     # per-task ones.  Requires matching
                                     # model/data shapes; the fleet owns
                                     # the engine lifecycle.

    def __post_init__(self):
        self.pool_size = len(self.features)
        if self.engines is not None:
            # shared-engine fleet mode: adopt the bundle's model + train
            # config so this task's params are exactly what the bundle's
            # compiled programs were built for.  Engines are stateless
            # per call given params (the fused fit derives its state from
            # the rng each call), so per-tenant results are bit-identical
            # to owning private engines — EXCEPT the fit engine's
            # resident pool, which is per-engine state and must stay off.
            b = self.engines
            assert not self.fit_resident, \
                "fit_resident keeps per-engine state; unsupported with " \
                "shared engines"
            assert b.input_dim == self.features.shape[1] and \
                b.num_classes == self.num_classes, \
                "shared engines were built for a different data shape"
            self.cfg = b.cfg
            self.model = b.model
            self.tc = b.tc
            self._engine = b.scoring
            self._sweep = b.sweep
            self._fit = b.fit
            self._params = None
            self._res_idx = np.zeros((0,), np.int64)
            self.metrics = None
            return
        from repro.configs.base import ModelConfig, TrainConfig
        from repro.models.registry import get_model
        cfg = ModelConfig(
            name=f"{self.arch_name}-live", family="mlp",
            num_layers=self.depth, d_model=self.hidden,
            num_classes=self.num_classes, input_dim=self.features.shape[1],
            dtype="float32", remat="none")
        self.cfg = cfg
        self.model = get_model(cfg)
        # constant LR so one compiled step serves every |B| (no re-jit per
        # MCAL iteration); the paper's step schedule is exercised by the
        # LM-arch training path.
        self.tc = TrainConfig(learning_rate=self.learning_rate,
                              schedule="constant",
                              weight_decay=1e-4, grad_clip=1.0)
        self._params = None
        from repro.core.scoring import PoolScoringEngine, ScoringConfig
        from repro.serving.sweep import (EngineSweepAdapter, PoolSweepRunner,
                                         SweepConfig)
        from repro.training.fit_device import FitConfig, FitEngine
        self._engine = PoolScoringEngine(
            self.model, ScoringConfig(microbatch=self.score_microbatch),
            mesh=self.mesh)
        self._sweep = PoolSweepRunner(
            EngineSweepAdapter(self._engine),
            SweepConfig(page_rows=self.sweep_page))
        self._fit = FitEngine(self.model, self.tc,
                              FitConfig(epochs=self.epochs,
                                        batch_size=self.batch_size),
                              mesh=self.mesh)
        self._res_idx = np.zeros((0,), np.int64)  # resident-pool row ledger
        self.metrics = None  # runtime metrics registry (attach_metrics)

    def attach_trace(self, trace) -> None:
        """Wire the campaign event bus into this task's runtimes: the
        paged sweep runner (page cursors, sink finalizations) and the fit
        engine (submit/fold timestamps for async retrains).  SHARED
        engines are left unwired — their telemetry interleaves every
        tenant's jobs and belongs to the fleet's observability, not to
        one tenant's trace (all of it is OBSERVABILITY_KINDS, so tenant
        decision streams stay complete without it)."""
        if self.engines is not None:
            return
        self._sweep.trace = trace
        self._fit.trace = trace

    def attach_metrics(self, metrics) -> None:
        """Wire the runtime metrics registry (repro.obs) through this
        task's engine stack: the host gather span, sweep spans + swept
        rows, fit spans + compile-cache hit/miss counters, and the
        k-center span.  Unlike
        :meth:`attach_trace`, SHARED engines are wired too — the fleet
        hands every tenant the same registry and attributes per-tenant
        time via the orchestrator's bound ``tenant`` label, so there is
        one metrics surface per process, not one per tenant."""
        self.metrics = metrics
        self._sweep.metrics = metrics
        self._fit.metrics = metrics
        self._engine.metrics = metrics

    def attach_faults(self, faults, retry=None) -> None:
        """Wire the chaos injector (and optional re-dispatch retry
        policy) into the OWNED engines' broker workers (fault sites
        ``worker.pool-sweep``/``worker.fit-engine``).  Shared engines
        are left unwired, same reasoning as :meth:`attach_trace`: their
        jobs interleave every tenant's work, so injecting there would
        chaos the whole fleet, not this tenant."""
        if self.engines is not None:
            return
        self._sweep.attach_faults(faults, retry)
        self._fit.attach_faults(faults, retry)

    def close(self) -> None:
        """Idempotent task teardown: join the OWNED engines' broker
        threads (shared engines belong to the fleet; the annotation
        service/session closes itself — a session's close is a no-op,
        a privately attached service's joins its broker)."""
        if self.engines is None:
            self._sweep.close()
            self._fit.close()
        ann = self.annotation
        if ann is not None and hasattr(ann, "close"):
            ann.close()

    # -- annotation service ------------------------------------------------
    def human_label(self, idx: np.ndarray) -> np.ndarray:
        """Purchased human labels.  With an :attr:`annotation` service
        attached these are AGGREGATED noisy-annotator votes (charged per
        request by the buyer — see ``SharedPool.buy_labels``); without
        one, the paper's perfect-label assumption."""
        idx = np.asarray(idx, np.int64)
        gt = self.groundtruth[idx]
        if self.annotation is not None:
            return self.annotation.annotate(idx, gt)
        return gt

    def oracle_labels(self, idx: np.ndarray) -> np.ndarray:
        """TRUE labels for evaluation only — never charged, never noisy
        (the simulation oracle measured_error is computed against)."""
        return self.groundtruth[np.asarray(idx, np.int64)]

    # -- training ------------------------------------------------------------
    def train(self, idx: np.ndarray, labels: np.ndarray) -> float:
        """Re-train from scratch on (idx, labels) for ``epochs`` epochs
        (fixed epochs => per-iteration cost proportional to |B|, Eqn. 4).

        Runs as ONE fused device program (``training.fit_device.FitEngine``:
        epochs x steps in a single ``lax.scan``, shuffles from
        ``jax.random.permutation`` on device, (n, batch) pow2-bucketed so
        growing |B| reuses the compile cache).  ``fit_fused=False`` keeps
        the per-step host loop — the exact-agreement oracle (identical
        permutation sequence -> bit-identical params on a CPU host).  With
        ``fit_resident`` the labeled set stays device-resident across MCAL
        iterations and only newly bought labels are scattered in."""
        idx = np.asarray(idx, np.int64)
        n = len(idx)
        rng = jax.random.key(self.seed)
        t0 = time.perf_counter()
        if not self.fit_fused:
            params, losses = self._fit.fit_reference(
                rng, self._rows(idx), np.asarray(labels, np.int32))
        elif self.fit_resident:
            prev = len(self._res_idx)
            if n < prev or not np.array_equal(idx[:prev], self._res_idx):
                # not an append-only extension of the resident set: rebuild
                self._fit.reset_resident()
                prev = 0
            if n > prev:
                fresh = idx[prev:]
                self._fit.extend_resident(
                    self._rows(fresh), np.asarray(labels, np.int32)[prev:])
            self._res_idx = idx.copy()
            params, losses = self._fit.fit_resident(rng)
        else:
            params, losses = self._fit.fit(
                rng, self._rows(idx), np.asarray(labels, np.int32))
        jax.block_until_ready(losses)
        wall = time.perf_counter() - t0
        self._params = params
        if self.measured_cost:
            return wall / 3600.0 * self.price_per_hour
        return self.c_u_nominal * n

    def train_cost(self, n: int) -> Optional[float]:
        """The $ cost :meth:`train` will charge for an ``n``-row retrain
        when it is known WITHOUT training (the deterministic nominal
        c_u * |B| model) — None under ``measured_cost`` (wall-clock
        pricing).  The campaign's async-fit path pays this at submit
        time so the shared ledger is never stale while a retrain is in
        flight."""
        return None if self.measured_cost else self.c_u_nominal * n

    def submit_train(self, idx: np.ndarray, labels: np.ndarray,
                     then: Optional[callable] = None):
        """Async retrain (``FitEngine.submit_fit`` worker): runs
        :meth:`train` off-thread and returns a ``FitFuture`` of its $ cost
        — or of ``(cost, then())`` when a ``then`` continuation is given
        (the campaign chains its measurement sweep there, so it reads the
        freshly trained params on the same worker and the retrain dispatch
        overlaps the measurement's host-side paging)."""
        idx = np.asarray(idx, np.int64).copy()
        labels = np.asarray(labels).copy()

        def job():
            c = self.train(idx, labels)
            return (c, then()) if then is not None else c

        return self._fit.submit_call(job)

    # -- scoring ----------------------------------------------------------
    # Pool-scale passes (top-k M(.), k-center features, the L(.)/commit
    # rank) stream through the paged pool-sweep runtime
    # (``serving.sweep.PoolSweepRunner`` over the device engine), so the
    # pool never materializes on the device and only each sink's fold
    # returns to the host.  A sweep takes a :class:`RowView` of its rows:
    # the host gathers each page as the sweep stages it (inside the
    # ``sweep`` span), and the whole candidate matrix is never built.
    # Small measurement scoring (the test set) stays on the direct engine
    # path over gathered rows; the seed host loop survives as
    # ``repro.core.scoring.score_pool_reference`` (the oracle the engine
    # is validated against and benchmarked over).

    def _rows(self, idx: np.ndarray) -> np.ndarray:
        """The float32 feature rows of ``idx`` in one copy: the host
        gather that feeds the retrain (labeled set) and the direct engine
        paths (test set) -- the ``gather`` span, outside the ``fit`` span
        of the work it feeds.  Sweeps gather page by page instead
        (:meth:`_pages`)."""
        idx = np.asarray(idx, np.int64)
        if self.metrics is None:
            return self.features[idx].astype(np.float32, copy=False)
        with self.metrics.span("gather"):
            return self.features[idx].astype(np.float32, copy=False)

    def _pool(self, idx: np.ndarray) -> np.ndarray:
        assert self._params is not None, "train() before score()"
        return self._rows(idx)

    def _pages(self, idx: np.ndarray) -> RowView:
        """The rows of ``idx`` for a paged sweep, gathered a page at a
        time as the sweep stages them."""
        assert self._params is not None, "train() before score()"
        return RowView(self.features, idx)

    def score(self, idx: np.ndarray):
        stats, feats = self._engine.score_host(self._params, self._pool(idx))
        return stats, feats

    def topk_candidates(self, metric: str, k: int,
                        candidates: np.ndarray) -> np.ndarray:
        """M(.) fast path: paged sweep folding a device top-k reservoir —
        only the k chosen rows ever reach the host."""
        from repro.serving.sweep import TopKSink
        rows = self._sweep.run(self._params, self._pages(candidates),
                               TopKSink(k, metric))
        return np.asarray(candidates, np.int64)[rows]

    def kcenter_candidates(self, k: int, candidates: np.ndarray,
                           anchors: Optional[np.ndarray] = None):
        """M(.) k-center fast path: the paged sweep emits device-resident
        features and the greedy farthest-point loop runs on device too —
        the only host transfers are the k chosen rows and their features.
        The host oracle ``selection.k_center_greedy`` remains the
        reference path."""
        from repro.core.selection_device import k_center_greedy_device
        from repro.serving.sweep import FeatureSink
        feats = self._sweep.run(self._params, self._pages(candidates),
                                FeatureSink())
        rows = k_center_greedy_device(feats, k, anchors=anchors,
                                      metrics=self.metrics)
        picked = np.asarray(candidates, np.int64)[rows]
        return picked, np.asarray(feats[jnp.asarray(rows)], np.float32)

    def anchor_features(self, idx: np.ndarray) -> np.ndarray:
        """(len(idx), D) pooled features of ``idx`` under the CURRENT
        classifier (one paged feature sweep) — the campaign's k-center
        anchor set, rebuildable from ``B_idx`` alone on resume."""
        from repro.serving.sweep import FeatureSink
        return np.asarray(
            self._sweep.run(self._params, self._pages(idx), FeatureSink()),
            np.float32)

    def machine_label_sweep(self, idx: np.ndarray, metric: str = "margin",
                            *, checkpoint=None, checkpoint_every: int = 0,
                            on_checkpoint=None):
        """L(.)/commit fast path: one paged sweep over ``idx`` ->
        (rows most-confident-first, machine labels row-aligned with
        ``idx``).  Only the rank field + top1 per row return to host.

        ``checkpoint`` resumes a previously cut ``SweepCheckpoint``
        mid-pool (bit-identical to an uninterrupted sweep);
        ``checkpoint_every``/``on_checkpoint`` cut a cursor every N pages
        and hand it to the callback — the launcher persists it in its
        ``--state`` file so a preempted commit sweep restarts mid-pool."""
        from repro.serving.sweep import RankTop1Sink
        order, top1 = self._sweep.run(self._params, self._pages(idx),
                                      RankTop1Sink(metric),
                                      checkpoint=checkpoint,
                                      checkpoint_every=checkpoint_every,
                                      on_checkpoint=on_checkpoint)
        return order, top1

    def submit_candidates(self, metric: str, k: int, candidates: np.ndarray,
                          anchors: Optional[np.ndarray] = None):
        """Async M(.): launch the ranking sweep on the runner's worker
        thread and return a ``SweepFuture`` — the campaign overlaps its
        host-side fits/search and synchronizes at ``result()``.
        Uncertainty metrics resolve to the picked pool indices; k-center
        to the same ``(picked, features)`` pair as
        :meth:`kcenter_candidates`."""
        from repro.serving.sweep import TopKSink
        # a copy: the worker gathers and maps rows after this returns
        cand = np.array(candidates, np.int64)
        if metric in UNCERTAINTY_METRICS:
            return self._sweep.submit(
                self._params, self._pages(cand), TopKSink(k, metric),
                map_result=lambda rows: cand[rows])
        if metric == "kcenter":
            return self._sweep.submit_call(self.kcenter_candidates, k, cand,
                                           anchors)
        raise ValueError(f"no async sweep for metric {metric!r}")

    def predict(self, idx: np.ndarray) -> np.ndarray:
        stats, _ = self._engine.score_host(self._params, self._pool(idx))
        return np.asarray(stats.top1, np.int64)

    # -- compile-cache persistence ----------------------------------------
    def pack_cache_keys(self) -> Dict:
        """The pow2 pack-shape buckets both device engines have compiled
        (scoring sweep pages + fused-fit programs) — JSON-embeddable in
        campaign checkpoints so a resumed replay prewarms them instead of
        recompiling mid-loop."""
        return {"scoring": [list(k) for k in self._engine.cache_keys()],
                "fit": [list(k) for k in self._fit.cache_keys()]}

    def prewarm_caches(self, keys: Optional[Dict]):
        """Rebuild both engines' compile caches from persisted pack keys
        (requires a trained model for the scoring side)."""
        if not keys:
            return
        self._fit.warm(keys.get("fit", ()))
        if self._params is not None:
            self._engine.warm(self._params, keys.get("scoring", ()))

    def eval_correct(self, idx: np.ndarray, labels: np.ndarray) -> np.ndarray:
        return self.predict(idx) == np.asarray(labels)
