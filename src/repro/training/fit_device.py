"""Device-resident retrain engine — MCAL's per-iteration training hot path.

Every MCAL iteration retrains the classifier from scratch on the labeled
set for a fixed number of epochs (per-iteration cost proportional to |B|,
Eqn. 4).  The seed implementation (``LiveTask.train``) ran this as a
per-step Python host loop: a host permutation per epoch, a numpy batch
gather + one host-to-device upload + one jitted-step dispatch per batch,
blocking at every step.  This engine runs the ENTIRE fixed-epoch retrain
as ONE jit-compiled device program:

* the labeled set ``(x, y)`` is padded once with the engine's pow2
  bucketing and uploaded once (or kept **campaign-resident** across MCAL
  iterations with only the newly bought labels scattered in —
  :meth:`FitEngine.extend_resident` / :meth:`FitEngine.fit_resident`);
* epoch shuffles come from ``jax.random.permutation`` inside the program
  (:func:`epoch_orders`): a permutation of the PADDED row range is cut per
  epoch and its valid (< n) entries are stably partitioned to the front,
  so the first-n prefix is a uniform permutation of the true rows while
  every shape stays static;
* ``epochs x steps`` are fused into a single ``lax.scan`` over the train
  step; the ragged tail of each epoch wraps into the front of the SAME
  epoch's permutation (``(s*bs + arange(bs)) % n``) exactly like the host
  loop's wrap, so padding rows are never trained on and no masked loss is
  needed;
* the freshly initialized train state is donated into the program and
  threaded through the scan carry;
* ``(n, batch)`` is bucketed through the same :func:`scoring.pack_shape`
  convention as every other device engine (``(steps_per_epoch, bs) =
  pack_shape(n, batch_size)``, padded pool = ``steps_per_epoch * bs``
  rows), so successive MCAL iterations with growing |B| reuse O(log N)
  compiled programs instead of recompiling every retrain.

The per-step host loop survives as :meth:`FitEngine.fit_reference` — the
exact-agreement oracle (same permutation sequence -> bit-identical params
and per-step losses on a CPU host; tests/test_fit_device.py) and the
baseline ``benchmarks/bench_fit.py`` enforces the >= 2x gate over.

:meth:`FitEngine.submit_fit` mirrors ``PoolSweepRunner.submit``: the fit
runs on the engine's worker thread and the caller synchronizes at
``result()``, so ``MCALCampaign._train_and_measure`` overlaps the retrain
dispatch with the L(.) measurement sweep (and, in architecture selection,
every candidate's retrain runs concurrently).

With a mesh, the program is jit-compiled with the same state shardings
``make_sharded_train_step`` derives (``state_pspecs`` over the logical-axis
trees) and the mesh-aware raw step, so the fused retrain data-parallelizes
without changing the scan.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

from repro.core.worker import SerialWorker

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compat
from repro.configs.base import TrainConfig
from repro.core.scoring import pack_shape
from repro.distributed import sharding as shd
# the sweep runtime's future wrapper, shared rather than mirrored so
# worker-handle hardening lands in one place
from repro.serving.sweep import SweepFuture as FitFuture
from repro.training.train_loop import (init_train_state, make_train_step,
                                       state_pspecs)


def fit_plan(n: int, batch_size: int) -> Tuple[int, int, int]:
    """The engine's schedule for an ``n``-row labeled set:
    ``(steps_per_epoch, bs, n_pad)`` with ``n_pad = steps_per_epoch * bs``
    — the :func:`scoring.pack_shape` pow2 bucketing, so the compile-cache
    key set stays O(log N) as |B| grows across MCAL iterations.  One epoch
    sweeps the padded row count (every sample is visited at least once per
    epoch; the ragged tail wraps into the front of the epoch's
    permutation)."""
    spe, bs = pack_shape(n, batch_size)
    return spe, bs, spe * bs


def epoch_orders(key_data: jax.Array, epochs: int, n_pad: int,
                 n: jax.Array) -> jax.Array:
    """(epochs, n_pad) int32 row orders: per epoch, a
    ``jax.random.permutation`` of the padded row range with its valid
    (< n) entries stably partitioned to the front — the first-n prefix is
    a uniform random permutation of the true rows, computed entirely with
    static shapes (``n`` stays a traced scalar).  Shared verbatim by the
    fused scan and the reference host loop, so both consume the identical
    permutation sequence."""
    key = jax.random.wrap_key_data(key_data)

    def one(e):
        perm = jax.random.permutation(jax.random.fold_in(key, e), n_pad)
        return perm[jnp.argsort(perm >= n, stable=True)]

    return jax.vmap(one)(jnp.arange(epochs))


# one shared jitted wrapper (static epochs/n_pad) so the reference loop's
# permutation program caches across retrains like the fused path's does
_epoch_orders_jit = jax.jit(epoch_orders, static_argnums=(1, 2))




@dataclasses.dataclass(frozen=True)
class FitConfig:
    epochs: int = 40
    batch_size: int = 256


class FitEngine:
    """jit-compiled fused multi-epoch trainer for one (model, TrainConfig).

    ``fit(rng, x, y) -> (params, losses)`` retrains from scratch on the
    full labeled set as one device program; ``fit_resident`` does the same
    over the campaign-resident device pool (only newly bought labels are
    scattered in per iteration, :meth:`extend_resident`).  ``losses`` is
    the per-step training loss, ``(epochs * steps_per_epoch,)``.
    """

    def __init__(self, model, tc: TrainConfig, cfg: FitConfig = FitConfig(),
                 mesh=None, policy: str = "tp"):
        self.model = model
        self.tc = tc
        self.cfg = cfg
        self.mesh = mesh
        self.policy = policy
        self._batch_key = ("features" if model.cfg.family == "mlp"
                           else "tokens")
        self._step = make_train_step(model, tc, mesh=mesh, jit=False)
        self._programs: Dict[Tuple[int, int, int], Any] = {}
        # AOT-compiled executables from warm(): jit's dispatch cache is
        # NOT populated by lower().compile(), so these are dispatched
        # directly — a warmed bucket never traces or compiles again
        self._compiled: Dict[Tuple[int, int, int], Any] = {}
        self._ref_step = None
        self._exec: Optional[SerialWorker] = None
        # campaign-resident labeled pool: device buffers + valid row count
        self._res_x: Optional[jax.Array] = None
        self._res_y: Optional[jax.Array] = None
        self._res_n = 0
        # campaign event bus (observability only: submit/fold timestamps
        # for async retrains; the fold emit runs on the worker thread)
        self.trace = None
        self._submit_seq = 0
        # runtime metrics (repro.obs.MetricsRegistry); None = free no-op
        self.metrics = None
        # resilience seam: chaos injector + broker re-dispatch policy,
        # handed to the lazy SerialWorker (site ``worker.fit-engine``)
        self.faults = None
        self.retry = None

    def attach_faults(self, faults, retry=None) -> None:
        """Wire the fault injector (and optional re-dispatch policy)
        into the fit broker: every submitted job ticks the
        ``worker.fit-engine`` site, and transient crashes re-dispatch."""
        self.faults = faults
        if retry is not None:
            self.retry = retry
        if self._exec is not None:
            self._exec.attach_faults(faults, retry)

    # -- program construction ------------------------------------------------

    def _program(self, n: int):
        """The fused program for the ``fit_plan`` bucket of ``n`` (compile
        cache keyed on the bucket, not the raw size)."""
        spe, bs, n_pad = fit_plan(n, self.cfg.batch_size)
        key = (spe, bs, n_pad)
        prog = self._programs.get(key)
        if self.metrics is not None:
            self.metrics.inc("pack_cache_hits_total" if prog is not None
                             else "pack_cache_misses_total", engine="fit")
        if prog is not None:
            return prog, key
        epochs, step, batch_key = self.cfg.epochs, self._step, self._batch_key

        # named for the trace: the device program reads ``jit_fit_epochs``
        def fit_epochs(state, xp, yp, nn, key_data):
            orders = epoch_orders(key_data, epochs, n_pad, nn)

            def body(state, t):
                e, s = t // spe, t % spe
                pos = (s * bs + jnp.arange(bs)) % nn
                rows = orders[e][pos]
                batch = {batch_key: xp[rows], "labels": yp[rows]}
                state, metrics = step(state, batch)
                return state, metrics["loss"]

            state, losses = jax.lax.scan(
                body, state, jnp.arange(epochs * spe, dtype=jnp.int32))
            return state, losses

        # the state is built fresh per fit, so donating it is always safe
        kwargs: Dict[str, Any] = {"donate_argnums": (0,)}
        if self.mesh is not None:
            _, pspecs = state_pspecs(self.model, self.tc, self.mesh,
                                     self.policy)
            rep = NamedSharding(self.mesh, P())
            kwargs["in_shardings"] = (shd.tree_named(self.mesh, pspecs),
                                      rep, rep, rep, rep)
        prog = jax.jit(fit_epochs, **kwargs)
        self._programs[key] = prog
        return prog, key

    # -- packing -------------------------------------------------------------

    def _pack_host(self, x, y, n: int) -> Tuple[np.ndarray, np.ndarray]:
        """Pad (x, y) to the fit_plan bucket on host (one h2d upload)."""
        _, _, n_pad = fit_plan(n, self.cfg.batch_size)
        x = np.asarray(x)
        xp = np.zeros((n_pad,) + x.shape[1:], x.dtype)
        xp[:n] = x
        yp = np.zeros((n_pad,), np.int32)
        yp[:n] = np.asarray(y, np.int32)
        return xp, yp

    @staticmethod
    def _keys(rng: jax.Array) -> Tuple[jax.Array, jax.Array]:
        init_key, shuffle_key = jax.random.split(rng)
        return init_key, shuffle_key

    def init_state(self, rng: jax.Array) -> Dict:
        return init_train_state(self.model, self.tc, rng)

    # -- the fused path ------------------------------------------------------

    def fit(self, rng: jax.Array, x, y) -> Tuple[Dict, jax.Array]:
        """One fused retrain-from-scratch over the full labeled set:
        ``(params, per-step losses)``, device-resident (dispatch is async —
        callers that time the retrain must block on ``losses``)."""
        n = int(np.asarray(x).shape[0])
        xp, yp = self._pack_host(x, y, n)
        return self._run(rng, jnp.asarray(xp), jnp.asarray(yp), n)

    def _run(self, rng, xd, yd, n: int) -> Tuple[Dict, jax.Array]:
        if self.metrics is not None:
            # fence on losses: the span covers the device retrain, not
            # just the async dispatch (runs on the fit worker for
            # submit_fit, so campaign-side overlap is unaffected).
            # labeled by the fit_plan bucket, not raw n — O(log N) series
            n_pad = fit_plan(n, self.cfg.batch_size)[2]
            with self.metrics.span("fit", n_pad=n_pad) as sp:
                params, losses = self._run_impl(rng, xd, yd, n)
                sp.fence(losses)
            return params, losses
        return self._run_impl(rng, xd, yd, n)

    def _run_impl(self, rng, xd, yd, n: int) -> Tuple[Dict, jax.Array]:
        prog, key = self._program(n)
        prog = self._compiled.get(key, prog)   # warmed AOT executable
        init_key, shuffle_key = self._keys(rng)
        state = self.init_state(init_key)
        key_data = jax.random.key_data(
            jax.random.fold_in(shuffle_key, n))
        state, losses = prog(state, xd, yd, jnp.int32(n), key_data)
        return state["params"], losses

    # -- campaign-resident pool ---------------------------------------------

    @property
    def resident_size(self) -> int:
        return self._res_n

    def reset_resident(self):
        self._res_x = self._res_y = None
        self._res_n = 0

    def extend_resident(self, new_x, new_y) -> int:
        """Scatter newly bought labels into the device-resident pool
        (growing the buffers to the next ``fit_plan`` bucket when needed);
        returns the new valid row count.  Successive MCAL iterations pay
        h2d only for the delta rows."""
        new_x = np.asarray(new_x)
        new_y = np.asarray(new_y, np.int32)
        d = int(new_x.shape[0])
        if d == 0:
            return self._res_n
        n = self._res_n + d
        _, _, n_pad = fit_plan(n, self.cfg.batch_size)
        if self._res_x is None:
            self._res_x = jnp.zeros((n_pad,) + new_x.shape[1:], new_x.dtype)
            self._res_y = jnp.zeros((n_pad,), jnp.int32)
        elif n_pad > self._res_x.shape[0]:
            grow = n_pad - self._res_x.shape[0]
            self._res_x = jnp.concatenate(
                [self._res_x,
                 jnp.zeros((grow,) + self._res_x.shape[1:],
                           self._res_x.dtype)])
            self._res_y = jnp.concatenate(
                [self._res_y, jnp.zeros((grow,), jnp.int32)])
        self._res_x = jax.lax.dynamic_update_slice(
            self._res_x, jnp.asarray(new_x),
            (self._res_n,) + (0,) * (new_x.ndim - 1))
        self._res_y = jax.lax.dynamic_update_slice(
            self._res_y, jnp.asarray(new_y), (self._res_n,))
        self._res_n = n
        return n

    def fit_resident(self, rng: jax.Array) -> Tuple[Dict, jax.Array]:
        """:meth:`fit` over the resident pool — no pool upload at all (the
        compiled program is shared with :meth:`fit`: same bucket, same
        cache key)."""
        if self._res_n == 0:
            raise ValueError("resident pool is empty; extend_resident first")
        return self._run(rng, self._res_x, self._res_y, self._res_n)

    # -- async handle --------------------------------------------------------

    def _executor(self) -> SerialWorker:
        if self._exec is None:
            self._exec = SerialWorker("fit-engine", retry=self.retry,
                                      faults=self.faults)
            self._exec.metrics = self.metrics
        return self._exec

    def close(self) -> None:
        """Idempotent engine shutdown: join the fit worker thread (no-op
        if nothing was ever submitted).  ``submit_fit``/``submit_call``
        afterwards raise — synchronous ``fit`` calls remain valid."""
        if self._exec is not None:
            self._exec.close()

    def __enter__(self) -> "FitEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def _traced(self, fn: Callable, label: str) -> Callable:
        """Bracket a worker-thread job with fit_submit/fit_done events —
        the submit/fold timestamps the live report's overlap view reads.
        The pairing key is a per-engine job counter (events from the
        worker interleave arbitrarily with the main thread's)."""
        if self.trace is None:
            return fn
        job, self._submit_seq = self._submit_seq, self._submit_seq + 1
        self.trace.emit("fit_submit", job=int(job), what=label)
        trace = self.trace

        def wrapped(*args, **kw):
            out = fn(*args, **kw)
            trace.emit("fit_done", job=int(job), what=label)
            return out
        return wrapped

    def submit_fit(self, rng: jax.Array, x, y) -> FitFuture:
        """Launch :meth:`fit` on the engine's worker thread (mirrors
        ``PoolSweepRunner.submit``); the caller overlaps its own work and
        synchronizes at ``result()``."""
        return FitFuture(self._executor().submit(
            self._traced(self.fit, "fit"), rng, x, y), label="fit")

    def submit_call(self, fn: Callable, *args, **kw) -> FitFuture:
        """Run an arbitrary callable on the fit worker (composite jobs
        like retrain + measurement sweep that start with a fit)."""
        return FitFuture(self._executor().submit(
            self._traced(fn, "call"), *args, **kw), label="fit[call]")

    # -- compile-cache bookkeeping ------------------------------------------

    def cache_keys(self) -> List[Tuple[int, int, int]]:
        """The (steps_per_epoch, bs, n_pad) buckets compiled so far —
        persisted in campaign checkpoints so a resumed paper-scale replay
        can prewarm them (:meth:`warm`) instead of paying compiles
        mid-campaign."""
        return sorted(self._programs)

    def warm(self, keys) -> int:
        """AOT-compile the programs for ``keys`` (cache-key tuples or raw
        pool sizes) without running a single train step — a resumed
        campaign pays its compiles upfront instead of mid-loop.  The
        compiled executables are kept and dispatched directly by
        :meth:`fit` (``lower().compile()`` does not populate jit's own
        dispatch cache); returns how many programs were compiled."""
        if self.metrics is None:
            return self._warm_impl(keys)
        with self.metrics.span("warm", engine="fit"):
            return self._warm_impl(keys)

    def _warm_impl(self, keys) -> int:
        from repro.training.train_loop import abstract_train_state
        if self._batch_key != "features":
            raise NotImplementedError(
                "warm() supports feature-classifier models")
        ab_state, _ = abstract_train_state(self.model, self.tc)
        kd = jax.random.key_data(jax.random.key(0))
        count = 0
        for k in keys:
            n_pad = int(k[2]) if isinstance(k, (tuple, list)) else \
                fit_plan(int(k), self.cfg.batch_size)[2]
            prog, key = self._program(n_pad)
            if key in self._compiled:
                continue
            xs = jax.ShapeDtypeStruct((n_pad, self.model.cfg.input_dim),
                                      jnp.float32)
            ys = jax.ShapeDtypeStruct((n_pad,), jnp.int32)
            nn = jax.ShapeDtypeStruct((), jnp.int32)
            self._compiled[key] = prog.lower(ab_state, xs, ys, nn,
                                             kd).compile()
            count += 1
        return count

    # -- the per-step host loop, kept as the reference oracle ---------------

    def fit_reference(self, rng: jax.Array, x, y) -> Tuple[Dict, jax.Array]:
        """The seed ``LiveTask.train`` shape: one numpy batch gather + one
        h2d upload + one jitted-step dispatch per batch, blocking on every
        step — over the SAME permutation sequence (:func:`epoch_orders`)
        and schedule (:func:`fit_plan`) as the fused scan.  Bit-identical
        params and losses on a CPU host; the benchmark baseline."""
        n = int(np.asarray(x).shape[0])
        spe, bs, n_pad = fit_plan(n, self.cfg.batch_size)
        xp, yp = self._pack_host(x, y, n)
        if self._ref_step is None:
            self._ref_step = make_train_step(self.model, self.tc,
                                             mesh=self.mesh, jit=True)
        init_key, shuffle_key = self._keys(rng)
        key_data = jax.random.key_data(jax.random.fold_in(shuffle_key, n))
        orders = np.asarray(_epoch_orders_jit(key_data, self.cfg.epochs,
                                              n_pad, jnp.int32(n)))
        state = self.init_state(init_key)
        losses = []
        arange = np.arange(bs)
        for e in range(self.cfg.epochs):
            order = orders[e]
            for s in range(spe):
                sel = order[(s * bs + arange) % n]
                batch = {self._batch_key: jnp.asarray(xp[sel]),
                         "labels": jnp.asarray(yp[sel])}
                state, metrics = self._ref_step(state, batch)
                losses.append(metrics["loss"])
        return state["params"], jnp.stack(losses)
