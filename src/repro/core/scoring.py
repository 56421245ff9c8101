"""Device-resident pool-scoring engine — MCAL's per-iteration hot path.

Every MCAL iteration scores the entire unlabeled pool twice (Alg. 1):
M(.) ranks candidates for the next delta human labels, L(.) ranks the
remainder for the machine-label prefix.  The seed implementation ran this
as a host-side python loop — chunked forward, transfer logits to host,
numpy statistics per chunk — which serializes device work against host
round-trips and re-materializes (chunk, V) logits in host memory.

This engine runs the whole pool as ONE jit-compiled program:

* the pool is padded into ``(n_microbatches, microbatch, ...)`` and swept
  with ``lax.map`` — device-resident end to end, no host sync until the
  packed statistics are fetched;
* per microbatch: model forward + the vocab head fused into
  :class:`ScoreStats` (margin / entropy / max-logprob / top1) via the
  dense reference, the vocab-chunked online-softmax path, or the Pallas
  ``margin_head`` kernel (``head_mode``), so (T, V) logits never hit HBM
  for large vocabularies;
* microbatch counts are bucketed to powers of two so a shrinking
  candidate set re-uses O(log N) compiled programs instead of recompiling
  every MCAL iteration;
* top-k candidate selection happens on device (``lax.top_k`` over the
  packed scores, padding masked to -inf);
* the same sweep optionally emits pooled last-hidden-state features
  (``ScoringConfig.with_features`` / :meth:`PoolScoringEngine.pool_features`)
  which stay device-resident — the k-center selection engine
  (``core.selection_device``) consumes them for M(.) without a host
  round-trip.

The seed's host loop is preserved as :func:`score_pool_reference` — the
oracle the engine is validated against (tests/test_scoring.py) and the
baseline ``benchmarks/bench_selection.py`` measures speedup over.

With a mesh, the microbatch dimension is sharded over the ``data`` axis
(params replicated) and the same program scales across the pool's devices.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compat
from repro.core.selection import UNCERTAINTY_METRICS  # noqa: F401 (re-export)
from repro.models import layers as L
from repro.models.layers import ScoreStats


def next_pow2(n: int) -> int:
    """The pow2 bucketing primitive shared by every device engine that
    pads pools for compile-cache reuse (:meth:`PoolScoringEngine._pack`,
    ``selection_device.k_center_greedy_device``)."""
    return 1 << max(n - 1, 0).bit_length()


def pack_shape(n: int, microbatch: int) -> Tuple[int, int]:
    """The engine's pow2 microbatch bucketing for an ``n``-row pool:
    ``(n_mb, mb)`` with ``n_mb * mb >= n``.  Shared with the streaming
    sweep runtime (``serving.sweep``) so pages pack identically to an
    unpaged engine sweep and hit the same compile cache."""
    if n >= microbatch:
        mb = microbatch
        n_mb = next_pow2(math.ceil(n / mb))
    else:
        mb = max(next_pow2(n), 8)
        n_mb = 1
    return n_mb, mb


def resolve_head_weight(cfg, params) -> jax.Array:
    """The (D, V) scoring-head matrix for any model family: the explicit
    classifier head when present, otherwise the (possibly tied) LM head."""
    if "cls_head" in params:
        return params["cls_head"]
    from repro.models.transformer import lm_head_weight
    return lm_head_weight(cfg, params)


# ---------------------------------------------------------------------------
# score packing (shared by the engine, the emulator, and serving)
# ---------------------------------------------------------------------------


def uncertainty_from_stats(stats: ScoreStats, metric: str) -> jax.Array:
    """Higher = more uncertain, device-side (jnp twin of
    ``selection.uncertainty_scores``)."""
    if metric == "margin":
        return -stats.margin
    if metric == "entropy":
        return stats.entropy
    if metric == "least_confidence":
        return 1.0 - jnp.exp(stats.max_logprob)
    raise ValueError(f"unknown uncertainty metric {metric!r}")


def stats_from_confidence(conf: np.ndarray, num_classes: int,
                          top1: np.ndarray) -> ScoreStats:
    """Pack a scalar confidence in [~0, 1] into a consistent ScoreStats
    (the emulator's scoring path; margin == confidence by convention)."""
    conf = np.asarray(conf, np.float64)
    return ScoreStats(
        margin=conf,
        entropy=np.maximum(1.0 - conf, 0.0) * np.log(num_classes),
        max_logprob=np.minimum(conf - 1.0, -1e-9),
        top1=np.asarray(top1))


def head_stats(hidden: jax.Array, w_head: jax.Array, *, mode: str = "auto",
               vocab_chunk: int = 8192, pallas_bt: int = 128,
               pallas_bv: int = 512) -> ScoreStats:
    """Fused vocab projection + ScoreStats for last-token hidden states.

    ``hidden``: (T, D); ``w_head``: (D, V).  ``mode``:
      dense    materialize (T, V) logits (exact reference; small V),
      chunked  online top-2/logsumexp over vocab chunks (jnp),
      pallas   the ``margin_head`` TPU kernel (interpreted off-TPU),
      auto     dense when V fits comfortably, else chunked.
    """
    V = w_head.shape[-1]
    if mode == "auto":
        mode = "dense" if V <= 4096 else "chunked"
    if mode == "dense":
        logits = jnp.einsum("td,dv->tv", hidden, w_head,
                            preferred_element_type=jnp.float32)
        return L.score_stats_from_logits(logits)
    if mode == "chunked":
        return L.chunked_score_stats(hidden, w_head, chunk=vocab_chunk)
    if mode == "pallas":
        from repro.kernels import ops
        from repro.kernels.margin_head import margin_head
        margin, entropy, max_logprob, top1 = margin_head(
            hidden, w_head, bt=pallas_bt, bv=pallas_bv,
            interpret=ops._interpret())
        return ScoreStats(margin=margin, entropy=entropy,
                          max_logprob=max_logprob, top1=top1)
    raise ValueError(f"unknown head mode {mode!r}")


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ScoringConfig:
    microbatch: int = 1024
    head_mode: str = "auto"        # auto | dense | chunked | pallas
    vocab_chunk: int = 8192
    pallas_bt: int = 128
    pallas_bv: int = 512
    with_features: bool = True     # also return last-hidden features


class PoolScoringEngine:
    """jit-compiled microbatched pool scorer for one model.

    ``model`` is the registry facade; feature-classifier families consume
    ``(N, input_dim)`` float pools, token families ``(N, T)`` int pools
    (last-position statistics — the serving/labeling convention).
    """

    def __init__(self, model, cfg: ScoringConfig = ScoringConfig(),
                 mesh=None):
        self.model = model
        self.cfg = cfg
        self.mesh = mesh
        self._batch_key = ("features" if model.cfg.family == "mlp"
                           else "tokens")
        # no pool donation: no output has the packed pool's shape, so a
        # donated buffer could never be reused
        kwargs = {}
        if mesh is not None:
            xs_spec = NamedSharding(mesh, P(None, "data"))
            p_spec = NamedSharding(mesh, P())
            kwargs["in_shardings"] = (p_spec, xs_spec)
        self._score_all = jax.jit(self._score_padded, **kwargs)
        # (n_mb, mb) pack buckets swept so far — the compile-cache key set,
        # persisted in campaign checkpoints (cache_keys / warm) — and the
        # warmed AOT executables dispatched in place of the jit wrapper
        # (lower().compile() does not populate jit's dispatch cache)
        self.pack_keys: set = set()
        self._compiled: dict = {}
        # runtime metrics (repro.obs.MetricsRegistry); None = free no-op
        self.metrics = None

    def _note_pack(self, key: Tuple[int, int]) -> None:
        """Record a pack-bucket touch: compile-cache hit when the bucket
        was already swept, miss when this is its first (compiling) use."""
        if self.metrics is not None:
            if key in self.pack_keys:
                self.metrics.inc("pack_cache_hits_total", engine="scoring")
            else:
                self.metrics.inc("pack_cache_misses_total",
                                 engine="scoring")
        self.pack_keys.add(key)

    # -- model plumbing ----------------------------------------------------

    def _microbatch_stats(self, params, x) -> Tuple[ScoreStats, jax.Array]:
        hidden = self.model.forward(params, {self._batch_key: x})
        h = hidden[:, -1, :].astype(jnp.float32)
        c = self.cfg
        w = resolve_head_weight(self.model.cfg, params)
        stats = head_stats(h, w.astype(jnp.float32),
                           mode=c.head_mode, vocab_chunk=c.vocab_chunk,
                           pallas_bt=c.pallas_bt, pallas_bv=c.pallas_bv)
        return stats, h

    def _score_padded(self, params, xs):
        """xs: (n_mb, mb, ...) -> packed ScoreStats (n_mb * mb,), features."""

        def body(x):
            stats, h = self._microbatch_stats(params, x)
            if not self.cfg.with_features:
                h = jnp.zeros((x.shape[0], 0), jnp.float32)
            return stats, h

        stats, feats = jax.lax.map(body, xs)
        stats = compat.tree_map(lambda a: a.reshape(-1), stats)
        # explicit shape: reshape(-1, D) divides by D, which is 0 when
        # feature emission is disabled
        return stats, feats.reshape(
            (feats.shape[0] * feats.shape[1], feats.shape[2]))

    # -- pool plumbing -----------------------------------------------------

    def _pack(self, pool_x) -> Tuple[jax.Array, int]:
        """Pad the pool to a power-of-two microbatch count and fold it into
        (n_mb, mb, ...).  Bucketing (pow2 microbatch count, pow2 small-pool
        width) bounds the number of compiled programs at O(log N) as MCAL's
        candidate set shrinks across iterations."""
        x = jnp.asarray(pool_x)
        n = x.shape[0]
        n_mb, mb = pack_shape(n, self.cfg.microbatch)
        pad = n_mb * mb - n
        if pad:
            x = jnp.concatenate(
                [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
        self._note_pack((n_mb, mb))
        return x.reshape((n_mb, mb) + x.shape[1:]), n

    # -- public API --------------------------------------------------------

    def score_pages(self, params, xs) -> Tuple[ScoreStats, jax.Array]:
        """The jit-compiled packed scoring step over a pre-packed
        ``(n_mb, mb, ...)`` page (see :func:`pack_shape`) — the sweep
        runtime's page kernel (``serving.sweep.EngineSweepAdapter``).
        Returns PACKED statistics/features (padding rows included; the
        caller masks by its own valid count).  Shares the compile cache
        with :meth:`score`."""
        self._note_pack((int(xs.shape[0]), int(xs.shape[1])))
        return self._run_packed(params, xs)

    def cache_keys(self):
        """Sorted (n_mb, mb) pack buckets this engine has compiled."""
        return sorted(self.pack_keys)

    def _run_packed(self, params, xs):
        """Dispatch one packed page: the warmed AOT executable when the
        bucket was prewarmed, the jit wrapper otherwise."""
        exe = self._compiled.get((int(xs.shape[0]), int(xs.shape[1])))
        return (exe or self._score_all)(params, xs)

    def warm(self, params, keys) -> int:
        """AOT-compile the packed scoring step for the given (n_mb, mb)
        pack buckets (e.g. restored from a campaign checkpoint) without
        scoring a row; the executables are kept and dispatched directly.
        Feature classifiers only — token pools carry a sequence dim the
        pack key does not determine."""
        if self._batch_key != "features":
            raise NotImplementedError(
                "warm() supports feature-classifier engines")
        count = 0
        for n_mb, mb in keys:
            key = (int(n_mb), int(mb))
            if key in self._compiled:
                continue
            xs = jax.ShapeDtypeStruct(
                key + (self.model.cfg.input_dim,), jnp.float32)
            self._compiled[key] = self._score_all.lower(params, xs).compile()
            self.pack_keys.add(key)
            count += 1
        return count

    def score(self, params, pool_x) -> Tuple[ScoreStats, jax.Array]:
        """Score the whole pool.  Returns device-resident ScoreStats and
        (N, D) last-hidden features, trimmed to the true pool size."""
        xs, n = self._pack(pool_x)
        stats, feats = self._run_packed(params, xs)
        return (compat.tree_map(lambda a: a[:n], stats), feats[:n])

    def pool_features(self, params, pool_x) -> jax.Array:
        """Device-resident (N, D) pooled last-hidden features from the same
        jit-compiled sweep (identical microbatching / compile cache / mesh
        sharding as :meth:`score`).  The k-center selection engine
        (``core.selection_device``) consumes these directly — features
        never round-trip through the host."""
        if not self.cfg.with_features:
            raise ValueError(
                "engine built with with_features=False emits no features; "
                "construct it with ScoringConfig(with_features=True)")
        return self.score(params, pool_x)[1]

    def score_host(self, params, pool_x) -> Tuple[ScoreStats, np.ndarray]:
        """:meth:`score` fetched to host numpy (the task-facade boundary)."""
        stats, feats = self.score(params, pool_x)
        return (compat.tree_map(np.asarray, stats), np.asarray(feats))

    def top_k(self, params, pool_x, k: int,
              metric: str = "margin") -> np.ndarray:
        """Indices (into ``pool_x`` rows) of the k most uncertain samples,
        selected on device; sorted most-uncertain-first."""
        xs, n = self._pack(pool_x)
        k = min(k, n)
        if k <= 0:
            return np.zeros((0,), np.int64)
        stats, _ = self._run_packed(params, xs)
        scores = uncertainty_from_stats(stats, metric)
        valid = jnp.arange(scores.shape[0]) < n
        _, idx = jax.lax.top_k(jnp.where(valid, scores, -jnp.inf), k)
        return np.asarray(idx, np.int64)

    def rank_confident(self, params, pool_x,
                       metric: str = "margin") -> np.ndarray:
        """Full pool ordering most-confident-first (L(.)); scores come from
        the device sweep, the stable argsort stays on host."""
        stats, _ = self.score(params, pool_x)
        scores = np.asarray(uncertainty_from_stats(stats, metric))
        return np.argsort(scores, kind="stable")


# ---------------------------------------------------------------------------
# the seed host path, kept as the reference oracle
# ---------------------------------------------------------------------------


def score_pool_reference(model, params, pool_x, chunk: int = 2048,
                         batch_key: Optional[str] = None
                         ) -> Tuple[ScoreStats, np.ndarray]:
    """The seed implementation: chunked forward with a host round-trip per
    chunk, numpy statistics at the end.  Exact; used to validate the engine
    and as the benchmark baseline."""
    batch_key = batch_key or ("features" if model.cfg.family == "mlp"
                              else "tokens")
    w = resolve_head_weight(model.cfg, params)
    outs, feats = [], []
    n = np.asarray(pool_x).shape[0]
    for lo in range(0, n, chunk):
        x = jnp.asarray(np.asarray(pool_x)[lo:lo + chunk])
        hidden = model.forward(params, {batch_key: x})
        logits = jnp.einsum("btd,dv->btv", hidden.astype(jnp.float32),
                            w.astype(jnp.float32))[:, -1]
        outs.append(np.asarray(logits, np.float32))
        feats.append(np.asarray(hidden[:, -1], np.float32))
    logits = np.concatenate(outs)
    stats = L.score_stats_from_logits(jnp.asarray(logits))
    return compat.tree_map(np.asarray, stats), np.concatenate(feats)
