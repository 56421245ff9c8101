"""Fused vocab-projection + online top-2 / logsumexp / entropy Pallas kernel.

MCAL's pool-scoring hot spot: ranking millions of unlabeled samples by
margin / entropy / least-confidence requires the final projection
``hidden @ W_vocab`` over vocabularies up to 262k.  Materializing the
(T, V) logits in HBM is O(T*V) memory traffic; this kernel keeps logits as
MXU-aligned VMEM tiles only and carries per-token running statistics
(max, sum-exp, sum x*exp — fp32) across the vocab-tile grid dimension —
the online-softmax trick applied to MCAL's L(.)/M(.) metrics.  HBM traffic
drops from O(T*V) to O(T*D + D*V + T).

Grid: (T tiles, V tiles), V innermost so the scratch carry is sequential.
Per grid step: one (bt, D) x (D, bv) MXU matmul + row reductions.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(h_ref, w_ref, margin_ref, ent_ref, mlp_ref, top1_ref,
            m_sc, s_sc, u_sc, v1_sc, v2_sc, i1_sc, *, V: int, bv: int):
    vi = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(vi == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        s_sc[:] = jnp.zeros_like(s_sc)
        u_sc[:] = jnp.zeros_like(u_sc)
        v1_sc[:] = jnp.full_like(v1_sc, NEG_INF)
        v2_sc[:] = jnp.full_like(v2_sc, NEG_INF)
        i1_sc[:] = jnp.zeros_like(i1_sc)

    x = jnp.dot(h_ref[:], w_ref[:], preferred_element_type=jnp.float32)
    col = vi * bv + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    valid = col < V
    x = jnp.where(valid, x, NEG_INF)

    # online logsumexp + sum(x * e^x) (entropy numerator); every per-row
    # statistic is a (bt, 1) column so it keeps the tile's sublane layout
    m_old, s_old, u_old = m_sc[:], s_sc[:], u_sc[:]
    cm = jnp.max(x, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_old, cm)
    corr = jnp.exp(m_old - m_new)
    e = jnp.exp(x - m_new)
    s_sc[:] = s_old * corr + jnp.sum(e, axis=-1, keepdims=True)
    u_sc[:] = u_old * corr + jnp.sum(jnp.where(valid, x, 0.0) * e, axis=-1,
                                     keepdims=True)
    m_sc[:] = m_new

    # online top-2 merge: tile top-2 vs carried top-2.  The tile argmax is
    # the first column attaining the max (min over matching iotas)
    local = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    c1 = cm
    a1 = jnp.min(jnp.where(x == c1, local, bv), axis=-1, keepdims=True)
    x2 = jnp.where(local == a1, NEG_INF, x)
    c2 = jnp.max(x2, axis=-1, keepdims=True)
    v1_old, v2_old, i1_old = v1_sc[:], v2_sc[:], i1_sc[:]
    v1_new = jnp.maximum(v1_old, c1)
    v2_new = jnp.maximum(jnp.minimum(v1_old, c1), jnp.maximum(v2_old, c2))
    i1_sc[:] = jnp.where(c1 > v1_old, a1 + vi * bv, i1_old)
    v1_sc[:] = v1_new
    v2_sc[:] = v2_new

    @pl.when(vi == nv - 1)
    def _emit():
        s = jnp.maximum(s_sc[:], 1e-30)
        lse = m_sc[:] + jnp.log(s)
        margin_ref[:] = v1_sc[:] - v2_sc[:]
        ent_ref[:] = lse - u_sc[:] / s
        mlp_ref[:] = v1_sc[:] - lse
        top1_ref[:] = i1_sc[:]


@functools.partial(jax.jit, static_argnames=("bt", "bv", "interpret"))
def margin_head(hidden: jax.Array, w_vocab: jax.Array, *,
                bt: int = 128, bv: int = 512,
                interpret: bool = True) -> Tuple[jax.Array, ...]:
    """hidden: (T, D); w_vocab: (D, V) ->
    (margin (T,), entropy (T,), max_logprob (T,), top1 (T,) i32), fp32.

    BlockSpecs: hidden (bt, D) and weight (D, bv) tiles live in VMEM; with
    the defaults and D=8192 that is bt*D*2 + D*bv*2 ~ 10 MB < v5e VMEM.
    T/V are padded up to tile multiples; padded vocab columns are masked.
    """
    T, D = hidden.shape
    D2, V = w_vocab.shape
    assert D == D2, (hidden.shape, w_vocab.shape)
    Tp = -(-T // bt) * bt
    Vp = -(-V // bv) * bv
    if Tp != T:
        hidden = jnp.pad(hidden, ((0, Tp - T), (0, 0)))
    if Vp != V:
        w_vocab = jnp.pad(w_vocab, ((0, 0), (0, Vp - V)))
    grid = (Tp // bt, Vp // bv)

    # per-row statistics travel as (Tp, 1) columns: Mosaic refuses 1-D
    # (bt,) blocks whose tiling differs from XLA's layout of the array
    out_shape = [
        jax.ShapeDtypeStruct((Tp, 1), jnp.float32),  # margin
        jax.ShapeDtypeStruct((Tp, 1), jnp.float32),  # entropy
        jax.ShapeDtypeStruct((Tp, 1), jnp.float32),  # max_logprob
        jax.ShapeDtypeStruct((Tp, 1), jnp.int32),    # top1
    ]
    stat_spec = pl.BlockSpec((bt, 1), lambda t, v: (t, 0))
    outs = pl.pallas_call(
        functools.partial(_kernel, V=V, bv=bv),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, D), lambda t, v: (t, 0)),
            pl.BlockSpec((D, bv), lambda t, v: (0, v)),
        ],
        out_specs=[stat_spec] * 4,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bt, 1), jnp.float32),  # m
            pltpu.VMEM((bt, 1), jnp.float32),  # s
            pltpu.VMEM((bt, 1), jnp.float32),  # u
            pltpu.VMEM((bt, 1), jnp.float32),  # v1
            pltpu.VMEM((bt, 1), jnp.float32),  # v2
            pltpu.VMEM((bt, 1), jnp.int32),    # i1
        ],
        interpret=interpret,
    )(hidden, w_vocab)
    return tuple(o[:T, 0] for o in outs)
