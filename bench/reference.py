"""Plain references for the layers a window campaign drives.

Written from the published description of each step, in straightforward
``jax.numpy``, and importing nothing of the program:

* the labeler's retrain: the MLP (input projection, ``depth`` residual
  ReLU blocks, RMS norm, class head), its seeded initialization, the
  epoch shuffles, cross-entropy, global-norm gradient clipping and AdamW
  at a constant learning rate.  :func:`first_losses` follows the first
  steps of a retrain and returns their losses; :func:`retrain` runs it
  through and returns the weights;
* the pool sweep: :func:`top1_margin` scores rows under a set of weights
  (the predicted class, and the margin between the two highest class
  scores that ranks rows for labeling).

``dtype=float32`` computes every matmul at ``Precision.HIGHEST``;
``dtype=bfloat16`` is the control, the same arithmetic one precision
below what the configuration states.
"""
from __future__ import annotations

import functools
import math
import zlib
from typing import Dict, Tuple

import numpy as np

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _precision(dtype):
    return HIGHEST if jnp.dtype(dtype) == jnp.float32 else None


def next_pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def batch_plan(n: int, batch: int) -> Tuple[int, int]:
    """``(steps_per_epoch, rows_per_step)`` of a retrain over ``n`` rows:
    steps per epoch rounded up to a power of two, every step a full batch
    (short sets take one power-of-two batch of at least 8 rows)."""
    if n >= batch:
        return next_pow2(math.ceil(n / batch)), batch
    return 1, max(next_pow2(n), 8)


# -- the labeler ---------------------------------------------------------------


def init_params(key, dim: int, hidden: int, depth: int, classes: int,
                dtype=jnp.float32) -> Dict:
    """Seeded initialization: each weight matrix is drawn from its own key,
    ``fold_in(key, crc32(path))``, as N(0, 1/fan_in) with the fan-in taken
    over all axes but the last; biases and the norm scale start at zero."""
    def normal(path: str, shape, fan_in: int):
        k = jax.random.fold_in(key, zlib.crc32(path.encode()) % (2 ** 31))
        w = jax.random.normal(k, shape, jnp.float32) / np.sqrt(fan_in)
        return w.astype(dtype)

    return {
        "b_in": jnp.zeros((hidden,), dtype),
        "blocks": {
            "b": jnp.zeros((depth, hidden), dtype),
            "w": normal("['blocks']['w']", (depth, hidden, hidden),
                        depth * hidden),
        },
        "cls_head": normal("['cls_head']", (hidden, classes), hidden),
        "final_norm": {"scale": jnp.zeros((hidden,), dtype)},
        "w_in": normal("['w_in']", (dim, hidden), dim),
    }


def features(params: Dict, x, dtype=jnp.float32):
    """The normed last hidden state, (rows, hidden)."""
    prec = _precision(dtype)
    h = jax.nn.relu(jnp.dot(x.astype(dtype), params["w_in"], precision=prec)
                    + params["b_in"])
    for w, b in zip(params["blocks"]["w"], params["blocks"]["b"]):
        h = jax.nn.relu(jnp.dot(h, w, precision=prec) + b) + h
    var = jnp.mean(h * h, axis=-1, keepdims=True)
    return h * jax.lax.rsqrt(var + 1e-6) * (1 + params["final_norm"]["scale"])


def loss(params: Dict, x, y, dtype=jnp.float32):
    """Mean cross-entropy of the class head over the batch."""
    logits = jnp.dot(features(params, x, dtype), params["cls_head"],
                     precision=_precision(dtype))
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - picked)


def adamw_step(params, m, v, t, grads, *, lr, b1, b2, eps, weight_decay,
               clip):
    """Clip the gradient to global norm ``clip``, then one AdamW step
    (decoupled weight decay on matrices only) at step count ``t`` >= 1."""
    leaves = jax.tree.leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
    scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-12))
    grads = jax.tree.map(lambda g: g * scale.astype(g.dtype), grads)
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
    bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t

    def upd(p, a, s):
        u = (a / bc1) / (jnp.sqrt(s / bc2) + eps)
        if p.ndim >= 2:
            u = u + weight_decay * p
        return (p - lr * u).astype(p.dtype)

    return jax.tree.map(upd, params, m, v), m, v


def epoch_order(shuffle_key_data, epoch: int, n_pad: int, n: int):
    """Row order of one epoch: a permutation of the padded range with its
    real rows (< n) moved, in order, to the front."""
    key = jax.random.wrap_key_data(shuffle_key_data)
    perm = jax.random.permutation(jax.random.fold_in(key, epoch), n_pad)
    return perm[jnp.argsort(perm >= n, stable=True)]


@functools.partial(jax.jit, static_argnames=(
    "steps", "spe", "bs", "hidden", "depth", "classes", "dtype", "opt"))
def _first_losses(seed_key, x, y, n, *, steps, spe, bs, hidden, depth,
                  classes, dtype, opt):
    opt = dict(opt)
    init_key, shuffle_key = jax.random.split(seed_key)
    params = init_params(init_key, x.shape[1], hidden, depth, classes, dtype)
    kd = jax.random.key_data(jax.random.fold_in(shuffle_key, n))
    orders = jnp.stack([epoch_order(kd, e, spe * bs, n)
                        for e in range((steps - 1) // spe + 1)])
    zeros = jax.tree.map(jnp.zeros_like, params)

    def step(carry, t):
        params, m, v = carry
        rows = orders[t // spe][((t % spe) * bs + jnp.arange(bs)) % n]
        val, grads = jax.value_and_grad(loss)(params, x[rows], y[rows],
                                              dtype)
        params, m, v = adamw_step(params, m, v, t + 1, grads, **opt)
        return (params, m, v), val.astype(jnp.float32)

    _, out = jax.lax.scan(step, (params, zeros, zeros),
                          jnp.arange(steps, dtype=jnp.int32))
    return out


@functools.partial(jax.jit, static_argnames=(
    "epochs", "spe", "bs", "hidden", "depth", "classes", "dtype", "opt"))
def _retrain(seed_key, x, y, n, *, epochs, spe, bs, hidden, depth, classes,
             dtype, opt):
    opt = dict(opt)
    init_key, shuffle_key = jax.random.split(seed_key)
    params = init_params(init_key, x.shape[1], hidden, depth, classes, dtype)
    kd = jax.random.key_data(jax.random.fold_in(shuffle_key, n))
    orders = jax.vmap(lambda e: epoch_order(kd, e, spe * bs, n))(
        jnp.arange(epochs))
    zeros = jax.tree.map(jnp.zeros_like, params)

    def step(carry, t):
        p, m, v = carry
        rows = orders[t // spe][((t % spe) * bs + jnp.arange(bs)) % n]
        grads = jax.grad(loss)(p, x[rows], y[rows], dtype)
        p, m, v = adamw_step(p, m, v, t + 1, grads, **opt)
        return (p, m, v), None

    (final, _, _), _ = jax.lax.scan(step, (params, zeros, zeros),
                                    jnp.arange(epochs * spe, dtype=jnp.int32))
    return final


def retrain(seed: int, x: np.ndarray, y: np.ndarray, *, hidden: int,
            depth: int, classes: int, batch: int, epochs: int, lr: float,
            weight_decay: float, b1: float = 0.9, b2: float = 0.95,
            eps: float = 1e-8, clip: float = 1.0, dtype=jnp.float32):
    """The weights of a whole retrain from scratch at ``dtype`` (float32
    at ``HIGHEST``)."""
    n = int(x.shape[0])
    spe, bs = batch_plan(n, batch)
    xp = np.zeros((spe * bs, x.shape[1]), np.float32)
    xp[:n] = x
    yp = np.zeros((spe * bs,), np.int32)
    yp[:n] = y
    opt = (("lr", lr), ("b1", b1), ("b2", b2), ("eps", eps),
           ("weight_decay", weight_decay), ("clip", clip))
    return _retrain(jax.random.key(seed), jnp.asarray(xp), jnp.asarray(yp),
                    jnp.int32(n), epochs=epochs, spe=spe, bs=bs,
                    hidden=hidden, depth=depth, classes=classes,
                    dtype=jnp.dtype(dtype).name, opt=opt)


@functools.partial(jax.jit, static_argnames=("dtype",))
def _top2(params, x, *, dtype):
    logits = jnp.dot(features(params, x, dtype), params["cls_head"],
                     precision=_precision(dtype)).astype(jnp.float32)
    top, idx = jax.lax.top_k(logits, 2)
    return idx[:, 0], top[:, 0] - top[:, 1]


def top1_margin(params, x: np.ndarray, dtype=jnp.float32,
                block: int = 8192) -> Tuple[np.ndarray, np.ndarray]:
    """Each row's predicted class and margin (the gap between its two
    highest class scores) under ``params``, ``block`` rows at a time."""
    name = jnp.dtype(dtype).name
    n = int(x.shape[0])
    top1 = np.zeros((n,), np.int64)
    margin = np.zeros((n,), np.float64)
    for lo in range(0, n, block):
        xb = np.zeros((block, x.shape[1]), np.float32)
        xb[:min(block, n - lo)] = x[lo:lo + block]
        i, m = _top2(params, jnp.asarray(xb), dtype=name)
        k = min(block, n - lo)
        top1[lo:lo + k] = np.asarray(i)[:k]
        margin[lo:lo + k] = np.asarray(m, np.float64)[:k]
    return top1, margin


def first_losses(seed: int, x: np.ndarray, y: np.ndarray, *, hidden: int,
                 depth: int, classes: int, batch: int, lr: float,
                 weight_decay: float, steps: int = 4, b1: float = 0.9,
                 b2: float = 0.95, eps: float = 1e-8, clip: float = 1.0,
                 dtype=jnp.float32) -> np.ndarray:
    """Losses of the first ``steps`` steps of a retrain from scratch on the
    rows ``(x, y)`` (in their labeled order), seeded by ``seed``."""
    n = int(x.shape[0])
    spe, bs = batch_plan(n, batch)
    opt = (("lr", lr), ("b1", b1), ("b2", b2), ("eps", eps),
           ("weight_decay", weight_decay), ("clip", clip))
    # rows padded to the plan's size, so one program serves every n
    # of a bucket; padding rows are never drawn
    xp = np.zeros((spe * bs, x.shape[1]), np.float32)
    xp[:n] = x
    yp = np.zeros((spe * bs,), np.int32)
    yp[:n] = y
    out = _first_losses(jax.random.key(seed), jnp.asarray(xp),
                        jnp.asarray(yp), jnp.int32(n), steps=steps,
                        spe=spe, bs=bs, hidden=hidden, depth=depth,
                        classes=classes, dtype=jnp.dtype(dtype).name,
                        opt=opt)
    return np.asarray(out, np.float64)
