"""The ``mlp`` labeler: the program's ``LiveTask`` MLP over a pool of
feature vectors (an input projection, ``depth`` residual ``hidden x
hidden`` blocks, an RMS norm and a class head).

A module of ``bench/labelers`` is found by its configuration's
``labeler.arch`` and owns what the harness and the checks do with that
labeler family (``harness.LABELER`` names the functions):

* ``pool(config, seed)``: the pool's rows ``x`` and ground truth ``y``,
  from the seed; the checks only index the rows of ``x``;
* ``engines(config, traffic)``: the program's shared engine bundle;
* ``task(config, traffic, x, y, seed, engines)``: the program's task for
  one campaign at campaign seed ``seed``;
* ``first_losses(config, seed, x, y, steps, dtype)``,
  ``retrain(config, seed, x, y, dtype)`` and
  ``top1_margin(params, x, dtype)``: the plain reference of a retrain's
  first losses, of a whole retrain's weights and of a sweep's top-1 and
  margin;
* ``macs_per_row(config)``: multiply-accumulates of one forward pass over
  one row, from which ``bench.layers`` counts a campaign's model FLOPs.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from bench.labelers import mlp_reference as reference


def make_classification(n: int, num_classes: int, dim: int,
                        difficulty: float, hard_frac: float,
                        seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """``(features (n, dim) float32, labels (n,) int64)`` from ``seed``.

    A copy of the program's ``data.synth.make_classification`` kept with
    the benchmark, so that a later change to the program cannot move the
    yardstick: class centroids on a hypersphere, isotropic Gaussian noise
    whose scale ``difficulty`` sets, and a "hard tail" of rows drawn near
    the boundary between two classes.  ``test_bench_synth.py`` pins a
    seeded sample by its hash."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(num_classes, dim))
    centers /= np.linalg.norm(centers, axis=1, keepdims=True)
    labels = rng.integers(0, num_classes, n)
    # the noise-to-margin ratio (and so the Bayes error) does not depend
    # on the width: per-dimension sigma scales with sqrt(32 / dim)
    base_sigma = (0.1 + 0.5 * difficulty) * np.sqrt(32.0 / dim)
    x = centers[labels] + rng.normal(size=(n, dim)) * base_sigma
    hard = rng.random(n) < hard_frac
    other = (labels + rng.integers(1, num_classes, n)) % num_classes
    lam = rng.uniform(0.25, 0.48, n)
    boundary = (1 - lam[:, None]) * centers[labels] + \
        lam[:, None] * centers[other] + \
        rng.normal(size=(n, dim)) * (base_sigma * 0.6)
    x[hard] = boundary[hard]
    return x.astype(np.float32), labels.astype(np.int64)


def pool(config: Dict, seed: int) -> Tuple[np.ndarray, np.ndarray]:
    return make_classification(config["pool"], config["classes"],
                               config["features"], config["difficulty"],
                               config["hard_frac"], seed)


def engines(config: Dict, traffic: Dict):
    from repro.launch.orchestrator import SharedEngines
    lab = config["labeler"]
    return SharedEngines.build(
        config["features"], config["classes"], hidden=lab["hidden"],
        depth=lab["depth"], epochs=lab["epochs"], batch_size=lab["batch"],
        learning_rate=lab["learning_rate"],
        score_microbatch=traffic["score_microbatch"],
        sweep_page=traffic["sweep_page"])


def task(config: Dict, traffic: Dict, x, y, seed: int, engines):
    from repro.core import LiveTask
    return LiveTask(features=x, groundtruth=y,
                    num_classes=config["classes"], seed=seed,
                    engines=engines, sweep_page=traffic["sweep_page"],
                    score_microbatch=traffic["score_microbatch"])


def _fit_kw(config: Dict) -> Dict:
    lab = config["labeler"]
    return dict(hidden=lab["hidden"], depth=lab["depth"],
                classes=config["classes"], batch=lab["batch"],
                lr=lab["learning_rate"], weight_decay=lab["weight_decay"])


def first_losses(config: Dict, seed: int, x, y, steps: int,
                 dtype: str = "float32") -> np.ndarray:
    return reference.first_losses(seed, x, y, steps=steps, dtype=dtype,
                                  **_fit_kw(config))


def retrain(config: Dict, seed: int, x, y, dtype: str = "float32"):
    return reference.retrain(seed, x, y, epochs=config["labeler"]["epochs"],
                             dtype=dtype, **_fit_kw(config))


def top1_margin(params, x, dtype: str = "float32"):
    return reference.top1_margin(params, x, dtype)


def macs_per_row(config: Dict) -> int:
    """The three matmuls; biases, activations and the norm are
    elementwise and not counted."""
    hidden, depth = config["labeler"]["hidden"], config["labeler"]["depth"]
    return (config["features"] * hidden + depth * hidden * hidden
            + hidden * config["classes"])
