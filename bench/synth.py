"""The benchmark's own pool generator.

A copy of the program's ``data.synth.make_classification`` kept with the
benchmark, so that a later change to the program cannot move the
yardstick: class centroids on a hypersphere, isotropic Gaussian noise
whose scale ``difficulty`` sets, and a "hard tail" of rows drawn near the
boundary between two classes.  ``tests/test_bench_synth.py`` pins a
seeded sample by its hash.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np


def make_classification(n: int, num_classes: int, dim: int,
                        difficulty: float, hard_frac: float,
                        seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """``(features (n, dim) float32, labels (n,) int64)`` from ``seed``."""
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
