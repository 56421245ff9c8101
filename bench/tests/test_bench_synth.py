"""The benchmark's pool generator is pinned: a later change to it (or a
copy that drifts) moves this hash."""
import hashlib
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import harness  # noqa: E402

mlp = harness.load_module("labelers", "mlp")
make_classification = mlp.make_classification

PINNED = "352bc5f6adcd5544e7ac0c1ce1a551f8fa45511368a47bea5d001ef2ca1bd166"


@pytest.mark.parametrize("make", [
    lambda: make_classification(64, 10, 16, 0.3, 0.25, 1234),
    # the ``mlp`` labeler's pool of a configuration is that sample
    lambda: mlp.pool({"pool": 64, "classes": 10, "features": 16,
                      "difficulty": 0.3, "hard_frac": 0.25}, 1234),
], ids=["generator", "mlp_pool"])
def test_seeded_sample_is_pinned(make):
    x, y = make()
    assert x.shape == (64, 16) and y.shape == (64,)
    assert hashlib.sha256(x.tobytes() + y.tobytes()).hexdigest() == PINNED


def test_same_seed_same_pool_other_seed_other_pool():
    a = make_classification(256, 100, 512, 0.3, 0.25, 2 ** 31 + 5)
    b = make_classification(256, 100, 512, 0.3, 0.25, 2 ** 31 + 5)
    c = make_classification(256, 100, 512, 0.3, 0.25, 2 ** 31 + 6)
    assert (a[0] == b[0]).all() and (a[1] == b[1]).all()
    assert not (a[0] == c[0]).all()
