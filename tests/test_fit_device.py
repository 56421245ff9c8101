"""Fused-scan retrain engine oracle grids.

The engine contract (same spirit as the selection/sweep engines): the
fused ``lax.scan`` program and the per-step host loop consume the
IDENTICAL permutation sequence (``fit_device.epoch_orders``) over the
identical ``fit_plan`` schedule, so on a CPU host the trained params and
the per-step loss trace must agree BIT-EXACTLY — across ragged epoch
tails, sub-batch pools, and pow2 bucket boundaries.  The async fit path
must leave campaign economics untouched: an ``fit_async`` campaign's
iteration records match the synchronous campaign's exactly.
"""
import json

import numpy as np
import pytest

import jax

from repro import compat
from repro.configs.base import ModelConfig, TrainConfig
from repro.models.registry import get_model
from repro.training.fit_device import (FitConfig, FitEngine, epoch_orders,
                                       fit_plan)


def _make_engine(epochs=3, batch=32, dim=8, classes=5, **kw):
    cfg = ModelConfig(name="fit-test", family="mlp", num_layers=2,
                      d_model=32, num_classes=classes, input_dim=dim,
                      dtype="float32", remat="none")
    model = get_model(cfg)
    tc = TrainConfig(learning_rate=1e-2, schedule="constant",
                     weight_decay=1e-4, grad_clip=1.0)
    return model, tc, FitEngine(model, tc,
                                FitConfig(epochs=epochs, batch_size=batch),
                                **kw)


def _data(n, dim=8, classes=5, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.normal(size=(n, dim)).astype(np.float32),
            rng.integers(0, classes, n).astype(np.int32))


def _leaves_equal(a, b):
    la, lb = compat.tree_leaves(a), compat.tree_leaves(b)
    assert len(la) == len(lb)
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(la, lb))


# ---------------------------------------------------------------------------
# oracle grids: fused scan vs per-step host loop, exact
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,batch,epochs", [
    (64, 32, 2),     # even split
    (100, 32, 3),    # ragged epoch tail (wraps into the permutation front)
    (20, 64, 3),     # sub-batch pool (n < batch -> pow2 batch, wrap)
    (257, 64, 2),    # pow2 bucket boundary (spe jumps 4 -> 8)
    (5, 32, 2),      # tiny pool (bs floors at 8)
])
def test_fused_matches_hostloop_exact(n, batch, epochs):
    _, _, eng = _make_engine(epochs=epochs, batch=batch)
    x, y = _data(n)
    key = jax.random.key(7)
    p_fused, l_fused = eng.fit(key, x, y)
    p_ref, l_ref = eng.fit_reference(key, x, y)
    assert _leaves_equal(p_fused, p_ref), \
        "fused params diverged from the per-step host loop"
    np.testing.assert_array_equal(np.asarray(l_fused), np.asarray(l_ref))
    spe, bs, n_pad = fit_plan(n, batch)
    assert l_fused.shape == (epochs * spe,)


def test_fit_deterministic_and_seed_sensitive():
    _, _, eng = _make_engine()
    x, y = _data(80)
    p1, l1 = eng.fit(jax.random.key(3), x, y)
    p2, l2 = eng.fit(jax.random.key(3), x, y)
    assert _leaves_equal(p1, p2)
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
    _, l3 = eng.fit(jax.random.key(4), x, y)
    assert not np.array_equal(np.asarray(l1), np.asarray(l3))


def test_epoch_orders_prefix_is_permutation():
    """The first-n prefix of every epoch order is a permutation of
    [0, n); padding rows are stably pushed to the tail."""
    kd = jax.random.key_data(jax.random.key(0))
    for n, n_pad in ((100, 128), (128, 128), (5, 8)):
        orders = np.asarray(epoch_orders(kd, 4, n_pad, np.int32(n)))
        assert orders.shape == (4, n_pad)
        for row in orders:
            assert sorted(row[:n].tolist()) == list(range(n))
            assert sorted(row[n:].tolist()) == list(range(n, n_pad))
    # different epochs shuffle differently
    assert not np.array_equal(orders[0], orders[1])


# ---------------------------------------------------------------------------
# compile-cache bucketing
# ---------------------------------------------------------------------------


def test_growing_pool_reuses_compile_cache():
    """Successive MCAL iterations with growing |B| inside one pack_shape
    bucket share ONE compiled program; a wide size range stays O(log N)."""
    _, _, eng = _make_engine(epochs=1, batch=32)
    for n in (130, 160, 200, 256):   # all bucket to (8, 32, 256)
        x, y = _data(n)
        eng.fit(jax.random.key(0), x, y)
    assert eng.cache_keys() == [(8, 32, 256)]
    for n in (300, 600, 1200):
        x, y = _data(n)
        eng.fit(jax.random.key(0), x, y)
    assert len(eng.cache_keys()) == 4   # one new bucket per pow2 doubling


def test_warm_prebuilds_cache_from_keys():
    _, _, eng = _make_engine(epochs=1, batch=32)
    x, y = _data(100)
    eng.fit(jax.random.key(0), x, y)
    keys = eng.cache_keys()
    _, _, eng2 = _make_engine(epochs=1, batch=32)
    # JSON round-trip: checkpoints persist keys as lists
    assert eng2.warm(json.loads(json.dumps(keys))) == len(keys)
    assert eng2.cache_keys() == keys


def test_fused_retrain_lowers_under_its_own_name():
    """The fused retrain's program is named for the device trace
    (``jit_fit_epochs``), which reduces traces by program name."""
    from repro.training.train_loop import abstract_train_state
    model, tc, eng = _make_engine(epochs=1, batch=32)
    prog, (_, _, n_pad) = eng._program(100)
    ab_state, _ = abstract_train_state(model, tc)
    text = prog.lower(
        ab_state, jax.ShapeDtypeStruct((n_pad, 8), np.float32),
        jax.ShapeDtypeStruct((n_pad,), np.int32),
        jax.ShapeDtypeStruct((), np.int32),
        jax.random.key_data(jax.random.key(0))).as_text()
    assert "module @jit_fit_epochs" in text


# ---------------------------------------------------------------------------
# campaign-resident pool
# ---------------------------------------------------------------------------


def test_resident_extension_matches_oneshot_fit():
    """Scatter-extending the device-resident pool across MCAL-style
    acquisitions trains bit-identically to uploading the whole set."""
    _, _, eng = _make_engine(epochs=2, batch=32)
    x, y = _data(200)
    key = jax.random.key(5)
    p_full, l_full = eng.fit(key, x, y)
    _, _, eng2 = _make_engine(epochs=2, batch=32)
    for lo, hi in ((0, 40), (40, 90), (90, 200)):   # crosses a bucket grow
        eng2.extend_resident(x[lo:hi], y[lo:hi])
    assert eng2.resident_size == 200
    p_res, l_res = eng2.fit_resident(key)
    assert _leaves_equal(p_full, p_res)
    np.testing.assert_array_equal(np.asarray(l_full), np.asarray(l_res))


def test_resident_reset_and_empty_raises():
    _, _, eng = _make_engine()
    with pytest.raises(ValueError):
        eng.fit_resident(jax.random.key(0))
    x, y = _data(30)
    eng.extend_resident(x, y)
    assert eng.resident_size == 30
    eng.reset_resident()
    assert eng.resident_size == 0


# ---------------------------------------------------------------------------
# mesh wiring
# ---------------------------------------------------------------------------


def test_mesh_fit_matches_unmeshed():
    """The mesh program (state shardings via state_pspecs, the
    mesh-aware raw step) lowers and agrees with the unmeshed engine on a
    host mesh."""
    from repro.compat import make_mesh
    mesh = make_mesh((jax.device_count(), 1), ("data", "model"))
    model, tc, eng = _make_engine(epochs=2, batch=32)
    eng_mesh = FitEngine(model, tc, FitConfig(epochs=2, batch_size=32),
                         mesh=mesh)
    x, y = _data(100)
    key = jax.random.key(2)
    p_plain, l_plain = eng.fit(key, x, y)
    p_mesh, l_mesh = eng_mesh.fit(key, x, y)
    np.testing.assert_allclose(np.asarray(l_mesh), np.asarray(l_plain),
                               rtol=1e-6, atol=1e-6)
    for a, b in zip(compat.tree_leaves(p_plain),
                    compat.tree_leaves(p_mesh)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# async handle
# ---------------------------------------------------------------------------


def test_submit_fit_matches_sync():
    _, _, eng = _make_engine()
    x, y = _data(90)
    key = jax.random.key(9)
    p_sync, l_sync = eng.fit(key, x, y)
    fut = eng.submit_fit(key, x, y)
    p_async, l_async = fut.result()
    assert fut.done()
    assert _leaves_equal(p_sync, p_async)
    np.testing.assert_array_equal(np.asarray(l_sync), np.asarray(l_async))


# ---------------------------------------------------------------------------
# LiveTask + campaign integration
# ---------------------------------------------------------------------------


def _live_task(x, y, **kw):
    from repro.core import LiveTask
    return LiveTask(features=x, groundtruth=y, num_classes=10, epochs=3,
                    seed=4, sweep_page=256, score_microbatch=256, **kw)


@pytest.fixture(scope="module")
def small_pool():
    from repro.data.synth import make_classification
    return make_classification(700, num_classes=10, dim=16,
                               difficulty=0.3, seed=4)


def test_live_task_fused_matches_hostloop_oracle(small_pool):
    """LiveTask.train through the fused engine == the per-step host-loop
    oracle path, bit-exactly (same task seed -> same permutations)."""
    x, y = small_pool
    fused, oracle = _live_task(x, y), _live_task(x, y, fit_fused=False)
    idx = np.arange(200)
    c_f = fused.train(idx, y[:200])
    c_o = oracle.train(idx, y[:200])
    assert c_f == c_o   # nominal cost: c_u * n on both paths
    assert _leaves_equal(fused._params, oracle._params)


def test_live_task_resident_matches_upload(small_pool):
    x, y = small_pool
    a, b = _live_task(x, y), _live_task(x, y, fit_resident=True)
    idx1 = np.arange(150)
    idx2 = np.arange(260)           # append-only growth
    for t in (a, b):
        t.train(idx1, y[idx1])
        t.train(idx2, y[idx2])
    assert _leaves_equal(a._params, b._params)
    # non-append update forces a resident rebuild, still exact
    idx3 = np.concatenate([np.arange(100), np.arange(300, 400)])
    a.train(idx3, y[idx3])
    b.train(idx3, y[idx3])
    assert _leaves_equal(a._params, b._params)


def _campaign(x, y, *, fit_async, max_iters=3, **task_kw):
    from repro.core import AMAZON, MCALCampaign, MCALConfig
    task = _live_task(x, y, **task_kw)
    camp = MCALCampaign(task, AMAZON,
                        MCALConfig(seed=4, max_iters=max_iters,
                                   delta0_frac=0.02, fit_async=fit_async))
    camp.bootstrap()
    while not camp.done:
        camp.iteration()
    return camp


def test_async_fit_campaign_matches_sync(small_pool):
    """fit_async defers each retrain + measurement onto the engine
    worker; the folded records must be identical to the synchronous
    campaign — acquisitions, eps history, ledger, commit labels."""
    x, y = small_pool
    sync = _campaign(x, y, fit_async=False)
    async_ = _campaign(x, y, fit_async=True)
    np.testing.assert_array_equal(sync.pool.B_idx, async_.pool.B_idx)
    assert sync.eps_hist == async_.eps_hist
    assert sync.train_sizes == async_.train_sizes
    assert sync.train_costs == async_.train_costs
    assert [r.cstar for r in sync.history] == \
        [r.cstar for r in async_.history]
    assert [r.training_spent for r in sync.history] == \
        [r.training_spent for r in async_.history]
    a, b = sync.commit(), async_.commit()
    assert a.total_cost == pytest.approx(b.total_cost, rel=1e-12)
    np.testing.assert_array_equal(a.labels, b.labels)
    np.testing.assert_array_equal(a.machine_mask, b.machine_mask)


def test_async_fit_state_dict_folds_pending(small_pool):
    """state_dict during an in-flight async retrain folds it first — the
    checkpoint is indistinguishable from a synchronous campaign's."""
    x, y = small_pool
    from repro.core import AMAZON, MCALCampaign, MCALConfig

    def boot(fit_async):
        camp = MCALCampaign(_live_task(x, y), AMAZON,
                            MCALConfig(seed=4, delta0_frac=0.02,
                                       fit_async=fit_async))
        camp.bootstrap()   # leaves a pending fit in async mode
        return camp

    sd_async = boot(True).state_dict()
    sd_sync = boot(False).state_dict()
    assert sd_async["train_sizes"] == sd_sync["train_sizes"]
    assert sd_async["eps_hist"] == sd_sync["eps_hist"]
    assert sd_async["ledger"] == sd_sync["ledger"]


def test_async_fit_arch_selection_matches_sync(small_pool):
    """Architecture selection with fit_async retrains every candidate
    concurrently; shared-ledger payments land at submit time, so the
    winner, every candidate's history, and the shared ledger must be
    identical to the synchronous run."""
    from repro.core import AMAZON, MCALConfig, select_architecture

    x, y = small_pool

    def run(fit_async):
        tasks = {
            "small": _live_task(x, y, hidden=32),
            "big": _live_task(x, y, hidden=64),
        }
        cfg = MCALConfig(seed=4, max_iters=4, delta0_frac=0.02,
                         fit_async=fit_async)
        return select_architecture(tasks, AMAZON, cfg,
                                   max_explore_iters=3)

    (w_s, res_s, hist_s) = run(False)
    (w_a, res_a, hist_a) = run(True)
    assert w_s == w_a
    for name in hist_s:
        assert [r.cstar for r in hist_s[name]] == \
            [r.cstar for r in hist_a[name]]
        assert [r.training_spent for r in hist_s[name]] == \
            [r.training_spent for r in hist_a[name]]
        assert [r.human_spent for r in hist_s[name]] == \
            [r.human_spent for r in hist_a[name]]
    assert res_s.total_cost == pytest.approx(res_a.total_cost, rel=1e-12)
    np.testing.assert_array_equal(res_s.labels, res_a.labels)


def test_warm_executables_serve_dispatch_exactly():
    """warm() keeps the AOT executables and fit() dispatches them (jit's
    own cache is NOT populated by lower().compile()): a warmed engine
    must produce bit-identical results through the compiled path."""
    _, _, eng = _make_engine(epochs=2, batch=32)
    x, y = _data(120)
    key = jax.random.key(11)
    p_ref, l_ref = eng.fit(key, x, y)
    keys = eng.cache_keys()

    _, _, warmed = _make_engine(epochs=2, batch=32)
    assert warmed.warm(keys) == len(keys)
    assert set(warmed._compiled) == set(keys)   # executables retained
    p_w, l_w = warmed.fit(key, x, y)            # served by the AOT path
    assert _leaves_equal(p_ref, p_w)
    np.testing.assert_array_equal(np.asarray(l_ref), np.asarray(l_w))
