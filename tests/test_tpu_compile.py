"""Compile the campaign's device programs for a TPU v5e, without a chip.

The TPU compiler is installed beside the CPU backend: it compiles for a
described ``v5e:2x2`` topology that is not attached.  This catches what
interpret mode hides (Mosaic tiling and layout refusals, VMEM limits,
programs that do not fit the chip's 16 GB) at the shapes a 50,000-row,
10-class campaign runs.  Nothing executes; no result or time comes from
here.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file.
"""
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro import compat

V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else the compiler logs to /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # noqa: BLE001 - any refusal means no TPU compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described-device compile cannot be read back without a chip:
        # keep the persistent cache out of it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def engines():
    """The campaign's own engine construction at LiveTask defaults
    (MLP depth 2, hidden 64, input 32; 10 classes)."""
    from repro.launch.orchestrator import SharedEngines
    eng = SharedEngines.build(32, 10)
    yield eng
    eng.close()


def _on(sharding, tree):
    return compat.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _fits(compiled) -> int:
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)
    assert used < V5E_HBM_BYTES, used
    return used


@pytest.mark.parametrize("V", [10, 100, 1000])
def test_margin_head_compiles_for_v5e(one_chip, V):
    from repro.kernels.margin_head import margin_head
    h = jax.ShapeDtypeStruct((2048, 64), jnp.float32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((64, V), jnp.float32, sharding=one_chip)
    compiled = margin_head.lower(h, w, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


@pytest.mark.parametrize("anchors", [512, 8192])
def test_pairwise_sqdist_compiles_for_v5e(one_chip, anchors):
    """A k-center row block of a 50k pool (65,536 padded rows of 64
    features) against the padded anchor set."""
    from repro.kernels.pairwise_dist import pairwise_sqdist
    x = jax.ShapeDtypeStruct((65536, 64), jnp.float32, sharding=one_chip)
    c = jax.ShapeDtypeStruct((anchors, 64), jnp.float32, sharding=one_chip)
    compiled = pairwise_sqdist.lower(x, c, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


@pytest.mark.parametrize("n", [2048, 65536])
def test_fused_fit_program_compiles_for_v5e(one_chip, engines, n):
    from repro.training.fit_device import fit_plan
    from repro.training.train_loop import abstract_train_state
    fit = engines.fit
    prog, key = fit._program(n)
    assert key == fit_plan(n, fit.cfg.batch_size)
    n_pad = key[2]
    state, _ = abstract_train_state(fit.model, fit.tc)
    kd = jax.random.key_data(jax.random.key(0))
    args = _on(one_chip, (
        state,
        jax.ShapeDtypeStruct((n_pad, 32), jnp.float32),
        jax.ShapeDtypeStruct((n_pad,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct(kd.shape, kd.dtype)))
    compiled = prog.lower(*args).compile()
    _fits(compiled)


def test_scoring_page_step_compiles_for_v5e(one_chip, engines):
    """One sweep page: 4 microbatches of 2048 rows of 32 features."""
    assert engines.scoring.cfg.microbatch == 2048
    params = _on(one_chip, engines.model.abstract_params())
    xs = jax.ShapeDtypeStruct((4, 2048, 32), jnp.float32, sharding=one_chip)
    compiled = engines.scoring._score_all.lower(params, xs).compile()
    _fits(compiled)
