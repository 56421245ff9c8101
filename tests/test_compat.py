"""The JAX shim: every public name binds to the JAX 0.9 API."""
import numpy as np

import jax
import jax.numpy as jnp

from repro import compat


def test_version_tuple_parsed():
    assert len(compat.JAX_VERSION) >= 2
    assert compat.JAX_VERSION >= (0, 9)


def test_tree_family_roundtrip():
    tree = {"a": jnp.arange(3), "b": [jnp.zeros(2), jnp.ones(1)]}
    leaves, treedef = compat.tree_flatten(tree)
    assert len(leaves) == 3
    rebuilt = compat.tree_unflatten(treedef, leaves)
    assert compat.tree_structure(rebuilt) == treedef
    doubled = compat.tree_map(lambda x: x * 2, tree)
    np.testing.assert_array_equal(doubled["a"], np.asarray([0, 2, 4]))


def test_tree_flatten_with_path_spellings():
    """flatten_with_path + keystr (keystr has no jax.tree spelling)."""
    tree = {"w": jnp.ones(2), "b": jnp.zeros(1)}
    flat = compat.tree_flatten_with_path(tree)[0]
    keys = sorted(compat.keystr(path) for path, _ in flat)
    assert keys == ["['b']", "['w']"]
    named = compat.tree_map_with_path(
        lambda path, x: compat.keystr(path), tree)
    assert named == {"w": "['w']", "b": "['b']"}


def test_make_mesh_tolerates_axis_types_everywhere():
    """axis_types=True builds a mesh with Auto axes."""
    mesh = compat.make_mesh((1,), ("data",), axis_types=True)
    assert mesh.axis_names == ("data",)
    assert mesh.devices.size == 1
    assert mesh.axis_types == compat.default_axis_types(1)


def test_default_axis_types_modern():
    types = compat.default_axis_types(2)
    assert types == (jax.sharding.AxisType.Auto,) * 2


def test_shard_map_normalizes_replication_kwarg():
    """Callers use the check_vma spelling of jax.shard_map."""
    mesh = compat.make_mesh((1,), ("data",))
    P = compat.PartitionSpec

    @compat.shard_map(mesh=mesh, in_specs=P("data"), out_specs=P("data"),
                      check_vma=False)
    def double(x):
        return x * 2

    np.testing.assert_array_equal(double(jnp.arange(4.0)),
                                  np.arange(4.0) * 2)


def test_cost_analysis_dict_normalizes_shapes():
    """A dict passes through as a copy; no analysis is an empty dict."""

    class Fake:
        def __init__(self, ret):
            self._ret = ret

        def cost_analysis(self):
            return self._ret

    ret = {"flops": 3.0}
    assert compat.cost_analysis_dict(Fake(ret)) == {"flops": 3.0}
    assert compat.cost_analysis_dict(Fake(ret)) is not ret
    assert compat.cost_analysis_dict(Fake(None)) == {}


def test_cost_analysis_dict_on_real_compiled():
    """A real compiled program's analysis comes back as a dict."""
    compiled = jax.jit(lambda x: x @ x).lower(
        jnp.ones((8, 8), jnp.float32)).compile()
    cost = compat.cost_analysis_dict(compiled)
    assert isinstance(cost, dict)
