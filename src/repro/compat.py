"""One import point for the JAX APIs whose spelling has drifted.

POLICY: repo code reaches these families through this module, never
directly, so the next JAX bump is a one-file change.  The repo targets
JAX 0.9 (see requirements.txt); every name below binds straight to the
0.9 API:

* the pytree family — ``jax.tree.*`` (``keystr`` from ``jax.tree_util``,
  which has no ``jax.tree`` spelling);
* ``make_mesh`` — ``jax.make_mesh`` with ``axis_types=True`` meaning
  ``(AxisType.Auto,) * ndim``;
* ``shard_map`` — the top-level ``jax.shard_map`` (``check_vma``);
* ``cost_analysis_dict`` — ``compiled.cost_analysis()`` as a plain dict.

Anything stable (``jax.jit``, ``jax.numpy``, ``NamedSharding``,
``PartitionSpec``) is intentionally NOT wrapped.
"""
from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple

import jax
import jax.tree_util as jtu
from jax.sharding import Mesh, NamedSharding, PartitionSpec  # noqa: F401  (re-export)

JAX_VERSION: Tuple[int, ...] = tuple(
    int(p) for p in jax.__version__.split(".")[:3] if p.isdigit())

__all__ = [
    "JAX_VERSION",
    # pytree family
    "tree_map", "tree_leaves", "tree_flatten", "tree_unflatten",
    "tree_structure", "tree_flatten_with_path", "tree_map_with_path",
    "keystr",
    # mesh / sharding
    "Mesh", "NamedSharding", "PartitionSpec", "make_mesh", "shard_map",
    "default_axis_types",
    # compiled-artifact introspection
    "cost_analysis_dict",
]


# ---------------------------------------------------------------------------
# pytree family
# ---------------------------------------------------------------------------

tree_map = jax.tree.map
tree_leaves = jax.tree.leaves
tree_flatten = jax.tree.flatten
tree_unflatten = jax.tree.unflatten
tree_structure = jax.tree.structure
tree_flatten_with_path = jax.tree.flatten_with_path
tree_map_with_path = jax.tree.map_with_path
keystr = jtu.keystr


# ---------------------------------------------------------------------------
# mesh construction
# ---------------------------------------------------------------------------


def default_axis_types(n: int):
    """``(AxisType.Auto,) * n``."""
    return (jax.sharding.AxisType.Auto,) * n


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              axis_types: Any = None, devices=None) -> Mesh:
    """``jax.make_mesh``; ``axis_types=True`` asks for Auto axes."""
    if axis_types is True:
        axis_types = default_axis_types(len(axis_shapes))
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=axis_types, devices=devices)


# ---------------------------------------------------------------------------
# shard_map entry point
# ---------------------------------------------------------------------------

shard_map = jax.shard_map


# ---------------------------------------------------------------------------
# compiled-artifact introspection
# ---------------------------------------------------------------------------


def cost_analysis_dict(compiled) -> Dict[str, float]:
    """``compiled.cost_analysis()`` as a flat dict (``{}`` when the
    backend reports no analysis)."""
    return dict(compiled.cost_analysis() or {})
