"""Persistent XLA compilation cache for the entry points.

A cold campaign compiles one fused-fit program per ``fit_plan`` bucket
plus the scoring, sweep, k-center and aggregation programs; a process
that starts again at the same shapes can load them from disk instead.
The entry points (``launch.label``, ``launch.orchestrator``,
``benchmarks.run``, ``chip_smoke.py``) call :func:`enable_compile_cache`
from their ``main()``; nothing turns it on at import time.
"""
from __future__ import annotations

import os

# a fixed path inside the checkout (gitignored): the cache directory is
# part of what JAX keys on, so it must not move between runs
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is configured.  Otherwise the cache goes to
    :data:`DEFAULT_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
