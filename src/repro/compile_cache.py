"""Persistent XLA compile cache for the entry points that drive a chip.

``chip_smoke.py`` and ``benchmarks/run.py`` call
:func:`enable_compile_cache` before their first jit; library modules
never call it. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX keeps its
cache there and this sets no other directory. Otherwise the cache lives
at a fixed ``.jax_cache/`` in the checkout: the path is part of the
cache's key, so a temporary or per-process path would never hit.
"""

from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    if not jax.config.jax_compilation_cache_dir:  # JAX reads the env var
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    # the arena's programs compile in milliseconds, far under JAX's 1 s
    # default floor, and would otherwise never be written
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax.config.jax_compilation_cache_dir
