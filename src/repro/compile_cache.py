"""JAX persistent compilation cache for the repo's entry points.

Entry points (``chip_smoke.py``, ``benchmarks/fleet.py``) call
:func:`enable_compile_cache` before their first compile; importing the
library never does. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
keeps its cache there and this sets no other directory. Otherwise the cache
lives at one fixed path inside the checkout, ``<repo>/.jax_cache``: the path
is part of the cache key, so a per-run name would never hit.
"""
from __future__ import annotations

import os

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), ".jax_cache"
)


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory. Every JRBA
    program compiles in well under JAX's default one-second floor for
    caching an entry, so the floor drops to zero (without that the cache
    stays empty)."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
