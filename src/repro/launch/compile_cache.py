"""JAX's persistent compilation cache for this repo's entry points.

Call :func:`enable_compile_cache` at the top of a ``main`` before the
first compile.  ``JAX_COMPILATION_CACHE_DIR``, where set, names the
directory and JAX reads it itself; otherwise the cache lives at the
fixed ``<checkout>/.jax_cache``.  The path must not vary between runs
(no temp name, process id or time): a directory that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache", "DEFAULT_CACHE_DIR"]

#: ``<checkout>/.jax_cache`` (this file is ``src/repro/launch/...``)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

#: low enough that a 1-2 s Pallas kernel compile is written to the cache
#: (JAX's own default, 1 s, skips the smaller ones)
MIN_COMPILE_TIME_S = 0.1


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      MIN_COMPILE_TIME_S)
    return path
