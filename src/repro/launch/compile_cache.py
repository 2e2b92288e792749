"""JAX's persistent compilation cache, at one fixed place.

The cache key includes the cache directory, so a directory that moves
between runs (a temporary name, a pid, a time) never hits. The entry
points (``chip_smoke.py``, ``launch/train.py``, ``launch/serve.py``,
``benchmarks/run.py``) call :func:`enable_compile_cache` first thing in
``main``; importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

#: ``<checkout>/.jax_cache`` (listed in .gitignore), derived from this
#: file's own location: src/repro/launch/compile_cache.py
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets nothing. Otherwise the cache goes to :data:`CACHE_DIR`."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
