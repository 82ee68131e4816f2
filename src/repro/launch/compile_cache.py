"""JAX's persistent compilation cache at one fixed place.

Every process on a chip otherwise compiles its whole program set from
cold.  ``enable_compile_cache`` is called by ``chip_smoke.py`` and the
bench entry points before their first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# the path is part of the cache's key, so it is fixed: never derived from
# a temp name, a process id or the time
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no other directory is set here; otherwise the cache is
    ``<checkout>/.jax_cache`` (listed in ``.gitignore``).  Every program
    is cached, however fast it compiled: the planner's programs each
    compile in about a second, below JAX's default floor."""
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
