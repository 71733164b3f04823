"""JAX's persistent compilation cache for this repository's entry points.

The cache key includes its directory, so the directory must not move
between runs: a name made from a temporary path, a process id or a
time would never hit.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: the fixed in-checkout cache directory (listed in ``.gitignore``)
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here. Otherwise the cache goes to
    :data:`CHECKOUT_CACHE_DIR`. Call it before the first compilation:
    JAX decides once per process whether the cache is in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
