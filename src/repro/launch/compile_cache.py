"""JAX's persistent compilation cache for the repository's entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing. Otherwise the cache lives at one fixed, gitignored
directory inside the checkout (``.jax_cache/``): the path is part of the
cache's key, so it is built from no temp name, pid or time. Library code
and tests never call this; entry points call it before their first
compile.
"""
from __future__ import annotations

import os

CACHE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..", ".jax_cache")
)


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
