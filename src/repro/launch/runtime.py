"""Process-level JAX set-up for serving on the chip.

One rule lives here: where JAX keeps its persistent compilation cache. A cold
start on the chip otherwise recompiles every step program of every model it
serves. The cache key includes the cache's path, so the directory is fixed:
never derived from a temp name, a pid or the time.
"""
from __future__ import annotations

import os

import jax

#: The checkout's root (``src/repro/launch`` is three levels below it).
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def init_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here. Otherwise the cache lives in ``.jax_cache``
    at the root of the checkout (git-ignored). Call before the first
    compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
