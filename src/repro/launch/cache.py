"""Where JAX keeps its persistent compilation cache.

A compiled step of a published-width model takes tens of seconds to
build; the persistent cache lets the next process on the same chip load
it instead. The cache key includes its directory, so the directory must
not move between runs.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the
    cache there and nothing is changed; otherwise the cache goes to the
    fixed ``.jax_cache/`` directory of this checkout.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
