"""Where JAX keeps its persistent compilation cache.

A cold step of a 33-layer model compiles for minutes; the cache makes the
next process that runs the same program skip that.  The cache key
includes the directory, so the directory must not move between runs.

``JAX_COMPILATION_CACHE_DIR``, when set, is where the cache lives (JAX
reads the variable itself, and nothing is set in code).  Otherwise the
cache goes to ``.jax_cache/`` at the root of the checkout: a fixed path,
listed in ``.gitignore``.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)


def use_compile_cache() -> str:
    """Turn on the persistent compile cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
