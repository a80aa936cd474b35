"""Where JAX keeps its persistent compilation cache.

A compiled program is found again only under the same cache path, so
the path must not move between runs.  ``JAX_COMPILATION_CACHE_DIR``,
when set, wins: JAX reads it itself and nothing here overrides it.
Otherwise the cache goes to ``<checkout>/.jax_cache`` (git-ignored).
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.
    Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
