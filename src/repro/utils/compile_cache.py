"""Where JAX keeps its persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR`` wins when it is set: JAX reads it itself,
so nothing is set in code.  Otherwise the cache lives at the fixed,
git-ignored ``<checkout>/.jax_cache`` — never a temp, pid or time-derived
name, because the path is part of every cache key and a directory that
moves never hits.
"""

from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
