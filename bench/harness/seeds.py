"""Keys and generators from ``--seed`` (any whole number, beyond 32 bits
too: ``jax.random.key`` alone would drop the high bits)."""

from __future__ import annotations

import numpy as np


def _words(seed: int):
    s = int(seed) % (1 << 64)
    return s & 0xFFFFFFFF, s >> 32


def jax_key(seed: int, *salt: int):
    import jax

    lo, hi = _words(seed)
    key = jax.random.fold_in(jax.random.key(lo), hi)
    for s in salt:
        key = jax.random.fold_in(key, s)
    return key


def rng(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng([*_words(seed), *salt])
