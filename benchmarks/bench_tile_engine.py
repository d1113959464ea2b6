"""Tile-engine benchmark: (T, T) merge matrix vs the bitonic engine.

Per tile size T, the same Pallas SPM kernel runs with

* ``engine="matrix"`` — the single-level body: a full (T, T) merge
  matrix + (T, T) one-hot rank application, O(T^2) per tile;
* ``engine="hier"``  — a stable bitonic merge of the two windows:
  ``log2 T`` stages of elementwise compare-exchanges, O(T log T) per
  tile (``leaf`` is passed and unused).

Both engines produce bit-identical merges (asserted by
``tests/test_tile_engine.py``); this file records the speed gap for keys
and key-value merges at T in {128, 512, 1024} plus the derived
``speedup`` rows that BENCH_3.json carries forward.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks._timing import timeit
from benchmarks.bench_merge import _sorted_pair

TILES = (128, 512, 1024)
LEAF = 32


def bench_tile_engine(rows: List[Dict], smoke: bool = False) -> None:
    from repro.kernels.merge_path import merge_kv_pallas, merge_pallas

    n = (1 << 13) if smoke else (1 << 15)  # per side
    iters, warmup = (2, 1) if smoke else (4, 2)
    a, b = _sorted_pair(n, seed=11)
    av = jnp.arange(n, dtype=jnp.float32)
    bv = jnp.arange(n, dtype=jnp.float32) + n
    for tile in TILES:
        us = {}
        for engine in ("matrix", "hier"):
            fn = jax.jit(
                lambda x, y, t=tile, e=engine: merge_pallas(x, y, tile=t, leaf=LEAF, engine=e)
            )
            us[engine] = timeit(
                fn, a, b, iters=iters, warmup=warmup,
                label=f"tile_engine/keys_{engine}/T={tile}",
            )
            rows.append({
                "name": f"tile_engine/keys_{engine}/T={tile}",
                "us_per_call": us[engine],
                "derived": f"{2*n/us[engine]:.1f} Melem/s",
            })
        rows.append({
            "name": f"tile_engine/keys_speedup/T={tile}",
            "us_per_call": 0.0,
            "derived": f"{us['matrix']/us['hier']:.2f}x (hier S={LEAF} vs matrix)",
        })
        us = {}
        for engine in ("matrix", "hier"):
            fn = jax.jit(
                lambda ak, xv, bk, yv, t=tile, e=engine: merge_kv_pallas(
                    ak, xv, bk, yv, tile=t, leaf=LEAF, engine=e
                )
            )
            us[engine] = timeit(
                fn, a, av, b, bv, iters=iters, warmup=warmup,
                label=f"tile_engine/kv_{engine}/T={tile}",
            )
            rows.append({
                "name": f"tile_engine/kv_{engine}/T={tile}",
                "us_per_call": us[engine],
                "derived": f"{2*n/us[engine]:.1f} Melem/s",
            })
        rows.append({
            "name": f"tile_engine/kv_speedup/T={tile}",
            "us_per_call": 0.0,
            "derived": f"{us['matrix']/us['hier']:.2f}x (hier S={LEAF} vs matrix)",
        })
