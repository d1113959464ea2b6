"""(tile, leaf) selection for the merge-path kernels.

The output tile ``T`` sets the partition's grain, the VMEM working set
and the width of the bitonic network that merges a tile.  The leaf width
``S`` is still resolved and passed, for the callers and the kernel
contract; no engine uses it.  ``kernels.ops`` resolves unspecified
``tile=None`` / ``leaf=None`` arguments through :func:`pick`, which
consults a small micro-bench table:

* ``DEFAULT_TABLE`` ships with the repo — measured with
  :func:`build_table` in interpret mode on the dev container (regenerate
  with ``python -m repro.kernels.tune``; run on a TPU it measures the
  compiled kernels — commit that result).
* :func:`autotune` re-measures one ``(dtype, size)`` cell over a
  candidate grid and updates the in-process table, for callers whose
  workload is hot enough to warrant a startup sweep.

Keys are ``(dtype kind, log2-size bucket)``; lookups fall back to the
nearest measured bucket, then to ``(DEFAULT_TILE, DEFAULT_LEAF)``, so
:func:`pick` never fails.  Tiles are powers of two (the flat sort rounds
require ``tile | 2 * width``).
"""

from __future__ import annotations

import sys
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.telemetry import wall_seconds

from .merge_path import DEFAULT_LEAF, DEFAULT_TILE, _interp, merge_pallas

TILE_CANDIDATES = (128, 256, 512, 1024)
LEAF_CANDIDATES = (8, 16, 32, 64)

# (dtype kind, log2(total elements) bucket) -> (tile, leaf).
# Measured by build_table() in interpret mode on the CPU-only dev
# container (see module docstring); sparse on purpose — pick() snaps to
# the nearest bucket.
DEFAULT_TABLE: Dict[Tuple[str, int], Tuple[int, int]] = {
    ("f", 12): (512, 16),
    ("f", 15): (512, 8),
    ("f", 18): (512, 8),
    ("i", 12): (256, 8),
    ("i", 15): (1024, 8),
    ("i", 18): (1024, 8),
}

_TABLE: Dict[Tuple[str, int], Tuple[int, int]] = dict(DEFAULT_TABLE)


def _kind(dtype) -> str:
    """Collapse a dtype to the table's kind axis: 'i' (ints) or 'f'
    (floats — incl. bfloat16, whose numpy kind is 'V')."""
    k = jnp.dtype(dtype).kind
    return "i" if k in ("i", "u") else "f"


def _bucket(n: int) -> int:
    return max(8, min(22, int(round(np.log2(max(2, n))))))


def pick(n: int, dtype) -> Tuple[int, int]:
    """Best known ``(tile, leaf)`` for merging/sorting ``n`` total elements.

    Exact-bucket hit first, then the nearest measured bucket of the same
    dtype kind, then the module defaults.  Never larger than the problem:
    the tile is capped at the next power of two >= n so tiny inputs do
    not get a 1024-wide tile.
    """
    kind, b = _kind(dtype), _bucket(n)
    entry = _TABLE.get((kind, b))
    if entry is None:
        same_kind = [(abs(kb - b), kb) for (kk, kb) in _TABLE if kk == kind]
        if same_kind:
            entry = _TABLE[(kind, min(same_kind)[1])]
        else:
            entry = (DEFAULT_TILE, DEFAULT_LEAF)
    tile, leaf = entry
    cap = 1 << max(0, (max(1, n) - 1).bit_length())
    tile = min(tile, max(cap, min(TILE_CANDIDATES)))
    return tile, min(leaf, tile)


def _time_us(fn, *args, iters: int = 3, warmup: int = 1) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = wall_seconds()
        jax.block_until_ready(fn(*args))
        ts.append((wall_seconds() - t0) * 1e6)
    return float(np.median(ts))


def _probe_pair(n: int, dtype):
    rng = np.random.default_rng(n)
    half = max(1, n // 2)
    if _kind(dtype) == "i":
        a = np.sort(rng.integers(-(2**30), 2**30, half)).astype(np.int32)
        b = np.sort(rng.integers(-(2**30), 2**30, half)).astype(np.int32)
    else:
        a = np.sort(rng.standard_normal(half)).astype(np.float32)
        b = np.sort(rng.standard_normal(half)).astype(np.float32)
    return jnp.asarray(a, dtype=dtype), jnp.asarray(b, dtype=dtype)


def autotune(
    n: int,
    dtype,
    *,
    tiles: Tuple[int, ...] = TILE_CANDIDATES,
    leaves: Tuple[int, ...] = LEAF_CANDIDATES,
    iters: int = 3,
    interpret: Optional[bool] = None,
    update_table: bool = True,
) -> Tuple[int, int]:
    """Measure the candidate ``(tile, leaf)`` grid on an ``n``-element
    merge and return the fastest pair.

    The micro-bench is the keys-only 1-D merge (the kv and batched
    variants share the same tile body, so the optimum transfers).  With
    ``update_table`` (default) the result is written into the in-process
    table, so subsequent :func:`pick` calls in the same bucket use it.
    ``interpret=None`` follows the backend like every kernel wrapper, so
    regenerating the table on a TPU (``python -m repro.kernels.tune``)
    measures compiled kernels, not the interpreter.
    """
    interpret = _interp(interpret)
    a, b = _probe_pair(n, dtype)
    best, best_us = None, float("inf")
    # the autotuner's job is exactly to launch candidates one by one
    for tile in tiles:  # lint: ok(L004)
        if tile > max(1024, n):  # a tile wider than the problem is noise
            continue
        for leaf in leaves:  # lint: ok(L004)
            if leaf > tile:
                continue
            fn = jax.jit(
                lambda x, y, t=tile, s=leaf: merge_pallas(
                    x, y, tile=t, leaf=s, engine="hier", interpret=interpret
                )
            )
            us = _time_us(fn, a, b, iters=iters)
            if us < best_us:
                best, best_us = (tile, leaf), us
    assert best is not None
    if update_table:
        _TABLE[(_kind(dtype), _bucket(n))] = best
    return best


def build_table(
    sizes: Tuple[int, ...] = (1 << 12, 1 << 15, 1 << 18),
    dtypes=(jnp.float32, jnp.int32),
    **kw,
) -> Dict[Tuple[str, int], Tuple[int, int]]:
    """Run :func:`autotune` over a (sizes x dtypes) grid; returns the table
    fragment (also installed in-process).  This is what produced
    ``DEFAULT_TABLE``."""
    out = {}
    for dtype in dtypes:
        for n in sizes:
            out[(_kind(dtype), _bucket(n))] = autotune(n, dtype, **kw)
    return out


def main() -> None:
    table = build_table()
    print("DEFAULT_TABLE: Dict[Tuple[str, int], Tuple[int, int]] = {")
    for k in sorted(table):
        print(f"    {k!r}: {table[k]!r},")
    print("}")


if __name__ == "__main__":
    sys.exit(main())
