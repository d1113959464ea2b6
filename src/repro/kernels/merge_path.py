"""Pallas TPU kernel for Merge Path — the paper's SPM, VMEM-tiled.

Mapping of the paper's cache-efficient Segmented Parallel Merge (Alg. 3)
onto the TPU memory hierarchy:

* the **cache** is VMEM; a segment is one grid step's working set;
* the per-segment window guarantee (Lemma 16: a T-output segment needs at
  most T consecutive inputs from each array) bounds every grid step to
  ``2*T`` input elements + ``T`` outputs staged through VMEM;
* the **partition phase** (Alg. 2's cross-diagonal binary searches) runs
  once, vectorized, *outside* the kernel and its results ride in as
  scalar-prefetch operands (SMEM) that the kernel body uses to fetch its
  input windows — the TPU analogue of the paper's "p cores independently
  compute their start points".

**Layout and staging.**  Every operand is a lane-dense ``(rows, W)``
array (``W = 128`` lanes when it divides the tile, else ``W = T``), padded
past its end.  A grid step's window ``[start, start + T)`` lies inside the
``T/W + 8`` rows that begin at the 8-row-aligned row at or below
``start // W``: the BlockSpec pipeline fetches those as whole ``(8, W)``
tiles (aligned copies, double-buffered across grid steps — a copy from an
unaligned dynamic row compiles but hangs on a v5e), and the kernel selects
the window's ``T/W + 1`` rows and shifts it into place with two lane
rotations (``pltpu.roll``) and a select.  Output blocks are whole
``(T/W, W)`` slabs of a ``(..., steps, T/W, W)`` array, so every block
shape equals the trailing dims of its array.

Inside a tile, two engines are available (``engine=`` on every wrapper):

* ``"hier"`` (default) — a **stable bitonic merge network** on whole
  vregs (:func:`_hier_merge_window`).  Every element carries (key, source
  index[, value]); the (key, index) order is exactly the stable
  A-priority merge.  One compare-exchange of A against reversed B keeps
  the T smallest of the 2T window elements as a bitonic sequence, and
  ``log2(T)`` half-cleaner stages — lane rotations (``pltpu.roll``) for
  strides under ``W``, sublane rotations above, a select on
  ``p & stride`` each — sort it.  Work is O(T log T) elementwise vector
  ops per tile, with no cross-lane reduction.  A tile that is not a power
  of two is padded inside the network to the next one.  (The name stays
  for the callers and the guard's ``pallas-hier`` edge; ``leaf=`` is
  still accepted by every wrapper and does not shape the kernel.)
* ``"matrix"`` — the single-level engine: materialize the full ``(T, T)``
  Merge Matrix and apply ranks via a ``(T, T)`` one-hot select.  Kept as
  the bit-exactness oracle for the bitonic engine and as the guard's
  fallback edge.

Both engines exclude pads by *index*, never by comparing against the
sentinel, so the ragged / key-value length-masking guarantees hold for
payload keys equal to the sentinel.  Every data movement inside a tile is
a select of the elements' bit patterns, so results are bit-identical to
the pure-JAX core routes.

Output tiles are *exactly* T elements each (Corollary 7 — equal output
partitions is the whole point of the path partition).

Compiled (non-interpret) kernels take 32-bit keys and values.
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.batched import (
    _as_lens,
    _mask_rows,
    diagonal_intersections_batched,
    diagonal_intersections_ragged,
)
from repro.core.merge_path import diagonal_intersections, max_sentinel

DEFAULT_TILE = 512
DEFAULT_LEAF = 32
DEFAULT_ENGINE = "hier"
LANES = 128

_FALSEY = ("0", "false", "no", "off")


def default_interpret() -> bool:
    """Whether a Pallas call made now runs in interpret mode.

    Compiled on a TPU backend, interpreted everywhere else — decided from
    ``jax.default_backend()`` when the call is made.  A non-empty
    ``REPRO_PALLAS_INTERPRET`` overrides it for tests: ``0``/``false``/
    ``no``/``off`` force compiled kernels, anything else forces the
    interpreter.  (An AOT compile for a described, unattached TPU still
    sees the CPU backend; such callers pass ``interpret=False``.)
    """
    env = os.environ.get("REPRO_PALLAS_INTERPRET", "").strip().lower()
    if env:
        return env not in _FALSEY
    return jax.default_backend() != "tpu"


def _interp(interpret: Optional[bool]) -> bool:
    return default_interpret() if interpret is None else interpret


def _lanes(tile: int) -> int:
    """Lane width ``W`` of the ``(rows, W)`` staging layout for ``tile``."""
    return LANES if tile % LANES == 0 else tile


def _fetch_blocks(tile: int) -> int:
    """Aligned ``(8, W)`` blocks per operand per grid step: enough rows for
    a window's ``T/W + 1`` rows starting up to 7 rows into the first."""
    return pl.cdiv(tile // _lanes(tile) + 8, 8)


def sort_tail(tile: int) -> int:
    """Length of the sentinel tail a flat sort buffer carries: a multiple
    of ``tile`` that holds the aligned blocks a window fetch at the last
    data element reaches into."""
    return pl.cdiv(8 * _fetch_blocks(tile) * _lanes(tile), tile) * tile


def _iota(shape, dim: int) -> jax.Array:
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _bits(x: jax.Array) -> jax.Array:
    """Same-width integer view of ``x`` (sums over it are exact)."""
    if jnp.issubdtype(x.dtype, jnp.floating):
        return jax.lax.bitcast_convert_type(x, jnp.dtype(f"int{8 * x.dtype.itemsize}"))
    return x


def _unbits(x: jax.Array, dtype) -> jax.Array:
    dtype = jnp.dtype(dtype)
    return jax.lax.bitcast_convert_type(x, dtype) if x.dtype != dtype else x


# ---------------------------------------------------------------------------
# Single-level ("matrix") tile engine: the full (T, T) merge matrix
# ---------------------------------------------------------------------------


def _columns(x2: jax.Array):
    """An ``(R, W)`` window's ``R`` lane rows as ``(W, 1)`` columns."""
    xt = x2.T
    return [xt[:, c : c + 1] for c in range(x2.shape[0])]


def _scatter_ranks(ranks, cols, t: int) -> Tuple[jax.Array, jax.Array]:
    """Apply a rank permutation: ``out[k] = x[i]`` where ``rank[i] == k``.

    ``ranks`` / ``cols`` are ``x``'s ranks and values as ``(W, 1)``
    column chunks.  A ``(T, T)`` one-hot select + reduce, slab by slab,
    over the elements' integer bit patterns (exact for every dtype);
    returns the ``(1, T)`` output row and per-slot coverage.  Ranks >= T
    fall outside this tile (consumed by a later one) and contribute
    nothing.
    """
    k = _iota((1, t), 1)
    val = cnt = None
    for rank, col in zip(ranks, cols):
        hit = rank == k  # (W, T) slab of the one-hot
        bits = _bits(col)
        v = jnp.sum(jnp.where(hit, bits, jnp.zeros((), bits.dtype)), axis=0, keepdims=True, dtype=bits.dtype)
        c = jnp.sum(hit.astype(jnp.int32), axis=0, keepdims=True)
        val, cnt = (v, c) if val is None else (val + v, cnt + c)
    return val, cnt


def _matrix_merge(wa2, wb2, *, wav2, wbv2, valid_a, valid_b, fill):
    """Single-level merge of one tile's ``(R, W)`` windows → ``(1, T)`` rows.

    The ``(T, T)`` merge matrix ``M[i, j] = A[i] > B[j]`` is built in
    ``R`` slabs of ``W`` rows (A as columns against the B row); row sums
    give A's ranks, column sums of the complement (ties go to A) B's.
    Unmasked, sentinel pads rank like real elements — exact for keys-only
    tiles.  With valid lengths, pads are excluded from the counts by index
    and rank ``T`` (dropped), so payload keys equal to the sentinel rank
    exactly.
    """
    r, w = wa2.shape
    t = r * w
    masked = valid_a is not None
    wb_row = wb2.reshape(1, t)
    j = _iota((1, t), 1)
    ra, rb_cross = [], jnp.zeros((1, t), jnp.int32)
    for c, a_col in enumerate(_columns(wa2)):
        m = a_col > wb_row  # (W, T) slab of the merge matrix
        i = c * w + _iota((w, 1), 0)
        if masked:
            cross = jnp.sum((m & (j < valid_b)).astype(jnp.int32), axis=1, keepdims=True)
            ra.append(jnp.where(i < valid_a, i + cross, t))
            rb_cross = rb_cross + jnp.sum(((~m) & (i < valid_a)).astype(jnp.int32), axis=0, keepdims=True)
        else:
            ra.append(i + jnp.sum(m.astype(jnp.int32), axis=1, keepdims=True))
            rb_cross = rb_cross + jnp.sum((~m).astype(jnp.int32), axis=0, keepdims=True)
    rb = j + rb_cross
    if masked:
        rb = jnp.where(j < valid_b, rb, t)
    rb = _columns(rb.reshape(r, w))
    ka, ca = _scatter_ranks(ra, _columns(wa2), t)
    kb, cb = _scatter_ranks(rb, _columns(wb2), t)
    keys = _unbits(ka + kb, wa2.dtype)
    if fill:
        keys = jnp.where(ca + cb > 0, keys, max_sentinel(wa2.dtype))
    vals = None
    if wav2 is not None:
        va, _ = _scatter_ranks(ra, _columns(wav2), t)
        vb, _ = _scatter_ranks(rb, _columns(wbv2), t)
        vals = _unbits(va + vb, wav2.dtype)
    return keys, vals


# ---------------------------------------------------------------------------
# Bitonic tile engine ("hier")
# ---------------------------------------------------------------------------
#
# Level 1 (outside the kernel): Alg. 2 over the *global* cross diagonals
# produces per-tile (a_start, b_start) scalar-prefetch tables, so a grid
# step's T outputs are the T smallest of its two T-element windows.  Inside
# the tile those T are picked and ordered by a bitonic merge on whole
# vregs: every step is an elementwise compare-exchange between lanes or
# sublanes a power of two apart (``pltpu.roll`` + select), with no
# cross-lane reductions, gathers of ranks or quadratic work.


def _pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


def _flat_iota(shape) -> jax.Array:
    """Row-major flat position of every slot of a 2-D ``shape``."""
    return _iota(shape, 0) * shape[1] + _iota(shape, 1)


def _pad_to(x: jax.Array, shape, fill) -> jax.Array:
    """Grow an ``(R, W)`` window to ``shape`` (more lanes of one row, or
    more rows), ``fill`` in the new slots."""
    if x.shape == tuple(shape):
        return x
    axis = 1 if x.shape[0] == 1 else 0
    extra = list(x.shape)
    extra[axis] = shape[axis] - x.shape[axis]
    return jnp.concatenate([x, jnp.full(extra, fill, x.dtype)], axis=axis)


def _reverse(x: jax.Array) -> jax.Array:
    """Reverse the row-major flat order of a 2-D array: rows by static
    slices, lanes by an in-vreg lane gather."""
    r, w = x.shape
    if r > 1:
        x = jnp.concatenate([x[i : i + 1] for i in range(r - 1, -1, -1)], axis=0)
    else:  # the TPU lane gather takes no single-row operand
        x = jnp.broadcast_to(x, (8, w))
    return jnp.take_along_axis(x, (w - 1) - _iota(x.shape, 1), axis=1)[:r]


def _precedes(ka, ia, kb, ib) -> jax.Array:
    """Element-wise (key, source index) order: keys compare in their own
    dtype, ties go to the lower source index (A before B, earlier first)."""
    return (ka < kb) | ((ka == kb) & (ia < ib))


def _half_clean(elems, s: int):
    """One ascending bitonic half-cleaner stage of stride ``s`` (flat
    positions) on the ``(key, index[, value])`` arrays: slot ``p`` keeps
    the lesser of itself and ``p ^ s`` when ``p & s == 0``, else the
    greater.  Partners come from two rotations along lanes (``s < W``) or
    sublanes (``s >= W``)."""
    r, w = elems[0].shape
    axis, step = (1, s) if s < w else (0, s // w)
    n = elems[0].shape[axis]
    low = (_iota((r, w), axis) & step) == 0
    mate = [jnp.where(low, pltpu.roll(x, n - step, axis), pltpu.roll(x, step, axis)) for x in elems]
    keep = _precedes(elems[0], elems[1], mate[0], mate[1]) == low
    return [jnp.where(keep, x, y) for x, y in zip(elems, mate)]


def _hier_merge_window(wa2, wb2, *, wav2, wbv2, valid_a, valid_b, fill):
    """Stable bitonic merge of one tile's ``(R, W)`` windows → ``(R, W)``
    output blocks (keys, values | None).

    1. **Elements**: each slot carries (key, source index[, value]); the
       index is ``i`` for A slot ``i`` and ``P + j`` for B slot ``j``,
       where ``P`` is the tile rounded up to a power of two (the network's
       width; slots ``T..P`` are pads).  Slots at or past ``valid_a`` /
       ``valid_b`` — and the round-up slots — are pads: sentinel key,
       index ``2P`` higher, so they order after every real element, real
       keys equal to the sentinel included.  The (key, index) order is
       exactly the stable A-priority merge.
    2. **Bitonic split**: A against reversed B, keeping the lesser of
       each pair, leaves a bitonic sequence holding the ``P`` smallest.
    3. **Sort**: ``log2(P)`` ascending half-cleaner stages; the first
       ``T`` slots are the tile's outputs.

    Unmasked (``valid_a`` None, keys-only), every window slot is real:
    sentinel pads rank like keys, which is exact for keys alone.
    ``fill=True`` (ragged callers): slots past the windows' merged valid
    length get sentinel keys / zero values — bit-identical to the matrix
    engine's coverage fill.
    """
    r, w = wa2.shape
    t = r * w
    p = _pow2(t)
    shape = (1, p) if r == 1 else (p // w, w)
    pos = _flat_iota(shape)
    sent = max_sentinel(wa2.dtype)
    va = t if valid_a is None else valid_a
    vb = t if valid_b is None else valid_b

    def side(k2, v2, valid, offset):
        """One window's (key, index[, value]) arrays; B (offset P) reversed."""
        xs = [_pad_to(k2, shape, sent)]
        if v2 is not None:
            xs.append(_pad_to(v2, shape, jnp.zeros((), v2.dtype)))
        slot = pos
        if offset:  # slot p holds B[P - 1 - p]
            xs = [_reverse(x) for x in xs]
            slot = (p - 1) - pos
        real = slot < valid
        return [jnp.where(real, xs[0], sent), offset + jnp.where(real, slot, slot + 2 * p), *xs[1:]]

    a = side(wa2, wav2, va, 0)
    b = side(wb2, wbv2, vb, p)
    first = _precedes(a[0], a[1], b[0], b[1])
    elems = [jnp.where(first, x, y) for x, y in zip(a, b)]
    s = p // 2
    while s:
        elems = _half_clean(elems, s)
        s //= 2
    out_k = elems[0][:r, :w]  # the first T slots, in the window's layout
    out_v = None if wav2 is None else elems[2][:r, :w]
    if valid_a is not None and fill:
        covered = _flat_iota((r, w)) < valid_a + valid_b
        out_k = jnp.where(covered, out_k, sent)
        if out_v is not None:
            out_v = jnp.where(covered, out_v, jnp.zeros((), out_v.dtype))
    return out_k, out_v


def _tile_merge(
    wak: jax.Array,
    wbk: jax.Array,
    *,
    engine: str,
    wav: Optional[jax.Array] = None,
    wbv: Optional[jax.Array] = None,
    valid_a: Optional[jax.Array] = None,
    valid_b: Optional[jax.Array] = None,
    fill: bool = False,
) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Engine dispatch for one tile: merge two ``(R, W)`` windows into
    ``(R, W)`` output blocks (keys, values | None).

    ``engine="hier"`` → :func:`_hier_merge_window`; ``engine="matrix"`` →
    the (T, T) merge-matrix + one-hot path.  Both produce bit-identical
    merged prefixes; ``fill`` additionally makes uncovered
    (past-the-valid-end) slots bit-identical (sentinel keys, zero values)
    for the ragged kernels whose padding is visible.
    """
    shape = wak.shape
    if engine == "hier":
        keys, vals = _hier_merge_window(
            wak, wbk, wav2=wav, wbv2=wbv, valid_a=valid_a, valid_b=valid_b, fill=fill,
        )
    elif engine == "matrix":
        keys, vals = _matrix_merge(
            wak, wbk, wav2=wav, wbv2=wbv, valid_a=valid_a, valid_b=valid_b, fill=fill,
        )
    else:
        raise ValueError(f"unknown tile engine {engine!r} (expected 'hier' or 'matrix')")
    return keys.reshape(shape), None if vals is None else vals.reshape(shape)


# ---------------------------------------------------------------------------
# One kernel body for every merge form
# ---------------------------------------------------------------------------


def _window_rows(blocks, row0, n: int) -> jax.Array:
    """The ``n`` rows starting ``row0`` (< 8) rows into the aligned
    ``(8, W)`` blocks, concatenated (one select per candidate offset)."""
    x = jnp.concatenate(blocks, axis=0)
    out = x[7 : 7 + n]
    for k in range(7):
        out = jnp.where(row0 == k, x[k : k + n], out)
    return out


def _shift_window(x: jax.Array, c) -> jax.Array:
    """``(R + 1, W)`` staged rows → the ``(R, W)`` window starting ``c``
    lanes into the first row (two lane rotations and a select)."""
    r, w = x.shape[0] - 1, x.shape[1]
    shift = (w - c) % w
    lo = pltpu.roll(x[:r], shift, 1)
    hi = pltpu.roll(x[1:], shift, 1)
    return jnp.where(_iota((r, w), 1) < w - c, lo, hi)


def _tile_kernel(*refs, n_prefetch, nd, nblk, geom, tile, engine, kv, fill):
    """One ``T``-output grid step: select the windows, merge, write the block.

    Operand order: ``n_prefetch`` scalar-prefetch tables, ``nblk`` aligned
    ``(8, W)`` input blocks for each input ``(a_keys, b_keys[, a_vals,
    b_vals])``, then the output blocks ``(keys[, vals])``.
    ``geom(ids, *tables)`` returns grid step ``ids``'s ``(row, a_start,
    b_start, valid_a, valid_b, live)``: ``row`` selects the batch row of
    3-D inputs (None for 1-D), the valid lengths are None for keys-only
    unmasked merges, and ``live`` (None = always) is False on the flat
    sort's sentinel-tail steps.
    """
    n_in = 4 if kv else 2
    tables = refs[:n_prefetch]
    blocks = refs[n_prefetch : n_prefetch + n_in * nblk]
    outs = refs[n_prefetch + n_in * nblk :]
    ids = tuple(pl.program_id(i) for i in range(nd))
    _, a0, b0, valid_a, valid_b, live = geom(ids, *tables)
    w = outs[0].shape[-1]

    def merge_step():
        win = []
        for i, start in enumerate((a0, b0) * (n_in // 2)):
            rows = _window_rows([blk[...] for blk in blocks[i * nblk : (i + 1) * nblk]],
                                (start // w) % 8, tile // w + 1)
            win.append(_shift_window(rows, start % w))
        keys, vals = _tile_merge(
            win[0], win[1], engine=engine,
            wav=win[2] if kv else None, wbv=win[3] if kv else None,
            valid_a=valid_a, valid_b=valid_b, fill=fill,
        )
        outs[0][...] = keys
        if kv:
            outs[1][...] = vals

    if live is None:
        merge_step()
        return

    pl.when(live)(merge_step)

    @pl.when(jnp.logical_not(live))
    def _():
        outs[0][...] = jnp.full(outs[0].shape, max_sentinel(outs[0].dtype), outs[0].dtype)
        if kv:
            outs[1][...] = jnp.zeros(outs[1].shape, outs[1].dtype)


def _rows(x: jax.Array, tile: int, fill) -> jax.Array:
    """``(..., n)`` → ``(..., rows, W)``, padded with ``fill`` so that the
    aligned blocks of every window fetch (from any start in ``[0, n]``)
    are in bounds."""
    w = _lanes(tile)
    n = x.shape[-1]
    rows = 8 * (n // w // 8 + _fetch_blocks(tile))
    pad = jnp.full(x.shape[:-1] + (rows * w - n,), fill, x.dtype)
    return jnp.concatenate([x, pad], axis=-1).reshape(x.shape[:-1] + (rows, w))


def _launch(geom, grid, tables, ins, *, tile, engine, fill, interpret):
    """``pallas_call`` of :func:`_tile_kernel` over ``grid``; returns the
    output(s) as ``grid + (T,)`` arrays (one T-slab per grid step)."""
    w = _lanes(tile)
    r = tile // w
    nd = len(grid)
    nblk = _fetch_blocks(tile)
    kv = len(ins) == 4

    def in_spec(side, j, batched):
        def index_map(*args):
            row, a0, b0 = geom(args[:nd], *args[nd:])[:3]
            blk = ((a0 if side == 0 else b0) // w) // 8 + j
            return (row, blk, 0) if batched else (blk, 0)

        return pl.BlockSpec(((None,) if batched else ()) + (8, w), index_map)

    out_spec = pl.BlockSpec((None,) * nd + (r, w), lambda *idx: (*idx[:nd], 0, 0))
    dtypes = [x.dtype for x in ins[0::2]]  # (keys[, vals])
    out = pl.pallas_call(
        functools.partial(
            _tile_kernel, n_prefetch=len(tables), nd=nd, nblk=nblk, geom=geom, tile=tile,
            engine=engine, kv=kv, fill=fill,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables),
            grid=grid,
            in_specs=[in_spec(i % 2, j, x.ndim == 3) for i, x in enumerate(ins) for j in range(nblk)],
            out_specs=[out_spec] * len(dtypes),
        ),
        out_shape=[jax.ShapeDtypeStruct(tuple(grid) + (r, w), d) for d in dtypes],
        interpret=_interp(interpret),
    )(*tables, *[x for x in ins for _ in range(nblk)])
    return [o.reshape(tuple(grid) + (tile,)) for o in out]


def _check_kv(ak, av, bk, bv):
    if av.shape != ak.shape or bv.shape != bk.shape:
        raise ValueError(
            f"value shapes must match key shapes: keys {ak.shape}/{bk.shape}, "
            f"values {av.shape}/{bv.shape}"
        )


# ---------------------------------------------------------------------------
# 1-D merges
# ---------------------------------------------------------------------------


def _prepare(a, b, tile):
    """Common host-side partition phase (Alg. 2, vectorized)."""
    dtype = jnp.result_type(a, b)
    a = a.astype(dtype)
    b = b.astype(dtype)
    n = a.shape[0] + b.shape[0]
    nt = pl.cdiv(n, tile)
    diags = jnp.minimum(jnp.arange(nt, dtype=jnp.int32) * tile, n)
    a_starts = diagonal_intersections(a, b, diags).astype(jnp.int32)
    b_starts = diags - a_starts
    sent = max_sentinel(dtype)
    return _rows(a, tile, sent), _rows(b, tile, sent), a_starts, b_starts, n, nt


def _geom_1d(na=None, nb=None, tile=None):
    """Per-step geometry of the 1-D merges (valid lengths for kv)."""

    def geom(ids, a_starts, b_starts):
        a0, b0 = a_starts[ids[0]], b_starts[ids[0]]
        if na is None:
            return None, a0, b0, None, None, None
        # Valid lengths: a window pad tied with a real sentinel-valued
        # key must not steal its slot and surface a zero value.
        return None, a0, b0, jnp.clip(na - a0, 0, tile), jnp.clip(nb - b0, 0, tile), None

    return geom


def merge_pallas(
    a: jax.Array,
    b: jax.Array,
    *,
    tile: int = DEFAULT_TILE,
    leaf: int = DEFAULT_LEAF,
    engine: str = DEFAULT_ENGINE,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Merge two sorted 1-D arrays with the Pallas SPM kernel.

    ``leaf`` is accepted by every wrapper here for its callers and the
    kernel contract; the tile engines do not use it.
    """
    ap, bp, a_starts, b_starts, n, nt = _prepare(a, b, tile)
    (out,) = _launch(
        _geom_1d(), (nt,), (a_starts, b_starts), (ap, bp),
        tile=tile, engine=engine, fill=False, interpret=interpret,
    )
    return out.reshape(-1)[:n]


def merge_kv_pallas(
    ak: jax.Array,
    av: jax.Array,
    bk: jax.Array,
    bv: jax.Array,
    *,
    tile: int = DEFAULT_TILE,
    leaf: int = DEFAULT_LEAF,
    engine: str = DEFAULT_ENGINE,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Stable key-value merge with the Pallas SPM kernel."""
    _check_kv(ak, av, bk, bv)
    akp, bkp, a_starts, b_starts, n, nt = _prepare(ak, bk, tile)
    vd = jnp.result_type(av, bv)
    zero = jnp.zeros((), vd)
    ko, vo = _launch(
        _geom_1d(ak.shape[0], bk.shape[0], tile), (nt,), (a_starts, b_starts),
        (akp, bkp, _rows(av.astype(vd), tile, zero), _rows(bv.astype(vd), tile, zero)),
        tile=tile, engine=engine, fill=False, interpret=interpret,
    )
    return ko.reshape(-1)[:n], vo.reshape(-1)[:n]


# ---------------------------------------------------------------------------
# Batched merges: 2-D (batch, tile) grid
# ---------------------------------------------------------------------------
#
# The batched form runs B independent merges in ONE kernel launch.  The
# partition phase is a single fused Algorithm 2 pass over every (row,
# diagonal) pair (``diagonal_intersections_batched``), and its (B, nt)
# start tables ride into the kernel as scalar-prefetch operands.  Each
# (batch, tile) grid step reads its two starts from SMEM, stages its input
# windows from the row it owns, and writes exactly one T-output block —
# Corollary 7's equal output partition, now per row.
#
# Versus vmapping the 1-D kernel, this keeps ONE grid whose trailing
# (tile) axis is innermost, so consecutive grid steps walk consecutive
# output blocks of the same row (sequential HBM writes), and the
# partition bisection is shared across the whole batch instead of being
# re-run per lane.


def _check_batched(a, b):
    if a.ndim != 2 or b.ndim != 2 or a.shape[0] != b.shape[0]:
        raise ValueError(f"expected (B, na) and (B, nb) with equal B, got {a.shape} and {b.shape}")


def _prepare_batched(a, b, tile):
    """Host-side partition phase for the batched kernel: one fused Alg. 2
    pass over all (row, diagonal) pairs."""
    _check_batched(a, b)
    dtype = jnp.result_type(a, b)
    a = a.astype(dtype)
    b = b.astype(dtype)
    bsz = a.shape[0]
    n = a.shape[1] + b.shape[1]
    nt = pl.cdiv(n, tile)
    diags = jnp.minimum(jnp.arange(nt, dtype=jnp.int32) * tile, n)
    a_starts = diagonal_intersections_batched(a, b, diags).astype(jnp.int32)  # (B, nt)
    b_starts = diags[None, :] - a_starts
    sent = max_sentinel(dtype)
    return _rows(a, tile, sent), _rows(b, tile, sent), a_starts, b_starts, bsz, n, nt


def _geom_batched(na=None, nb=None, tile=None, ragged=False):
    """Per-step geometry of the batched merges: static row lengths
    (``na``/``nb``, kv), per-row length tables (``ragged``), or none."""

    def geom(ids, a_starts, b_starts, *lens):
        bi, ti = ids
        a0, b0 = a_starts[bi, ti], b_starts[bi, ti]
        if ragged:
            la, lb = lens[0][bi], lens[1][bi]
        elif na is not None:
            la, lb = na, nb
        else:
            return bi, a0, b0, None, None, None
        return bi, a0, b0, jnp.clip(la - a0, 0, tile), jnp.clip(lb - b0, 0, tile), None

    return geom


def merge_batched_pallas(
    a: jax.Array,
    b: jax.Array,
    *,
    tile: int = DEFAULT_TILE,
    leaf: int = DEFAULT_LEAF,
    engine: str = DEFAULT_ENGINE,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Merge ``B`` pairs of sorted rows in one 2-D-grid SPM kernel launch.

    ``a`` is ``(B, na)``, ``b`` is ``(B, nb)``, both row-sorted; returns
    ``(B, na + nb)`` where row ``r`` is the stable A-priority merge of
    ``a[r]`` and ``b[r]`` — bit-identical to ``vmap(merge)``.
    """
    ap, bp, a_starts, b_starts, bsz, n, nt = _prepare_batched(a, b, tile)
    (out,) = _launch(
        _geom_batched(), (bsz, nt), (a_starts, b_starts), (ap, bp),
        tile=tile, engine=engine, fill=False, interpret=interpret,
    )
    return out.reshape(bsz, -1)[:, :n]


def merge_kv_batched_pallas(
    ak: jax.Array,
    av: jax.Array,
    bk: jax.Array,
    bv: jax.Array,
    *,
    tile: int = DEFAULT_TILE,
    leaf: int = DEFAULT_LEAF,
    engine: str = DEFAULT_ENGINE,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Batched stable key-value merge on the 2-D-grid SPM kernel.

    Keys ``(B, na)``/``(B, nb)`` row-sorted; values carried along the same
    permutation.  Row ``r`` equals ``merge_kv`` of row ``r``.
    """
    _check_kv(ak, av, bk, bv)
    akp, bkp, a_starts, b_starts, bsz, n, nt = _prepare_batched(ak, bk, tile)
    vd = jnp.result_type(av, bv)
    zero = jnp.zeros((), vd)
    ko, vo = _launch(
        _geom_batched(ak.shape[1], bk.shape[1], tile), (bsz, nt), (a_starts, b_starts),
        (akp, bkp, _rows(av.astype(vd), tile, zero), _rows(bv.astype(vd), tile, zero)),
        tile=tile, engine=engine, fill=False, interpret=interpret,
    )
    return ko.reshape(bsz, -1)[:, :n], vo.reshape(bsz, -1)[:, :n]


# ---------------------------------------------------------------------------
# Ragged batched merges: per-row length tables via scalar prefetch
# ---------------------------------------------------------------------------
#
# The ragged form is the batched kernel with one addition: alongside the
# (B, nt) start tables, the per-row valid lengths ride in as scalar-
# prefetch operands (SMEM).  Each (batch, tile) grid step derives its
# windows' valid prefixes from the length tables, and both engines
# exclude slots past them by index, so padding never shadows a payload and output slots past a row's merged
# length are filled with the sentinel.  The partition phase clamps every
# row's diagonals to that row's total valid length, so short rows simply
# run out of work early (their trailing tiles write pure sentinel
# blocks).


def _prepare_batched_ragged(a, b, a_lens, b_lens, tile):
    """Partition phase for the ragged kernel: per-row clamped diagonals.

    Rows are sentinel-masked beyond their lengths (so windows stay
    sorted whatever the caller left in the padding), and each row's
    diagonals are clamped to its own total valid length — the bisection
    of ``diagonal_intersections_ragged`` then never probes padding.
    """
    _check_batched(a, b)
    dtype = jnp.result_type(a, b)
    bsz, na = a.shape
    nb = b.shape[1]
    a_lens = _as_lens(a_lens, bsz, na)
    b_lens = _as_lens(b_lens, bsz, nb)
    sent = max_sentinel(dtype)
    am = _mask_rows(a.astype(dtype), a_lens, sent)
    bm = _mask_rows(b.astype(dtype), b_lens, sent)
    n = na + nb
    nt = pl.cdiv(n, tile)
    row_total = (a_lens + b_lens)[:, None]  # (B, 1)
    diags = jnp.minimum(jnp.arange(nt, dtype=jnp.int32)[None, :] * tile, row_total)
    a_starts = diagonal_intersections_ragged(am, bm, a_lens, b_lens, diags).astype(jnp.int32)
    b_starts = diags - a_starts
    tables = (a_starts, b_starts, a_lens, b_lens)
    return _rows(am, tile, sent), _rows(bm, tile, sent), tables, bsz, n, nt


def merge_batched_ragged_pallas(
    a: jax.Array,
    b: jax.Array,
    a_lens,
    b_lens,
    *,
    tile: int = DEFAULT_TILE,
    leaf: int = DEFAULT_LEAF,
    engine: str = DEFAULT_ENGINE,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Ragged batched merge on the 2-D ``(batch, tile)`` grid SPM kernel.

    Row ``r`` of the ``(B, na + nb)`` result starts with the stable
    A-priority merge of ``a[r, :a_lens[r]]`` and ``b[r, :b_lens[r]]``,
    followed by sentinel padding — bit-identical to
    :func:`repro.core.batched.merge_batched_ragged`.  The per-row length
    tables ride in as scalar-prefetch operands next to the start tables.
    """
    ap, bp, tables, bsz, n, nt = _prepare_batched_ragged(a, b, a_lens, b_lens, tile)
    (out,) = _launch(
        _geom_batched(tile=tile, ragged=True), (bsz, nt), tables, (ap, bp),
        tile=tile, engine=engine, fill=True, interpret=interpret,
    )
    return out.reshape(bsz, -1)[:, :n]


def merge_kv_batched_ragged_pallas(
    ak: jax.Array,
    av: jax.Array,
    bk: jax.Array,
    bv: jax.Array,
    a_lens,
    b_lens,
    *,
    tile: int = DEFAULT_TILE,
    leaf: int = DEFAULT_LEAF,
    engine: str = DEFAULT_ENGINE,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Ragged batched key-value merge on the 2-D-grid SPM kernel.

    Bit-identical to :func:`repro.core.batched.merge_kv_batched_ragged`:
    merged valid pairs first, then sentinel keys with zero values.
    """
    _check_kv(ak, av, bk, bv)
    akp, bkp, tables, bsz, n, nt = _prepare_batched_ragged(ak, bk, a_lens, b_lens, tile)
    vd = jnp.result_type(av, bv)
    zero = jnp.zeros((), vd)
    ko, vo = _launch(
        _geom_batched(tile=tile, ragged=True), (bsz, nt), tables,
        (akp, bkp, _rows(av.astype(vd), tile, zero), _rows(bv.astype(vd), tile, zero)),
        tile=tile, engine=engine, fill=True, interpret=interpret,
    )
    return ko.reshape(bsz, -1)[:, :n], vo.reshape(bsz, -1)[:, :n]


# ---------------------------------------------------------------------------
# Flat merge-sort rounds: the padded buffer lives across the whole sort
# ---------------------------------------------------------------------------
#
# The sort keeps ONE flat buffer of ``m + sort_tail(tile)`` elements (``m``
# = pow2-padded data, then a sentinel tail built once per sort), run pairs
# are addressed by *flat* offsets riding in as scalar-prefetch tables, and
# window overrun into a neighboring run is excluded by the length-masked
# windows (valid counts derived in-kernel from the static run width)
# instead of by padding.  The sentinel tail of the output buffer is
# re-written by trailing grid steps, so the buffer never round-trips
# through a host-side concatenate between rounds.  (The tail is longer
# than a tile because a window fetch reads whole aligned row blocks.)


def _sort_round_starts(xf, m, width, tile, ntail):
    """Flat scalar-prefetch tables for one sort round (plus the tail entries)."""
    npairs = m // (2 * width)
    tpp = (2 * width) // tile
    runs = xf[:m].reshape(npairs, 2 * width)
    diags = jnp.arange(tpp, dtype=jnp.int32) * tile
    a0 = diagonal_intersections_batched(runs[:, :width], runs[:, width:], diags).astype(jnp.int32)
    b0 = diags[None, :] - a0
    base = (jnp.arange(npairs, dtype=jnp.int32) * (2 * width))[:, None]
    fa = (base + a0).reshape(-1)
    fb = (base + width + b0).reshape(-1)
    # the sentinel-tail grid steps still *address* the tables: give them
    # safe in-bounds entries
    zero = jnp.zeros((ntail,), jnp.int32)
    return jnp.concatenate([fa, zero]), jnp.concatenate([fb, zero]), npairs * tpp, tpp


def _geom_sort(width, tile, tpp, n_data):
    def geom(ids, fa, fb):
        s_id = ids[0]
        base = (s_id // tpp) * (2 * width)
        a0, b0 = fa[s_id], fb[s_id]
        # valid lengths: overrun past a run's width reads the *neighbor*
        # run (flat layout) — excluded by index, exactly like padding
        valid_a = jnp.clip(width - (a0 - base), 0, tile)
        valid_b = jnp.clip(width - (b0 - base - width), 0, tile)
        return None, a0, b0, valid_a, valid_b, s_id < n_data

    return geom


def _sort_round(bufs, width, tile, engine, interpret):
    ntail = sort_tail(tile) // tile
    m = bufs[0].shape[0] - ntail * tile
    fa, fb, ndata, tpp = _sort_round_starts(bufs[0], m, width, tile, ntail)
    w = _lanes(tile)
    # each buffer is both the A and the B operand: (keys, keys[, vals, vals])
    ins = tuple(x.reshape(-1, w) for x in bufs for _ in (0, 1))
    out = _launch(
        _geom_sort(width, tile, tpp, ndata), (ndata + ntail,), (fa, fb), ins,
        tile=tile, engine=engine, fill=False, interpret=interpret,
    )
    return [o.reshape(-1) for o in out]


def sort_round_pallas(
    xf: jax.Array,
    width: int,
    *,
    tile: int = DEFAULT_TILE,
    leaf: int = DEFAULT_LEAF,
    engine: str = DEFAULT_ENGINE,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """One bottom-up merge-sort round on the flat padded layout.

    ``xf`` is ``(m + sort_tail(tile),)``: ``m`` (a power of two, a
    multiple of ``2 * width``; ``tile`` must divide ``2 * width``) data
    elements holding sorted runs of ``width``, then sentinels.
    Returns the same layout with runs of ``2 * width`` — call repeatedly
    to sort.
    """
    (out,) = _sort_round([xf], width, tile, engine, interpret)
    return out


def sort_round_kv_pallas(
    kf: jax.Array,
    vf: jax.Array,
    width: int,
    *,
    tile: int = DEFAULT_TILE,
    leaf: int = DEFAULT_LEAF,
    engine: str = DEFAULT_ENGINE,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Key-value :func:`sort_round_pallas` (values: zero-filled tail)."""
    ko, vo = _sort_round([kf, vf], width, tile, engine, interpret)
    return ko, vo
