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

* ``"hier"`` (default) — the **hierarchical two-level tile engine**.  The
  paper's partition idea is applied *again inside the tile* (the
  recursion Siebert & Träff's co-ranking makes explicit): a fixed-trip
  vectorized bisection over the tile's sub-diagonals (level 2 of the
  partition, :func:`_split`) cuts the T-output tile into ``ceil(T/S)``
  leaves of ``S`` outputs each, and only the ``(S, S)`` leaf
  materializes the paper's Merge Matrix to get cross-ranks.  Rank
  application is an in-vreg lane gather driven by the leaf ranks (no
  ``(T, T)`` one-hot).  Per-tile work drops from O(T^2) to
  O(T*S + T log T); quadratic work only ever happens at the leaf size.
* ``"matrix"`` — the single-level engine: materialize the full ``(T, T)``
  Merge Matrix and apply ranks via a ``(T, T)`` one-hot select.  Kept as
  the bit-exactness oracle for the hierarchical engine and as the
  benchmark baseline (``bench_tile_engine``).

Both engines share the masked/unmasked rank form, so the ragged /
key-value length-masking guarantees (pads excluded from ranks by *index*,
never by comparing against the sentinel) carry through unchanged.  Every
data movement inside a tile is a select or a gather of the elements'
bit patterns, so results are bit-identical to the pure-JAX core routes.

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
from repro.core.merge_path import bisect_steps, diagonal_intersections, max_sentinel

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


def _norm_leaf(tile: int, leaf: int) -> int:
    """Clamp the leaf width into [1, min(tile, W)]: an S > T leaf is pure
    waste, and a leaf window must fit one lane row to be gathered."""
    return max(1, min(int(leaf), int(tile), _lanes(int(tile))))


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
# Merge-matrix ranks (shared by both engines)
# ---------------------------------------------------------------------------


def _leaf_ranks(
    la: jax.Array,
    lb: jax.Array,
    valid_a: Optional[jax.Array] = None,
    valid_b: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Cross-ranks of ``L`` stacked window pairs = their Merge Matrices, reduced.

    ``la`` / ``lb`` are ``(L, S)``.  ``M[l, i, j] = (la[l, i] > lb[l, j])``
    is the paper's binary merge matrix restricted to the pair; row sums
    give how many B elements precede each A element, column sums of the
    complement (ties go to A) the symmetric count; rank = own index +
    cross count.  The hierarchical engine calls this on its ``(S, S)``
    leaves (total work ``T*S``).

    Unmasked, sentinel pads rank like real elements — exact for
    **keys-only** tiles (a pad tied with a sentinel-valued payload writes
    the same value).  ``valid_a`` / ``valid_b`` (``(L, 1)``) give the
    number of real elements at the head of each window: pads are then
    excluded from the cross counts by *index*, never by comparing against
    the sentinel, so payload keys equal to the sentinel (real ``+inf``,
    int ``iinfo.max``) rank exactly, and pad entries themselves rank
    ``S`` (outside the window pair, dropped).
    """
    nl, s = la.shape
    m = la[:, :, None] > lb[:, None, :]  # (L, S, S) merge matrices
    iot = _iota((nl, s), 1)
    if valid_a is None:
        ra = iot + jnp.sum(m.astype(jnp.int32), axis=2)
        rb = iot + jnp.sum((~m).astype(jnp.int32), axis=1)
        return ra, rb
    jvalid = _iota((nl, 1, s), 2) < valid_b[:, :, None]
    ivalid = _iota((nl, s, 1), 1) < valid_a[:, :, None]
    ra = iot + jnp.sum((m & jvalid).astype(jnp.int32), axis=2)
    rb = iot + jnp.sum(((~m) & ivalid).astype(jnp.int32), axis=1)
    return jnp.where(iot < valid_a, ra, s), jnp.where(iot < valid_b, rb, s)


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
    With valid lengths, pads are excluded from the counts by index and
    rank ``T`` (dropped) — the same rule as :func:`_leaf_ranks`.
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
# Hierarchical two-level tile engine
# ---------------------------------------------------------------------------
#
# Level 1 (host side): Alg. 2 over the *global* cross diagonals produces
# per-tile (a_start, b_start) scalar-prefetch tables.  Level 2 (in-kernel):
# Alg. 2 again, over the tile's own sub-diagonals (0, S, 2S, ...), splits
# the T-output tile into leaves of S outputs — Lemma 16 applies
# recursively, so leaf l needs at most S consecutive elements of each
# window starting at its sub-partition point.  Only the (S, S) leaf
# computes cross-ranks via the merge matrix; ranks are applied with an
# in-vreg lane gather, so the T^2 term of the single-level engine becomes
# T*S + T log T.


def _probe(row: jax.Array, idx: jax.Array) -> jax.Array:
    """``row[0, idx]`` for an ``(L, 1)`` index column (clamped into range):
    a one-hot lane select + reduce, ``(L, T)`` work per probe."""
    t = row.shape[1]
    hit = _iota((idx.shape[0], t), 1) == jnp.clip(idx, 0, t - 1)
    return jnp.sum(jnp.where(hit, row, jnp.zeros((), row.dtype)), axis=1, keepdims=True, dtype=row.dtype)


def _split(wa: jax.Array, wb: jax.Array, diags: jax.Array, valid_a=None, valid_b=None) -> jax.Array:
    """Algorithm 2 over two sorted window rows ``(1, Ta)`` / ``(1, Tb)``.

    For each sub-diagonal of the ``(L, 1)`` column ``diags`` returns how
    many of the first ``d`` outputs of the stable A-priority merge of the
    windows come from ``wa`` (an ``(L, 1)`` column).  The trip count is
    fixed by the static window sizes; traced scalar valid lengths bound
    the interval so no probe compares against padding (callers clamp
    ``diags`` to ``valid_a + valid_b`` first).
    """
    ta, tb = wa.shape[1], wb.shape[1]
    if valid_a is None:
        lo = jnp.maximum(0, diags - tb)
        hi = jnp.minimum(diags, ta)
    else:
        lo = jnp.maximum(0, diags - valid_b)
        hi = jnp.minimum(diags, valid_a)

    def body(_, lh):
        lo, hi = lh
        mid = (lo + hi) >> 1
        pred = _probe(wa, mid) <= _probe(wb, diags - 1 - mid)  # A[i] precedes B[j] iff A[i] <= B[j]
        active = lo < hi
        return jnp.where(active & pred, mid + 1, lo), jnp.where(active & ~pred, mid, hi)

    lo, _ = jax.lax.fori_loop(0, bisect_steps(min(ta, tb)), body, (lo, hi))
    return lo


def _leaf_windows(w2: jax.Array, start: jax.Array, s: int, fill) -> jax.Array:
    """``(L, s)`` leaf windows of an ``(R, W)`` window (flat row-major):
    row ``l`` holds ``w[start_l : start_l + s]``, ``fill`` past the end.

    Each leaf spans at most two lane rows (``s <= W``): the two rows are
    picked by a select over the ``R`` rows, then one in-vreg lane gather
    rotates the leaf to the front.
    """
    r, w = w2.shape
    nl = start.shape[0]
    q, c = start // w, start % w
    lane = _iota((nl, w), 1)
    fillv = jnp.full((nl, w), fill, w2.dtype)
    row0, row1 = fillv, fillv
    for i in range(r):
        row0 = jnp.where(q == i, w2[i : i + 1, :], row0)
        row1 = jnp.where(q + 1 == i, w2[i : i + 1, :], row1)
    wrap = c + lane >= w
    idx = jnp.where(wrap, c + lane - w, c + lane)
    g = jnp.where(
        wrap,
        jnp.take_along_axis(row1, idx, axis=1),
        jnp.take_along_axis(row0, idx, axis=1),
    )
    g = jnp.where(start + lane < r * w, g, fillv)
    return g[:, :s]


def _flatten_leaves(x: jax.Array, tile: int) -> jax.Array:
    """``(L, S)`` leaf outputs -> the tile's ``(1, T)`` output row."""
    return jnp.concatenate([x[l : l + 1, :] for l in range(x.shape[0])], axis=1)[:, :tile]


def _hier_merge_window(wa2, wb2, *, tile, leaf, wav2, wbv2, valid_a, valid_b, fill):
    """Two-level merge of one tile's ``(R, W)`` windows → ``(1, T)`` rows.

    1. **Level-2 split**: one fixed-trip vectorized bisection
       (:func:`_split`) over the tile's sub-diagonals ``0, S, 2S, ...``
       yields each leaf's sub-partition point ``(sa_l, sb_l)`` —
       O((T/S) log T) probes.
    2. **Leaf ranks**: the ``(S, S)`` merge matrix of every leaf window
       pair, reduced to cross-ranks (masked when valid lengths are given)
       — O(T*S) total, the only quadratic-in-anything step.
    3. **Gather apply**: for output slot ``j`` of a leaf,
       ``alpha[j] = |{i : ra[i] < j}|`` counts the A-side contributions
       among the first ``j`` leaf outputs (``ra`` is strictly increasing,
       so this is a rank lookup, computed leaf-locally); slot ``j`` is an
       A output iff ``ra[alpha[j]] == j``, and the element is *gathered*
       from ``la[alpha[j]]`` / ``lb[j - alpha[j]]``.

    ``fill=True`` (ragged callers): slots past the windows' merged valid
    length get sentinel keys / zero values — bit-identical to the matrix
    engine's coverage fill.
    """
    s = leaf
    nleaf = -(-tile // s)  # ceil-div: last leaf may be short (trimmed below)
    masked = valid_a is not None
    sent = max_sentinel(wa2.dtype)
    diags = _iota((nleaf, 1), 0) * s
    if masked:
        total = valid_a + valid_b
        diags = jnp.minimum(diags, total)
    sa = _split(wa2.reshape(1, tile), wb2.reshape(1, tile), diags, valid_a, valid_b)
    sb = diags - sa
    la = _leaf_windows(wa2, sa, s, sent)
    lb = _leaf_windows(wb2, sb, s, sent)
    if masked:
        va = jnp.clip(valid_a - sa, 0, s)  # (L, 1) valid prefix of each leaf window
        vb = jnp.clip(valid_b - sb, 0, s)
        ra, _ = _leaf_ranks(la, lb, va, vb)
    else:
        ra, _ = _leaf_ranks(la, lb)
    # Clamp to S before the alpha count: a valid element belonging to a
    # *later* leaf can rank past S, and pads rank exactly S — clamping
    # keeps the per-leaf rank vector sorted without changing any count
    # of ranks < j for j < S.
    ra_c = jnp.minimum(ra, s)
    jj = _iota((nleaf, s), 1)  # output slot within leaf
    alpha = jnp.sum((ra_c[:, :, None] < _iota((1, 1, s), 2)).astype(jnp.int32), axis=1)
    is_a = jnp.take_along_axis(ra_c, alpha, axis=1) == jj  # alpha[l, j] <= j < S: in bounds
    src_b = jj - alpha

    def apply(xa, xb):
        return _flatten_leaves(
            jnp.where(
                is_a,
                jnp.take_along_axis(xa, alpha, axis=1),
                jnp.take_along_axis(xb, src_b, axis=1),
            ),
            tile,
        )

    out_k = apply(la, lb)
    out_v = None
    if wav2 is not None:
        zero = jnp.zeros((), wav2.dtype)
        out_v = apply(_leaf_windows(wav2, sa, s, zero), _leaf_windows(wbv2, sb, s, zero))
    if masked and fill:
        covered = _iota((1, tile), 1) < total
        out_k = jnp.where(covered, out_k, sent)
        if out_v is not None:
            out_v = jnp.where(covered, out_v, jnp.zeros((), out_v.dtype))
    return out_k, out_v


def _tile_merge(
    wak: jax.Array,
    wbk: jax.Array,
    *,
    tile: int,
    leaf: int,
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
            wak, wbk, tile=tile, leaf=leaf, wav2=wav, wbv2=wbv,
            valid_a=valid_a, valid_b=valid_b, fill=fill,
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


def _tile_kernel(*refs, n_prefetch, nd, nblk, geom, tile, leaf, engine, kv, fill):
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
            win[0], win[1], tile=tile, leaf=leaf, engine=engine,
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


def _launch(geom, grid, tables, ins, *, tile, leaf, engine, fill, interpret):
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
            leaf=_norm_leaf(tile, leaf), engine=engine, kv=kv, fill=fill,
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
        # Length-masked ranks: a window pad tied with a real sentinel-valued
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
    """Merge two sorted 1-D arrays with the Pallas SPM kernel."""
    ap, bp, a_starts, b_starts, n, nt = _prepare(a, b, tile)
    (out,) = _launch(
        _geom_1d(), (nt,), (a_starts, b_starts), (ap, bp),
        tile=tile, leaf=leaf, engine=engine, fill=False, interpret=interpret,
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
        tile=tile, leaf=leaf, engine=engine, fill=False, interpret=interpret,
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
        tile=tile, leaf=leaf, engine=engine, fill=False, interpret=interpret,
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
        tile=tile, leaf=leaf, engine=engine, fill=False, interpret=interpret,
    )
    return ko.reshape(bsz, -1)[:, :n], vo.reshape(bsz, -1)[:, :n]


# ---------------------------------------------------------------------------
# Ragged batched merges: per-row length tables via scalar prefetch
# ---------------------------------------------------------------------------
#
# The ragged form is the batched kernel with one addition: alongside the
# (B, nt) start tables, the per-row valid lengths ride in as scalar-
# prefetch operands (SMEM).  Each (batch, tile) grid step derives its
# windows' valid prefixes from the length tables and uses the length-
# masked rank form (at leaf scale for the hierarchical engine), so
# padding never shadows a payload and output slots past a row's merged
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
        tile=tile, leaf=leaf, engine=engine, fill=True, interpret=interpret,
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
        tile=tile, leaf=leaf, engine=engine, fill=True, interpret=interpret,
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
# rank form (valid counts derived in-kernel from the static run width)
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
        # masked ranks: overrun past a run's width reads the *neighbor*
        # run (flat layout) — excluded by index, exactly like padding
        valid_a = jnp.clip(width - (a0 - base), 0, tile)
        valid_b = jnp.clip(width - (b0 - base - width), 0, tile)
        return None, a0, b0, valid_a, valid_b, s_id < n_data

    return geom


def _sort_round(bufs, width, tile, leaf, engine, interpret):
    ntail = sort_tail(tile) // tile
    m = bufs[0].shape[0] - ntail * tile
    fa, fb, ndata, tpp = _sort_round_starts(bufs[0], m, width, tile, ntail)
    w = _lanes(tile)
    # each buffer is both the A and the B operand: (keys, keys[, vals, vals])
    ins = tuple(x.reshape(-1, w) for x in bufs for _ in (0, 1))
    out = _launch(
        _geom_sort(width, tile, tpp, ndata), (ndata + ntail,), (fa, fb), ins,
        tile=tile, leaf=leaf, engine=engine, fill=False, interpret=interpret,
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
    (out,) = _sort_round([xf], width, tile, leaf, engine, interpret)
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
    ko, vo = _sort_round([kf, vf], width, tile, leaf, engine, interpret)
    return ko, vo
