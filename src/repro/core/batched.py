"""Batched & k-way Merge Path — the paper's partition, fused over a batch axis.

The paper's Segmented Parallel Merge is explicitly pitched as a building
block for "sorting and other functions" (§6).  This module generalizes the
pairwise 1-D primitives of :mod:`repro.core.merge_path` along the two axes
every real consumer needs:

* **Batched** (leading batch axis): ``merge_batched`` / ``merge_kv_batched``
  merge ``B`` independent pairs of sorted rows at once.  Instead of vmapping
  the scalar merge (which re-traces the bisection per lane), all ``B * n``
  diagonal binary searches run as *one* vectorized Algorithm 2 pass — the
  vector lanes play the role of the paper's cores across rows *and*
  diagonals simultaneously.  This is the form the Pallas kernel's 2-D
  ``(batch, tile)`` grid consumes (``repro.kernels.merge_path``).
* **k-way**: ``merge_k`` / ``merge_k_kv`` merge ``k`` sorted runs by a
  tournament of pairwise Merge Paths (``ceil(log2 k)`` batched rounds), the
  classic multiway generalization of the co-rank partition (cf. Träff,
  "Simplified, stable parallel merging", PAPERS.md).  ``merge_sort_k`` is
  the bottom-up sort whose outer rounds instead merge each group of ``k``
  runs in a *single* multiway co-rank pass, rewriting the data only
  ``ceil(log_k N)`` times; with ``k = 2`` it is exactly the paper's merge
  sort.

Conventions match :mod:`repro.core.merge_path`: rows sorted ascending,
merges stable with A-priority (ties take A first; original order kept
within each input).  Sentinel padding (``max_sentinel``) is used for
power-of-two round structure; payloads *equal* to the sentinel are safe:
pads are always appended after the real data, ties resolve by stability
toward the earlier position, and the ragged/key-value paths additionally
exclude pads from ranks by **length** rather than by comparison (see the
ragged section below), so a pad can never shadow a real ``+inf`` /
``iinfo.max`` key.

The ``*_ragged`` variants carry per-row valid lengths — each row's data
is a sorted *prefix* of its storage row — which is how production
batches actually arrive (per-request candidate counts, masked vocab,
variable bucket sizes).

Everything is jittable and shardable; no Python-level per-row loops.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from .merge_path import bisect_steps, flip_desc, max_sentinel, min_sentinel, total_order_keys

__all__ = [
    "searchsorted_batched",
    "diagonal_intersections_batched",
    "diagonal_intersections_ragged",
    "merge_batched",
    "merge_kv_batched",
    "merge_batched_ragged",
    "merge_kv_batched_ragged",
    "merge_sort_batched",
    "merge_sort_kv_batched",
    "merge_sort_batched_ragged",
    "merge_sort_kv_batched_ragged",
    "stable_argsort_batched",
    "stable_argsort_batched_ragged",
    "topk_batched",
    "topk_batched_ragged",
    "merge_k",
    "merge_k_kv",
    "merge_k_onepass",
    "merge_sort_k",
]


def searchsorted_batched(sorted_rows: jax.Array, queries: jax.Array, side: str = "left") -> jax.Array:
    """Row-wise ``searchsorted``: one fused bisection over the whole batch.

    ``sorted_rows`` is ``(B, n)`` with each row ascending; ``queries`` is
    ``(B, m)``.  Returns ``(B, m)`` int32 insertion points, equal to
    ``jnp.searchsorted(sorted_rows[i], queries[i], side)`` per row.

    This is the cross-diagonal binary search of Algorithm 2 in its rank
    reading: with ``side="left"`` the result is ``|{j : row[j] < q}|``,
    with ``side="right"`` it is ``|{j : row[j] <= q}|`` — the two tie
    orientations that make the pairwise merge stable with A-priority.
    """
    if side not in ("left", "right"):
        raise ValueError(f"side must be 'left' or 'right', got {side!r}")
    b, n = sorted_rows.shape
    if n == 0:
        return jnp.zeros(queries.shape, jnp.int32)
    lo = jnp.zeros(queries.shape, jnp.int32)
    hi = jnp.full(queries.shape, n, jnp.int32)

    def body(_, lh):
        lo, hi = lh
        mid = (lo + hi) >> 1
        sv = jnp.take_along_axis(sorted_rows, jnp.clip(mid, 0, n - 1), axis=1)
        go_right = (sv < queries) if side == "left" else (sv <= queries)
        active = lo < hi
        lo2 = jnp.where(active & go_right, mid + 1, lo)
        hi2 = jnp.where(active & ~go_right, mid, hi)
        return lo2, hi2

    lo, hi = jax.lax.fori_loop(0, bisect_steps(n), body, (lo, hi))
    return lo


def diagonal_intersections_batched(a: jax.Array, b: jax.Array, diags: jax.Array) -> jax.Array:
    """Algorithm 2, vectorized over rows *and* diagonals at once.

    ``a`` is ``(B, na)``, ``b`` is ``(B, nb)``, ``diags`` is ``(D,)`` or
    ``(B, D)`` with ints in ``[0, na + nb]``.  Returns ``ai`` of shape
    ``(B, D)``: for batch row ``r`` and diagonal ``d``, the first ``d``
    outputs of the stable merge of ``a[r]`` and ``b[r]`` are
    ``a[r, :ai]`` and ``b[r, :d - ai]``.

    Equivalent to ``vmap(diagonal_intersections)`` but with a single
    fused bisection — one trip count, one gather per step, every
    ``(row, diagonal)`` pair in its own vector lane.
    """
    bsz, na = a.shape
    nb = b.shape[1]
    diags = jnp.asarray(diags, jnp.int32)
    if diags.ndim == 1:
        diags = jnp.broadcast_to(diags[None, :], (bsz, diags.shape[0]))
    if nb == 0:  # path is a straight vertical line
        return jnp.minimum(diags, na)
    if na == 0:  # straight horizontal line
        return jnp.zeros_like(diags)
    lo = jnp.maximum(0, diags - nb)
    hi = jnp.minimum(diags, na)

    def body(_, lh):
        lo, hi = lh
        mid = (lo + hi) >> 1
        av = jnp.take_along_axis(a, jnp.clip(mid, 0, na - 1), axis=1)
        bv = jnp.take_along_axis(b, jnp.clip(diags - 1 - mid, 0, nb - 1), axis=1)
        pred = av <= bv  # A-priority: A[i] precedes B[j] iff A[i] <= B[j]
        active = lo < hi
        lo2 = jnp.where(active & pred, mid + 1, lo)
        hi2 = jnp.where(active & ~pred, mid, hi)
        return lo2, hi2

    lo, hi = jax.lax.fori_loop(0, bisect_steps(min(na, nb)), body, (lo, hi))
    return lo


def diagonal_intersections_ragged(
    a: jax.Array, b: jax.Array, a_lens: jax.Array, b_lens: jax.Array, diags: jax.Array
) -> jax.Array:
    """Algorithm 2 over rows with per-row valid lengths.

    Like :func:`diagonal_intersections_batched`, but row ``r``'s inputs
    are the sorted prefixes ``a[r, :a_lens[r]]`` / ``b[r, :b_lens[r]]``
    and ``diags`` must lie in ``[0, a_lens[r] + b_lens[r]]`` (clip before
    calling).  The bisection interval is bounded by the row's *lengths*
    — ``lo = max(0, d - b_len)``, ``hi = min(d, a_len)`` — so every probe
    lands inside the valid prefixes and the search never compares against
    padding, whatever the tails contain.
    """
    bsz, na = a.shape
    nb = b.shape[1]
    a_lens = _as_lens(a_lens, bsz, na)
    b_lens = _as_lens(b_lens, bsz, nb)
    diags = jnp.asarray(diags, jnp.int32)
    if diags.ndim == 1:
        diags = jnp.broadcast_to(diags[None, :], (bsz, diags.shape[0]))
    if na == 0 or nb == 0:
        return jnp.minimum(diags, a_lens[:, None])
    lo = jnp.maximum(0, diags - b_lens[:, None])
    hi = jnp.minimum(diags, a_lens[:, None])

    def body(_, lh):
        lo, hi = lh
        mid = (lo + hi) >> 1
        av = jnp.take_along_axis(a, jnp.clip(mid, 0, na - 1), axis=1)
        bv = jnp.take_along_axis(b, jnp.clip(diags - 1 - mid, 0, nb - 1), axis=1)
        pred = av <= bv  # A-priority: A[i] precedes B[j] iff A[i] <= B[j]
        active = lo < hi
        lo2 = jnp.where(active & pred, mid + 1, lo)
        hi2 = jnp.where(active & ~pred, mid, hi)
        return lo2, hi2

    lo, hi = jax.lax.fori_loop(0, bisect_steps(min(na, nb)), body, (lo, hi))
    return lo


def _batched_ranks(a: jax.Array, b: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Cross-ranks of every element of every row pair, in one fused pass."""
    na, nb = a.shape[1], b.shape[1]
    ia = jnp.arange(na, dtype=jnp.int32)[None, :] + searchsorted_batched(b, a, side="left")
    ib = jnp.arange(nb, dtype=jnp.int32)[None, :] + searchsorted_batched(a, b, side="right")
    return ia, ib


def merge_batched(a: jax.Array, b: jax.Array) -> jax.Array:
    """Stable merge of ``B`` pairs of sorted rows: ``(B, na) + (B, nb) -> (B, na + nb)``.

    Row ``r`` of the result is exactly ``merge(a[r], b[r])`` (stable,
    A-priority) — bit-identical to the vmapped pairwise merge, but computed
    by a single vectorized Algorithm 2 pass: every element's output
    position is its cross-rank, and all ``B * (na + nb)`` rank searches
    share one fixed-trip bisection.
    """
    bsz, na = a.shape
    nb = b.shape[1]
    dtype = jnp.result_type(a, b)
    ia, ib = _batched_ranks(a, b)
    rows = jnp.arange(bsz, dtype=jnp.int32)[:, None]
    out = jnp.zeros((bsz, na + nb), dtype)
    out = out.at[rows, ia].set(a.astype(dtype))
    out = out.at[rows, ib].set(b.astype(dtype))
    return out


def merge_kv_batched(
    ak: jax.Array, av: jax.Array, bk: jax.Array, bv: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Stable batched key-value merge; row ``r`` equals ``merge_kv`` of row ``r``.

    ``ak``/``bk`` are ``(B, na)``/``(B, nb)`` sorted key rows; ``av``/``bv``
    the same-shape value rows carried along the permutation.
    """
    bsz, na = ak.shape
    nb = bk.shape[1]
    kd = jnp.result_type(ak, bk)
    vd = jnp.result_type(av, bv)
    ia, ib = _batched_ranks(ak, bk)
    rows = jnp.arange(bsz, dtype=jnp.int32)[:, None]
    keys = jnp.zeros((bsz, na + nb), kd).at[rows, ia].set(ak.astype(kd)).at[rows, ib].set(bk.astype(kd))
    vals = jnp.zeros((bsz, na + nb), vd).at[rows, ia].set(av.astype(vd)).at[rows, ib].set(bv.astype(vd))
    return keys, vals


# ---------------------------------------------------------------------------
# Ragged batched merges: per-row valid lengths
# ---------------------------------------------------------------------------
#
# Production batches are ragged: per-request candidate counts, per-row
# valid vocab, variable bucket sizes.  The ragged API carries a `(B,)`
# length vector per input; each row's valid data is a *prefix* of the
# fixed-width storage row (the padding tail's contents are ignored).
# Output rows hold the merged valid elements first and sentinel padding
# after.  Ranks are computed length-aware — pads are excluded by count,
# never by comparing against the sentinel — so payloads *equal* to the
# sentinel (real ``+inf`` keys, int ``iinfo.max``) merge correctly even
# in the key-value forms.


def _as_lens(lens, bsz: int, n: int) -> jax.Array:
    """Normalize a lengths argument to a clipped ``(B,)`` int32 vector."""
    lens = jnp.asarray(lens, jnp.int32)
    if lens.ndim == 0:
        lens = jnp.broadcast_to(lens, (bsz,))
    if lens.shape != (bsz,):
        raise ValueError(f"expected lengths of shape ({bsz},), got {lens.shape}")
    return jnp.clip(lens, 0, n)

def _mask_rows(x: jax.Array, lens: jax.Array, fill) -> jax.Array:
    """Replace entries at/after each row's length with ``fill``."""
    col = jnp.arange(x.shape[1], dtype=jnp.int32)[None, :]
    return jnp.where(col < lens[:, None], x, jnp.asarray(fill, x.dtype))


def _ragged_ranks(
    a: jax.Array, b: jax.Array, a_lens: jax.Array, b_lens: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Length-aware cross-ranks; pad entries rank past the output row.

    ``a``/``b`` must already be sentinel-masked beyond their lengths (so
    rows are globally sorted).  The ``left`` search can never count pads
    (nothing is < the sentinel); the ``right`` search is capped at the
    cross row's valid length so pads tied with a sentinel-valued payload
    are not counted.
    """
    na, nb = a.shape[1], b.shape[1]
    n = na + nb
    ia = jnp.arange(na, dtype=jnp.int32)[None, :]
    ib = jnp.arange(nb, dtype=jnp.int32)[None, :]
    ra = ia + jnp.minimum(searchsorted_batched(b, a, side="left"), b_lens[:, None])
    rb = ib + jnp.minimum(searchsorted_batched(a, b, side="right"), a_lens[:, None])
    ra = jnp.where(ia < a_lens[:, None], ra, n)
    rb = jnp.where(ib < b_lens[:, None], rb, n)
    return ra, rb


def merge_batched_ragged(
    a: jax.Array, b: jax.Array, a_lens, b_lens
) -> jax.Array:
    """Stable merge of ``B`` row pairs with per-row valid lengths.

    ``a`` is ``(B, na)``, ``b`` is ``(B, nb)``; row ``r``'s valid data is
    the sorted prefix ``a[r, :a_lens[r]]`` / ``b[r, :b_lens[r]]`` (the
    tail contents are ignored).  Returns ``(B, na + nb)`` whose row ``r``
    starts with the stable A-priority merge of the two valid prefixes
    (``a_lens[r] + b_lens[r]`` elements) followed by sentinel padding.
    """
    bsz, na = a.shape
    nb = b.shape[1]
    if b.shape[0] != bsz:
        raise ValueError(f"batch mismatch: {a.shape} vs {b.shape}")
    a_lens = _as_lens(a_lens, bsz, na)
    b_lens = _as_lens(b_lens, bsz, nb)
    dtype = jnp.result_type(a, b)
    sent = max_sentinel(dtype)
    am = _mask_rows(a.astype(dtype), a_lens, sent)
    bm = _mask_rows(b.astype(dtype), b_lens, sent)
    ra, rb = _ragged_ranks(am, bm, a_lens, b_lens)
    rows = jnp.arange(bsz, dtype=jnp.int32)[:, None]
    out = jnp.full((bsz, na + nb), sent, dtype)
    out = out.at[rows, ra].set(am, mode="drop")
    out = out.at[rows, rb].set(bm, mode="drop")
    return out


def merge_kv_batched_ragged(
    ak: jax.Array, av: jax.Array, bk: jax.Array, bv: jax.Array, a_lens, b_lens
) -> Tuple[jax.Array, jax.Array]:
    """Ragged stable key-value merge; see :func:`merge_batched_ragged`.

    Output values past a row's merged length are zero (key slots are
    sentinel).  Safe for payload keys equal to the sentinel: pads are
    excluded from ranks by length, so they can never shadow a real
    ``+inf`` / ``iinfo.max`` key and leak a zero value.
    """
    if av.shape != ak.shape or bv.shape != bk.shape:
        raise ValueError(
            f"value shapes must match key shapes: keys {ak.shape}/{bk.shape}, "
            f"values {av.shape}/{bv.shape}"
        )
    bsz, na = ak.shape
    nb = bk.shape[1]
    if bk.shape[0] != bsz:
        raise ValueError(f"batch mismatch: {ak.shape} vs {bk.shape}")
    a_lens = _as_lens(a_lens, bsz, na)
    b_lens = _as_lens(b_lens, bsz, nb)
    kd = jnp.result_type(ak, bk)
    vd = jnp.result_type(av, bv)
    sent = max_sentinel(kd)
    akm = _mask_rows(ak.astype(kd), a_lens, sent)
    bkm = _mask_rows(bk.astype(kd), b_lens, sent)
    ra, rb = _ragged_ranks(akm, bkm, a_lens, b_lens)
    rows = jnp.arange(bsz, dtype=jnp.int32)[:, None]
    keys = jnp.full((bsz, na + nb), sent, kd)
    keys = keys.at[rows, ra].set(akm, mode="drop").at[rows, rb].set(bkm, mode="drop")
    vals = jnp.zeros((bsz, na + nb), vd)
    vals = vals.at[rows, ra].set(av.astype(vd), mode="drop")
    vals = vals.at[rows, rb].set(bv.astype(vd), mode="drop")
    return keys, vals


def merge_sort_batched_ragged(x: jax.Array, lens) -> jax.Array:
    """Sort each row's valid prefix ascending; tail slots become sentinel.

    Pads are sentinel-masked *before* the sort; stability keeps real
    sentinel-valued payloads (which start at positions < ``lens[r]``)
    ahead of the pads, so the first ``lens[r]`` outputs are exactly the
    sorted valid prefix.
    """
    bsz, n = x.shape
    lens = _as_lens(lens, bsz, n)
    if jnp.issubdtype(x.dtype, jnp.floating):
        # NaN-deterministic route: mask pads in int total-order key space,
        # where the pad sentinel (iinfo.max) is *strictly* above every real
        # key — including NaN (canonical-NaN bits) and real +inf — so NaN
        # keys sort to the end of the valid prefix, never into the tail.
        tok = total_order_keys(x)
        tok = _mask_rows(tok, lens, max_sentinel(tok.dtype))
        _, out = merge_sort_kv_batched(tok, x)
        col = jnp.arange(n, dtype=jnp.int32)[None, :]
        return jnp.where(col < lens[:, None], out, max_sentinel(x.dtype))
    return merge_sort_batched(_mask_rows(x, lens, max_sentinel(x.dtype)))


def merge_sort_kv_batched_ragged(
    keys: jax.Array, values: jax.Array, lens
) -> Tuple[jax.Array, jax.Array]:
    """Ragged row-wise stable kv-sort (keys ascending over each valid prefix).

    Row ``r``'s first ``lens[r]`` output pairs are the stably sorted
    valid pairs; the tail carries sentinel keys with the masked slots'
    original values (in original order), so the value row remains a
    permutation of the input row.
    """
    bsz, n = keys.shape
    lens = _as_lens(lens, bsz, n)
    if jnp.issubdtype(keys.dtype, jnp.floating):
        # see merge_sort_batched_ragged: pads are masked in int total-order
        # key space so NaN keys stay inside the valid prefix (sorted last)
        tok = total_order_keys(keys)
        tok = _mask_rows(tok, lens, max_sentinel(tok.dtype))
        idx = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None, :], (bsz, n))
        _, perm = merge_sort_kv_batched(tok, idx)
        rows = jnp.arange(bsz, dtype=jnp.int32)[:, None]
        ks = keys[rows, perm]
        col = jnp.arange(n, dtype=jnp.int32)[None, :]
        ks = jnp.where(col < lens[:, None], ks, max_sentinel(keys.dtype))
        return ks, values[rows, perm]
    return merge_sort_kv_batched(
        _mask_rows(keys, lens, max_sentinel(keys.dtype)), values
    )


def stable_argsort_batched_ragged(keys: jax.Array, lens) -> jax.Array:
    """Ragged row-wise stable argsort: the first ``lens[r]`` entries of row
    ``r`` are ``np.argsort(keys[r, :lens[r]], kind="stable")``; the tail
    lists the masked positions in original order (a full permutation)."""
    bsz, n = keys.shape
    idx = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None, :], (bsz, n))
    _, perm = merge_sort_kv_batched_ragged(keys, idx, lens)
    return perm


def topk_batched_ragged(x: jax.Array, k: int, lens) -> Tuple[jax.Array, jax.Array]:
    """Row-wise descending top-k over each row's valid prefix.

    Returns ``(values, indices)``, each ``(B, min(k, n))`` — like
    :func:`topk_batched` (and ``jax.lax.top_k`` callers expect), ``k``
    silently truncates to the row width.  Slots ``j >= lens[r]`` (rows
    with fewer valid candidates than ``k``) return index ``-1`` and the
    dtype's minimum value.  Tie-breaking matches ``jax.lax.top_k``
    (smallest index first); int inputs containing ``iinfo.min`` are
    handled exactly (:func:`repro.core.merge_path.flip_desc`).
    """
    bsz, n = x.shape
    k = min(k, n)
    lens = _as_lens(lens, bsz, n)
    perm = stable_argsort_batched_ragged(flip_desc(x), lens)
    top_idx = perm[:, :k]
    vals = jnp.take_along_axis(x, top_idx, axis=1)
    slot_valid = jnp.arange(k, dtype=jnp.int32)[None, :] < lens[:, None]
    vals = jnp.where(slot_valid, vals, min_sentinel(x.dtype))
    top_idx = jnp.where(slot_valid, top_idx, -1)
    return vals, top_idx


def _pad_rows_pow2(x: jax.Array, fill) -> jax.Array:
    """Pad the last axis of ``(B, n)`` to the next power of two with ``fill``."""
    n = x.shape[1]
    m = 1 << max(0, (n - 1).bit_length())
    if m == n:
        return x
    pad = jnp.full((x.shape[0], m - n), fill, x.dtype)
    return jnp.concatenate([x, pad], axis=1)


def merge_sort_batched(x: jax.Array) -> jax.Array:
    """Sort every row of ``(B, n)`` ascending via batched merge-path rounds.

    The classic bottom-up structure of the paper's merge sort, but each of
    the ``log2 n`` rounds merges *all* runs of *all* rows in one
    :func:`merge_batched` call — batch and pair axes are flattened
    together, so the vector utilization is independent of where we are in
    the round schedule.

    Float rows route through :func:`repro.core.merge_path.total_order_keys`
    — the merge network compares same-width int keys while the float
    payload rides along as the value — so NaN keys sort last,
    deterministically, instead of poisoning the ``<=`` comparisons.  For
    NaN-free input the int key order coincides with the float order and
    the result is bit-identical to sorting the floats directly.
    """
    bsz, n = x.shape
    if n <= 1:
        return x
    if jnp.issubdtype(x.dtype, jnp.floating):
        _, out = merge_sort_kv_batched(total_order_keys(x), x)
        return out
    xp = _pad_rows_pow2(x, max_sentinel(x.dtype))
    m = xp.shape[1]
    width = 1
    while width < m:
        runs = xp.reshape(-1, 2, width)  # (B * m/2w, 2, w)
        xp = merge_batched(runs[:, 0], runs[:, 1]).reshape(bsz, m)
        width *= 2
    return xp[:, :n]


def merge_sort_kv_batched(keys: jax.Array, values: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Row-wise stable key-value sort of ``(B, n)`` keys (ascending).

    Stability is inherited from the A-priority pairwise merge, making this
    the batched form of the dispatch sort MoE relies on for deterministic
    capacity drops.

    Float keys take the NaN-deterministic route: the permutation is
    computed by kv-sorting the int :func:`total_order_keys` of the keys
    (NaN last), then both keys and values are gathered through it — the
    output keys are the *original* float bit patterns in sorted order.
    Bit-identical to the direct float sort whenever no key is NaN.
    """
    bsz, n = keys.shape
    if n <= 1:
        return keys, values
    if jnp.issubdtype(keys.dtype, jnp.floating):
        idx = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None, :], (bsz, n))
        _, perm = merge_sort_kv_batched(total_order_keys(keys), idx)
        rows = jnp.arange(bsz, dtype=jnp.int32)[:, None]
        return keys[rows, perm], values[rows, perm]
    kp = _pad_rows_pow2(keys, max_sentinel(keys.dtype))
    vp = _pad_rows_pow2(values, jnp.zeros((), values.dtype))
    m = kp.shape[1]
    width = 1
    while width < m:
        kr = kp.reshape(-1, 2, width)
        vr = vp.reshape(-1, 2, width)
        kp, vp = merge_kv_batched(kr[:, 0], vr[:, 0], kr[:, 1], vr[:, 1])
        kp = kp.reshape(bsz, m)
        vp = vp.reshape(bsz, m)
        width *= 2
    return kp[:, :n], vp[:, :n]


def stable_argsort_batched(keys: jax.Array) -> jax.Array:
    """Row-wise stable argsort (ascending) of ``(B, n)`` keys."""
    bsz, n = keys.shape
    idx = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None, :], (bsz, n))
    _, perm = merge_sort_kv_batched(keys, idx)
    return perm


def topk_batched(x: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """Row-wise descending top-k of ``(B, n)``: ``(values, indices)``, each ``(B, k)``.

    Stable like :func:`repro.core.merge_path.topk_desc` (among equal values
    the smallest index wins, matching ``jax.lax.top_k``), but all rows ride
    one batched kv-sort instead of a vmapped per-row sort.  Descending
    order comes from the order-flipped keys of
    :func:`repro.core.merge_path.flip_desc` (bitwise NOT for ints — exact
    at ``iinfo.min``, where negation would wrap).
    """
    perm = stable_argsort_batched(flip_desc(x))
    top_idx = perm[:, :k]
    return jnp.take_along_axis(x, top_idx, axis=1), top_idx


# ---------------------------------------------------------------------------
# k-way tournament merges
# ---------------------------------------------------------------------------

def _stack_runs(runs, lens=None):
    """Normalize a ``(k, n)`` array or a sequence of sorted 1-D runs.

    Returns ``(stacked, lens, static_total)`` where ``lens`` is the
    ``(k,)`` int32 per-run valid lengths and ``static_total`` is the
    total true length when it is known at trace time (list input, or an
    array with no ``lens``) — ``None`` for a caller-supplied ``lens``
    (possibly traced), in which case the merged output cannot be trimmed
    to a data-dependent shape.  Ragged list runs are sentinel-padded to
    the longest.
    """
    if isinstance(runs, jax.Array) or hasattr(runs, "shape"):
        runs = jnp.asarray(runs)
        if runs.ndim != 2:
            raise ValueError(f"expected (k, n) runs, got shape {runs.shape}")
        if lens is None:
            k, n = runs.shape
            return runs, jnp.full((k,), n, jnp.int32), k * n
        return runs, _as_lens(lens, runs.shape[0], runs.shape[1]), None
    if lens is not None:
        raise ValueError("lens is only valid with a stacked (k, n) runs array")
    runs = [jnp.asarray(r) for r in runs]
    if not runs:
        raise ValueError("merge_k needs at least one run")
    dtype = jnp.result_type(*runs)
    width = max(r.shape[0] for r in runs)
    sent = max_sentinel(dtype)
    padded = [
        jnp.concatenate([r.astype(dtype), jnp.full((width - r.shape[0],), sent, dtype)])
        for r in runs
    ]
    lens_arr = jnp.array([r.shape[0] for r in runs], jnp.int32)
    return jnp.stack(padded), lens_arr, sum(r.shape[0] for r in runs)


def merge_k(runs, lens=None) -> jax.Array:
    """Merge ``k`` sorted runs into one sorted array via a pairwise tournament.

    ``runs`` is a ``(k, n)`` array of sorted rows, or a sequence of sorted
    1-D arrays (possibly ragged — shorter runs are sentinel-padded).  With
    a stacked array, ``lens`` optionally gives each row's valid length
    (the tail is ignored) — the ragged form consumed by
    ``distributed_sort``'s variable bucket counts.  The tournament runs
    ``ceil(log2 k)`` rounds; round ``j`` merges ``k / 2^j`` run pairs with
    one :func:`merge_batched_ragged` call, i.e. the co-rank partition
    applied multiway exactly as in the stable multiway merges of Träff et
    al. (PAPERS.md).  ``k = 1`` is the identity.

    Stable across runs in input order: ties resolve toward the
    lower-indexed run (tournament rounds always merge lower-index runs as
    the A side).  Output: all valid elements merged, then sentinel
    padding; when the total true length is static (list input, or no
    ``lens``) the padding is trimmed off, otherwise the row is exactly
    ``(k * n,)`` wide.  Valid lengths ride through every round, so
    payloads equal to the sentinel are merged exactly (no
    strictly-below-sentinel caveat).
    """
    stacked, run_lens, static_total = _stack_runs(runs, lens)
    if static_total is None:
        # caller-supplied lens: sentinel-normalize the tails up front so the
        # output contract (valid prefix, then sentinel) holds even for the
        # k == 1 identity, which runs no merge round
        stacked = _mask_rows(stacked, run_lens, max_sentinel(stacked.dtype))
    k, n = stacked.shape
    target = 1 << max(0, (k - 1).bit_length())
    if target != k:
        pad = jnp.full((target - k, stacked.shape[1]), max_sentinel(stacked.dtype), stacked.dtype)
        stacked = jnp.concatenate([stacked, pad], axis=0)
        run_lens = jnp.concatenate([run_lens, jnp.zeros((target - k,), jnp.int32)])
    while stacked.shape[0] > 1:
        stacked = merge_batched_ragged(
            stacked[0::2], stacked[1::2], run_lens[0::2], run_lens[1::2]
        )
        run_lens = run_lens[0::2] + run_lens[1::2]
    # pow2 pad rows only contribute trailing sentinels: (k * n,) is enough
    out = stacked[0][: k * n]
    return out if static_total is None else out[:static_total]


def merge_k_kv(key_runs, value_runs, lens=None) -> Tuple[jax.Array, jax.Array]:
    """Key-value :func:`merge_k`: merge ``k`` sorted (keys, values) runs.

    ``key_runs`` / ``value_runs`` are matching ``(k, n)`` arrays or
    sequences of matching 1-D runs; ``lens`` optionally gives per-run
    valid lengths for a stacked array.  Stable with lower-run priority,
    like :func:`merge_k`.  Output: merged valid pairs first, then
    sentinel keys with zero values (trimmed when the total is static,
    ``(k * n,)`` wide otherwise).
    Lengths (not sentinel comparisons) exclude the padding, so payload
    keys equal to the sentinel keep their values — the failure mode of
    the pre-ragged tournament.
    """
    kstack, run_lens, static_total = _stack_runs(key_runs, lens)
    if isinstance(value_runs, jax.Array) or hasattr(value_runs, "shape"):
        vstack = jnp.asarray(value_runs)
    else:
        value_runs = [jnp.asarray(v) for v in value_runs]
        vd = jnp.result_type(*value_runs)
        width = kstack.shape[1]
        vstack = jnp.stack(
            [
                jnp.concatenate([v.astype(vd), jnp.zeros((width - v.shape[0],), vd)])
                for v in value_runs
            ]
        )
    if vstack.shape != kstack.shape:
        raise ValueError(f"key runs {kstack.shape} and value runs {vstack.shape} differ")
    if static_total is None:
        # see merge_k: normalize tails so the k == 1 identity honors the
        # sentinel-keys / zero-values output contract
        kstack = _mask_rows(kstack, run_lens, max_sentinel(kstack.dtype))
        vstack = _mask_rows(vstack, run_lens, jnp.zeros((), vstack.dtype))
    k, n = kstack.shape
    target = 1 << max(0, (k - 1).bit_length())
    if target != k:
        kpad = jnp.full((target - k, kstack.shape[1]), max_sentinel(kstack.dtype), kstack.dtype)
        vpad = jnp.zeros((target - k, vstack.shape[1]), vstack.dtype)
        kstack = jnp.concatenate([kstack, kpad], axis=0)
        vstack = jnp.concatenate([vstack, vpad], axis=0)
        run_lens = jnp.concatenate([run_lens, jnp.zeros((target - k,), jnp.int32)])
    while kstack.shape[0] > 1:
        kstack, vstack = merge_kv_batched_ragged(
            kstack[0::2], vstack[0::2], kstack[1::2], vstack[1::2],
            run_lens[0::2], run_lens[1::2],
        )
        run_lens = run_lens[0::2] + run_lens[1::2]
    if static_total is None:
        # pow2 pad rows only contribute trailing sentinel/zero pairs
        return kstack[0][: k * n], vstack[0][: k * n]
    return kstack[0][:static_total], vstack[0][:static_total]


def merge_k_onepass(runs, lens=None) -> jax.Array:
    """Merge ``k`` sorted runs in ONE multiway co-rank pass — no rounds.

    Same contract as :func:`merge_k` (stable with lower-run priority;
    ragged ``lens`` supported; output is the merged valid prefix followed
    by sentinel padding, trimmed when the total is static), but instead of
    ``ceil(log2 k)`` tournament rounds that rewrite the data every round,
    each element's final output position is computed directly: its own
    index plus, for every other run, the count of that run's valid
    elements preceding it — ``side="right"`` against lower-indexed runs
    (their ties come first) and ``side="left"`` against higher-indexed
    runs (our ties come first).  That is Siebert & Träff's stable multiway
    co-rank partition (PAPERS.md): ``O(k²)`` rank searches but a *single*
    scatter pass over the data, the right trade when runs are long and
    ``k`` is a mesh-sized constant — this is ``distributed_sort``'s
    default bucket combine (``combine="onepass"``).

    Length-capped counts exclude padding by *count*, never by comparing
    against the sentinel, so payloads equal to the sentinel merge exactly
    (the same guarantee as the ragged tournament).
    """
    stacked, run_lens, static_total = _stack_runs(runs, lens)
    k, n = stacked.shape
    sent = max_sentinel(stacked.dtype)
    sm = _mask_rows(stacked, run_lens, sent)
    if k == 1:
        out = sm[0] if static_total is None else stacked[0][:static_total]
        return out
    total = k * n
    out = jnp.full((total,), sent, stacked.dtype)
    t = jnp.arange(n, dtype=jnp.int32)
    jidx = jnp.arange(k, dtype=jnp.int32)[:, None]
    for j in range(k):
        q = jnp.broadcast_to(sm[j][None, :], (k, n))
        # counts of each run's elements preceding run j's elements; capped
        # at the run's valid length so pads are excluded by count
        cl = jnp.minimum(searchsorted_batched(sm, q, side="left"), run_lens[:, None])
        cr = jnp.minimum(searchsorted_batched(sm, q, side="right"), run_lens[:, None])
        cross = jnp.where(jidx < j, cr, cl)
        cross = jnp.where(jidx == j, 0, cross)
        rank = t + jnp.sum(cross, axis=0)
        rank = jnp.where(t < run_lens[j], rank, total)  # pads drop
        out = out.at[rank].set(sm[j], mode="drop")
    return out if static_total is None else out[:static_total]


def _merge_k_groups(runs: jax.Array) -> jax.Array:
    """Merge every group of ``k`` sorted runs in ONE co-rank pass.

    ``runs`` is ``(G, k, w)``: G independent groups of k sorted width-w
    runs.  For run ``j``, an element's output position inside its group is
    its own index plus, for every other run ``j'``, the count of that
    run's elements preceding it — ``side="right"`` for ``j' < j`` (their
    ties come first) and ``side="left"`` for ``j' > j`` (our ties come
    first).  That is the stable multiway co-rank partition (Siebert &
    Träff, PAPERS.md): ``k*(k-1)`` fused rank searches but a single
    scatter pass over the data.  Returns ``(G, k*w)``.
    """
    g, k, w = runs.shape
    dtype = runs.dtype
    out = jnp.zeros((g, k * w), dtype)
    grp = jnp.arange(g, dtype=jnp.int32)[:, None]
    for j in range(k):
        q = runs[:, j]  # (G, w)
        rank = jnp.broadcast_to(jnp.arange(w, dtype=jnp.int32)[None, :], (g, w))
        for jp in range(k):
            if jp == j:
                continue
            side = "right" if jp < j else "left"
            rank = rank + searchsorted_batched(runs[:, jp], q, side=side)
        out = out.at[grp, rank].set(q)
    return out


def merge_sort_k(x: jax.Array, k: int = 4) -> jax.Array:
    """Bottom-up merge sort with fan-in ``k`` multiway rounds.

    ``k`` must be a power of two.  Each outer round merges every group of
    ``k`` consecutive sorted runs in a single co-rank pass
    (:func:`_merge_k_groups`), so the data is rewritten only
    ``ceil(log_k N)`` times instead of ``log2 N`` — the paper's merge sort
    generalized multiway, trading ``k - 1`` rank searches per element per
    round for fewer passes.  With ``k = 2`` this is exactly the paper's
    pairwise merge sort.
    """
    if k < 1 or (k & (k - 1)) != 0:
        raise ValueError(f"fan-in k must be a power of two, got {k}")
    n = x.shape[0]
    if n <= 1:
        return x
    xp = _pad_rows_pow2(x[None, :], max_sentinel(x.dtype))[0]
    m = xp.shape[0]
    fan_max = max(k, 2)  # k=1 degenerates to the pairwise sort
    width = 1
    while width < m:
        fan = min(fan_max, m // width)  # last round may have fewer runs than k
        xp = _merge_k_groups(xp.reshape(-1, fan, width)).reshape(-1)
        width *= fan
    return xp[:n]
