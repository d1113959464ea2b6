"""Distributed Merge Path — the paper's algorithm lifted to a device mesh.

The paper partitions one merge across p cores sharing a cache; here the
"cores" are TPU chips sharing an ICI, the partition math is identical, and
the shared cache is replaced by explicit collectives.  Every primitive
comes in two exchange flavors:

* ``exchange="window"`` (default, **bandwidth-optimal**): the paper's
  global diagonal intersection (Alg. 2) runs *collectively* — each probe
  of a remote element is a tiny ``psum`` (the memory fabric of the
  shared-cache machine becomes the mesh interconnect), so every device
  ends up with the exact, replicated cut table ``a_cuts[k] =
  intersection(k * seg)``.  Corollary 7 then says device ``i``'s 1/P
  output segment consumes *exactly* ``A[a_cuts[i]:a_cuts[i+1]]`` and
  ``B[b_cuts[i]:b_cuts[i+1]]`` — disjoint, consecutive windows covering
  the inputs — so one ``all_to_all`` of per-(sender, receiver) window
  pieces moves each element **once**: O(N/P) payload per device instead
  of the gather's O(N).  Pieces ride in fixed-size rows padded to the
  provable max-piece bound (:func:`window_bounds`; XLA collectives are
  static-shape — a ``ragged_all_to_all`` backend would make wire bytes
  equal payload bytes), and the merge itself is the ragged length-masked
  rank merge, so sentinel-valued payloads are exact.
* ``exchange="gather"``: the original Megatron-style all_gather body —
  bandwidth-suboptimal (every element moves P-1 times) but
  latency-optimal, kept as the bit-exactness oracle.  Both flavors share
  the same cut math and the same window-rank merge tail, and are fuzzed
  bit-identical in ``tests/test_distributed.py``.

Primitives:

* ``distributed_merge`` / ``distributed_merge_kv`` and their ``*_batched``
  forms: A and B sharded contiguously over the axis; each device returns
  exactly its 1/P slice of the merged output.
* ``distributed_sort``: one-round splitter-bucketed sample sort — local
  sort (optionally the Pallas hier engine via ``local_sort="pallas"``),
  splitter selection from a P*P sample, ONE all_to_all bucket exchange
  (each element moves once), then a local ragged combine of the P
  received runs: ``combine="onepass"`` (default) is the single multiway
  co-rank pass of :func:`repro.core.batched.merge_k_onepass`,
  ``combine="tournament"`` the log(P)-round pairwise tournament (rounds
  on the Pallas ragged kernel when ``local_sort="pallas"``).
* ``distributed_topk`` / ``distributed_topk_batched``: per-shard
  merge-path top-k, then either a log2(P) **butterfly** combine
  (``exchange="butterfly"``, default for power-of-two P: k·log2(P)
  elements moved per device) or an all_gather of the P candidate runs
  (``exchange="gather"``, P·k per device) merged by ``merge_k_kv``.
  Used for vocab-sharded sampling in serving.

Self-healing: every public wrapper routes its *eager* calls through
``repro.runtime.resilience.guarded_call`` with always-on output
verification (the distributed perf anchor gates exchanged bytes, so the
host-side check is free w.r.t. CI): merges degrade
``window -> gather -> core-resort``, the sample sort escalates capacity
(``sample -> capacity-2x -> core-resort``; escalation changes the padded
output shape — slice by the returned counts), and top-k degrades
``butterfly -> gather -> core-topk``.  Traced calls (inside ``jit`` or a
caller's ``shard_map``) bypass the guard: a per-device divergent fallback
would deadlock the collectives.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

try:  # jax >= 0.6: top-level export, replication check renamed to check_vma
    from jax import shard_map as _shard_map_impl

    _CHECK_KW = "check_vma"
except ImportError:  # jax 0.4.x
    from jax.experimental.shard_map import shard_map as _shard_map_impl

    _CHECK_KW = "check_rep"


def shard_map(f, *, mesh, in_specs, out_specs, **kwargs):
    """Version-portable ``shard_map``: accepts either replication-check
    kwarg name and translates to the installed jax's spelling.  Defaults
    the check off (this repo's bodies use untyped collectives), but an
    explicit ``check_vma=True`` / ``check_rep=True`` is honored."""
    check = kwargs.pop("check_vma", None)
    if check is None:
        check = kwargs.pop("check_rep", None)
    else:
        kwargs.pop("check_rep", None)
    kwargs[_CHECK_KW] = False if check is None else check
    return _shard_map_impl(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, **kwargs)


def _axis_size(axis_name: str) -> int:
    """``jax.lax.axis_size`` compat (added after 0.4.x)."""
    if hasattr(jax.lax, "axis_size"):
        return jax.lax.axis_size(axis_name)
    return jax.lax.psum(1, axis_name)

from .batched import (
    _mask_rows,
    _ragged_ranks,
    diagonal_intersections_batched,
    merge_k,
    merge_k_kv,
    merge_k_onepass,
    merge_kv_batched,
    merge_sort_batched,
    merge_sort_kv_batched,
    topk_batched,
)
from .merge_path import (
    bisect_steps,
    diagonal_intersections,
    flip_desc,
    max_sentinel,
    merge_sort,
    merge_sort_kv,
    total_order_keys,
)
from .segmented import _masked_window_ranks

# Module-form imports (not ``from repro.runtime import ...``): the runtime
# package imports ``repro.core`` back, so during a cycle only the
# sys.modules entries exist — binding the (possibly still-initialising)
# module objects here and deferring attribute access to call time keeps
# both import orders working.
import repro.runtime.faults as _faults
import repro.runtime.resilience as _res

# telemetry is dependency-free (stdlib only) — no cycle risk
from repro.telemetry import get_telemetry as _get_telemetry


# ---------------------------------------------------------------------------
# window partition math (shared by implementation, tests, and benchmarks)
# ---------------------------------------------------------------------------

def window_bounds(na: int, nb: int, p: int) -> Tuple[int, int, int, int, int]:
    """Static bounds of the window exchange: ``(seg, W_a, W_b, w_a, w_b)``.

    ``seg`` is the per-device output segment (ceil-div, Corollary 7).
    ``W_a``/``W_b`` bound any device's A/B *window* length: a ``seg``-output
    segment consumes at most ``seg`` consecutive elements of each input
    (Lemma 16), and never more than the whole input.  ``w_a``/``w_b``
    bound any single (sender, receiver) *piece*: a piece is the overlap of
    one sender's contiguous shard (``ceil(n/p)`` elements) with one
    receiver's window, so it is capped by both.

    These are theorems, not heuristics — the fuzz tests assert the true
    window/piece sizes never exceed them, which is what guarantees the
    fixed-size exchange buffers can never silently truncate data.
    """
    if p < 1:
        raise ValueError(f"p must be >= 1, got {p}")
    n = na + nb
    seg = -(-n // p)
    m_a = -(-na // p) if na else 0
    m_b = -(-nb // p) if nb else 0
    W_a = max(1, min(na, seg))
    W_b = max(1, min(nb, seg))
    w_a = max(1, min(m_a, W_a))
    w_b = max(1, min(m_b, W_b))
    return seg, W_a, W_b, w_a, w_b


def exchange_bytes(
    na: int, nb: int, p: int, itemsize: int, kv: bool = False, rows: int = 1
) -> dict:
    """Per-device element-bytes moved by each exchange flavor (analytic).

    ``gather``: every device receives the other ``p-1`` shards of both
    inputs (and both value arrays when ``kv``) — O(N) per device.
    ``window`` payload: each device receives exactly its output segment's
    windows (``alen + blen = seg`` elements, O(N/P)) plus the collective
    bisection's probe traffic (``2 * bisect_steps`` psums of an
    ``(rows, p+1)`` buffer — ``rows`` is the batch size of the
    ``*_batched`` forms, whose every row carries its own cut table).
    ``window`` wire: what the dense static-shape ``all_to_all`` actually
    ships with pieces padded to the max-piece bound — a
    ``ragged_all_to_all`` backend would collapse wire to payload.
    All data terms scale linearly in ``rows``.
    """
    seg, W_a, W_b, w_a, w_b = window_bounds(na, nb, p)
    # same guarded ceil-div as window_bounds — keep the two in lockstep
    m_a = -(-na // p) if na else 0
    m_b = -(-nb // p) if nb else 0
    nval = (2 if kv else 1) * rows
    gather = (p - 1) * (m_a + m_b) * itemsize * nval
    probes = 2 * bisect_steps(min(na, nb)) * rows * (p + 1) * itemsize
    payload = seg * itemsize * nval + probes
    wire = p * (w_a + w_b) * itemsize * nval + probes
    return {
        "gather": gather,
        "window_payload": payload,
        "window_wire_padded": wire,
        "seg": seg,
        "max_window": (W_a, W_b),
        "max_piece": (w_a, w_b),
    }


# ---------------------------------------------------------------------------
# collective diagonal intersections (Algorithm 2 over the mesh)
# ---------------------------------------------------------------------------

def _collective_intersections(
    a_sh: jax.Array,
    b_sh: jax.Array,
    diags: jax.Array,
    na: int,
    nb: int,
    axis_name: str,
    idx: jax.Array,
) -> jax.Array:
    """Algorithm 2's diagonal bisection with *collective* memory probes.

    ``a_sh``/``b_sh`` are this device's contiguous ``(R, m)`` shards of
    the global ``(R, na)``/``(R, nb)`` sorted rows; ``diags`` is ``(D,)``
    global cross diagonals.  The bisection state is replicated (every
    device runs the identical search), and each probe of ``A[g]`` /
    ``B[g]`` is one ``psum``: the owning device contributes the element,
    everyone else zero.  ``2 * bisect_steps(min(na, nb))`` psums of tiny
    ``(R, D)`` buffers total — the paper's O(p log N) partition stage
    (Table 1, col 1) with the shared cache replaced by the interconnect.

    Returns the replicated ``(R, D)`` a-side cuts.
    """
    r, m_a = a_sh.shape
    m_b = b_sh.shape[1]
    dg = jnp.broadcast_to(jnp.asarray(diags, jnp.int32)[None, :], (r, diags.shape[0]))
    if na == 0 or nb == 0:
        return jnp.minimum(dg, na)

    def probe(shard, g, m):
        own = g // m
        loc = jnp.clip(g - own * m, 0, m - 1)
        v = jnp.take_along_axis(shard, loc, axis=1)
        mine = jnp.where(own == idx, v, jnp.zeros((), shard.dtype))
        return jax.lax.psum(mine, axis_name)

    lo = jnp.maximum(0, dg - nb)
    hi = jnp.minimum(dg, na)

    def body(_, lh):
        lo, hi = lh
        mid = (lo + hi) >> 1
        av = probe(a_sh, jnp.clip(mid, 0, na - 1), m_a)
        bv = probe(b_sh, jnp.clip(dg - 1 - mid, 0, nb - 1), m_b)
        pred = av <= bv  # A-priority: A[i] precedes B[j] iff A[i] <= B[j]
        active = lo < hi
        lo2 = jnp.where(active & pred, mid + 1, lo)
        hi2 = jnp.where(active & ~pred, mid, hi)
        return lo2, hi2

    lo, hi = jax.lax.fori_loop(0, bisect_steps(min(na, nb)), body, (lo, hi))
    return lo


# ---------------------------------------------------------------------------
# the window exchange (one all_to_all, each element moves once)
# ---------------------------------------------------------------------------

def _exchange_windows(
    shards,  # sequence of ((R, m) shard, fill) sharing the same cut table
    cuts: jax.Array,  # (R, p+1) replicated global cut table
    w_piece: int,
    W: int,
    p: int,
    axis_name: str,
    idx: jax.Array,
):
    """Move each device's exact input window to it with one all_to_all.

    The cut table partitions the global index space into P consecutive,
    disjoint receiver windows ``[cuts[i], cuts[i+1])``.  Sender side:
    device ``j`` slices, for every receiver ``i``, the overlap of its own
    shard ``[j*m, (j+1)*m)`` with window ``i`` — each element is in
    exactly one piece, so each element is sent exactly once.  Pieces ride
    in ``(p, R, w_piece)`` rows (``w_piece`` = the provable max-piece
    bound of :func:`window_bounds`).  Receiver side: the piece lengths
    are recomputed locally from the replicated cut table (no extra
    collective) and the pieces are scattered at their running offsets
    into a ``(R, W)`` window buffer pre-filled with ``fill``.

    Returns ``(windows, wlen)``: one ``(R, W)`` buffer per input shard
    (fill-padded past the window length) and the ``(R,)`` window lengths.
    """
    r, m = shards[0][0].shape
    my_lo = idx * m
    # sender side only needs each piece's start (the receiver's scatter
    # mask, built from the same replicated cuts, bounds its length)
    lo_i = jnp.maximum(cuts[:, :-1], my_lo)  # (R, p) per-receiver piece starts
    start_loc = jnp.clip(lo_i - my_lo, 0, m)  # (R, p) piece start in my shard
    gcols = start_loc.T[:, :, None] + jnp.arange(w_piece, dtype=jnp.int32)[None, None, :]

    # receiver-side reassembly plan, from the replicated cuts alone
    c0 = jax.lax.dynamic_slice_in_dim(cuts, idx, 1, axis=1)[:, 0]  # (R,)
    c1 = jax.lax.dynamic_slice_in_dim(cuts, idx + 1, 1, axis=1)[:, 0]
    wlen = c1 - c0
    j_lo = jnp.arange(p, dtype=jnp.int32)[None, :] * m  # (1, p) sender shard starts
    cnt_recv = jnp.clip(
        jnp.minimum(c1[:, None], j_lo + m) - jnp.maximum(c0[:, None], j_lo), 0, m
    )  # (R, p) piece length from each sender
    offs = jnp.cumsum(cnt_recv, axis=1) - cnt_recv  # (R, p) exclusive
    pos = offs.T[:, :, None] + jnp.arange(w_piece, dtype=jnp.int32)[None, None, :]
    valid = jnp.arange(w_piece, dtype=jnp.int32)[None, None, :] < cnt_recv.T[:, :, None]
    pos = jnp.where(valid, pos, W)  # (p, R, w_piece); W = out-of-bounds drop
    rows = jnp.broadcast_to(jnp.arange(r, dtype=jnp.int32)[None, :, None], pos.shape)

    windows = []
    for shard, fill in shards:
        shard_p = jnp.concatenate(
            [shard, jnp.full((r, w_piece), fill, shard.dtype)], axis=1
        )
        send = jnp.take_along_axis(
            jnp.broadcast_to(shard_p[None], (p,) + shard_p.shape), gcols, axis=2
        )  # (p, R, w_piece)
        recv = jax.lax.all_to_all(
            send, axis_name, split_axis=0, concat_axis=0, tiled=True
        )  # (p, R, w_piece): sender j's piece for me
        win = jnp.full((r, W), fill, shard.dtype)
        win = win.at[rows, pos].set(recv, mode="drop")
        windows.append(win)
    return windows, wlen


# ---------------------------------------------------------------------------
# distributed merge (keys-only and key-value, 1-D and batched)
# ---------------------------------------------------------------------------

def _segment_from_windows(wa, wb, alen, blen, seg, va=None, vb=None):
    """Merge two fill-padded ragged windows into this device's segment.

    ``wa``/``wb`` are ``(R, W)`` windows sentinel-masked past
    ``alen``/``blen`` (and ``va``/``vb`` the zero-masked value windows for
    the kv form).  Ranks are length-masked (PR 2's ragged contract), so
    padding is excluded by count — payload keys equal to the sentinel
    merge exactly.  Because the windows are *exactly* the segment's
    inputs, ``alen + blen <= seg`` and every valid rank lands in-bounds.
    """
    ra, rb = _ragged_ranks(wa, wb, alen, blen)
    r = wa.shape[0]
    rows = jnp.arange(r, dtype=jnp.int32)[:, None]
    keys = jnp.full((r, seg), max_sentinel(wa.dtype), wa.dtype)
    keys = keys.at[rows, ra].set(wa, mode="drop").at[rows, rb].set(wb, mode="drop")
    if va is None:
        return keys, None
    vals = jnp.zeros((r, seg), va.dtype)
    vals = vals.at[rows, ra].set(va, mode="drop").at[rows, rb].set(vb, mode="drop")
    return keys, vals


def _merge_local_body(
    ak_sh, av_sh, bk_sh, bv_sh, *, axis_name, na, nb, p, exchange
):
    """Per-device body shared by every distributed merge variant.

    Shards are ``(R, m)`` (R = batch rows, R = 1 for the 1-D forms), with
    the last shard sentinel-padded past the true ``na``/``nb``.  Returns
    this device's ``(R, seg)`` output segment (keys, values-or-None).
    """
    idx = jax.lax.axis_index(axis_name)
    n = na + nb
    seg, W_a, W_b, w_a, w_b = window_bounds(na, nb, p)
    kv = av_sh is not None
    sent = max_sentinel(ak_sh.dtype)

    if exchange == "gather":
        # bandwidth-suboptimal oracle: replicate everything, slice windows
        a_full = jax.lax.all_gather(ak_sh, axis_name, tiled=True, axis=1)[:, :na]
        b_full = jax.lax.all_gather(bk_sh, axis_name, tiled=True, axis=1)[:, :nb]
        d0 = jnp.minimum(idx * seg, n)
        d1 = jnp.minimum(d0 + seg, n)
        dg = jnp.stack([d0, d1]).astype(jnp.int32)  # (2,)
        cuts2 = diagonal_intersections_batched(a_full, b_full, dg)  # (R, 2)
        a0, alen = cuts2[:, 0], cuts2[:, 1] - cuts2[:, 0]
        b0, blen = d0 - cuts2[:, 0], (d1 - d0) - (cuts2[:, 1] - cuts2[:, 0])

        def take_window(full, start, W, fill):
            fp = jnp.concatenate(
                [full, jnp.full((full.shape[0], W), fill, full.dtype)], axis=1
            )
            cols = start[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]
            return jnp.take_along_axis(fp, cols, axis=1)

        wa = _mask_rows(take_window(a_full, a0, W_a, sent), alen, sent)
        wb = _mask_rows(take_window(b_full, b0, W_b, sent), blen, sent)
        va = vb = None
        if kv:
            av_f = jax.lax.all_gather(av_sh, axis_name, tiled=True, axis=1)[:, :na]
            bv_f = jax.lax.all_gather(bv_sh, axis_name, tiled=True, axis=1)[:, :nb]
            va = _mask_rows(take_window(av_f, a0, W_a, 0), alen, 0)
            vb = _mask_rows(take_window(bv_f, b0, W_b, 0), blen, 0)
        return _segment_from_windows(wa, wb, alen, blen, seg, va, vb)

    if exchange != "window":
        raise ValueError(f"exchange must be 'window' or 'gather', got {exchange!r}")
    # bandwidth-optimal: collective Alg. 2 for the replicated cut table,
    # then ONE all_to_all per array moving each element exactly once
    diags = np.minimum(np.arange(p + 1, dtype=np.int32) * seg, n)
    a_cuts = _collective_intersections(ak_sh, bk_sh, diags, na, nb, axis_name, idx)
    b_cuts = jnp.asarray(diags, jnp.int32)[None, :] - a_cuts
    a_shards = [(ak_sh, sent)] + ([(av_sh, jnp.zeros((), av_sh.dtype))] if kv else [])
    b_shards = [(bk_sh, sent)] + ([(bv_sh, jnp.zeros((), bv_sh.dtype))] if kv else [])
    a_wins, alen = _exchange_windows(a_shards, a_cuts, w_a, W_a, p, axis_name, idx)
    b_wins, blen = _exchange_windows(b_shards, b_cuts, w_b, W_b, p, axis_name, idx)
    va = a_wins[1] if kv else None
    vb = b_wins[1] if kv else None
    return _segment_from_windows(a_wins[0], b_wins[0], alen, blen, seg, va, vb)


def _pad_shardable(x: jax.Array, p: int, fill) -> jax.Array:
    """Pad the last axis up to the next multiple of ``p`` with ``fill``."""
    n = x.shape[-1]
    pn = -(-n // p) * p
    if pn == n:
        return x
    pad = jnp.full(x.shape[:-1] + (pn - n,), fill, x.dtype)
    return jnp.concatenate([x, pad], axis=-1)


def _distributed_merge_impl(ak, av, bk, bv, mesh, axis, exchange):
    """Shared wrapper: pad to equal shards, shard_map the merge body, trim.

    Inputs are ``(R, na)`` / ``(R, nb)`` (values may be None); output is
    ``(R, na + nb)`` keys (and values), sharded over the mesh axis.
    """
    if mesh is None:
        mesh = Mesh(jax.devices(), (axis,))
    p = mesh.shape[axis]
    na, nb = ak.shape[-1], bk.shape[-1]
    kd = jnp.result_type(ak, bk)
    ak = ak.astype(kd)
    bk = bk.astype(kd)
    kv = av is not None
    if kv:
        vd = jnp.result_type(av, bv)
        av = av.astype(vd)
        bv = bv.astype(vd)
    if na == 0 or nb == 0:
        keys = bk if na == 0 else ak
        vals = (bv if na == 0 else av) if kv else None
        return keys, vals
    sent = max_sentinel(kd)
    ak = _pad_shardable(ak, p, sent)
    bk = _pad_shardable(bk, p, sent)
    if kv:
        av = _pad_shardable(av, p, jnp.zeros((), av.dtype))
        bv = _pad_shardable(bv, p, jnp.zeros((), bv.dtype))
    body = functools.partial(
        _merge_local_body, axis_name=axis, na=na, nb=nb, p=p, exchange=exchange
    )
    spec = P(None, axis)
    if kv:
        fn = shard_map(
            lambda a, v, b, w: body(a, v, b, w),
            mesh=mesh,
            in_specs=(spec, spec, spec, spec),
            out_specs=(spec, spec),
            check_vma=False,
        )
        keys, vals = fn(ak, av, bk, bv)
        return keys[:, : na + nb], vals[:, : na + nb]
    fn = shard_map(
        lambda a, b: body(a, None, b, None)[0],
        mesh=mesh,
        in_specs=(spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(ak, bk)[:, : na + nb], None


# ---------------------------------------------------------------------------
# guarded dispatch (window -> gather -> core-resort)
# ---------------------------------------------------------------------------

@jax.jit
def _resort_rows(ak, bk):
    """Terminal merge fallback: total-order re-sort of the concatenation
    (stable sort of ``[A; B]`` == the stable A-priority merge)."""
    return merge_sort_batched(jnp.concatenate([ak, bk], axis=-1))


@jax.jit
def _resort_rows_kv(ak, av, bk, bv):
    return merge_sort_kv_batched(
        jnp.concatenate([ak, bk], axis=-1), jnp.concatenate([av, bv], axis=-1)
    )


def _resort_merge(ak, av, bk, bv):
    if av is None:
        return _resort_rows(ak, bk), None
    return _resort_rows_kv(ak, av, bk, bv)


def _record_merge_telemetry(op, ak, bk, mesh, axis, kv):
    """Record the Cor. 7 load-balance metrics for one eager merge.

    Counters: per-device window sizes (``distributed.window_elems.dev*``)
    and accumulated analytic exchange bytes.  Gauges: the per-call
    exchange-byte flavors and ``distributed.balance_ratio`` — max/min of
    the per-device window totals, which Cor. 7 pins to ~1.0 (exactly 1.0
    when ``p | na+nb``; otherwise bounded by the ceil-div remainder).
    The cut table comes from the same Alg. 2 bisection the exchange uses,
    so the recorded windows are the windows that actually moved.
    """
    na, nb = ak.shape[-1], bk.shape[-1]
    if na == 0 or nb == 0:
        return
    p = mesh.shape[axis] if mesh is not None else len(jax.devices())
    rows = ak.shape[0]
    n = na + nb
    info = exchange_bytes(
        na, nb, p, jnp.dtype(jnp.result_type(ak, bk)).itemsize, kv=kv, rows=rows
    )
    tel = _get_telemetry()
    tel.counter("distributed.exchange_calls").add(1)
    for flavor in ("gather", "window_payload", "window_wire_padded"):
        tel.counter(f"distributed.exchange_bytes.{flavor}").add(info[flavor])
        tel.gauge(f"distributed.exchange_bytes.{flavor}").set(info[flavor])
    diags = np.minimum(np.arange(p + 1, dtype=np.int64) * info["seg"], n)
    cuts = np.asarray(
        diagonal_intersections_batched(
            total_order_keys(ak), total_order_keys(bk), jnp.asarray(diags, jnp.int32)
        )
    )
    wa = np.diff(cuts.astype(np.int64), axis=1)  # (rows, p) A-window lengths
    wb = np.diff(diags)[None, :] - wa
    win = (wa + wb).sum(axis=0)
    for d in range(p):
        tel.counter(f"distributed.window_elems.dev{d}").add(int(win[d]))
    nz = win[win > 0]
    ratio = float(nz.max() / nz.min()) if nz.size >= 2 else 1.0
    tel.gauge("distributed.balance_ratio").set(ratio)


def _guarded_merge(op, ak, av, bk, bv, mesh, axis, exchange):
    """Route one distributed merge through the guard.

    Attempt chain: the requested exchange, then ``gather`` (the all-gather
    oracle), then ``core-resort`` — a single-process total-order re-sort of
    the concatenation, which survives even NaN-laced (unsorted) inputs.
    Verification is always on here (tok-space sortedness of the trimmed
    keys): the distributed perf anchor gates exchanged *bytes*, not
    wall-clock, so the host-side check cannot regress CI.  Under tracing
    (the wrappers inside ``jit``/``grad``) the guard bypasses to the
    requested exchange — Python cannot branch on device failures there, and
    a per-device divergent fallback would deadlock the collectives.
    """
    if exchange not in ("window", "gather"):
        raise ValueError(f"exchange must be 'window' or 'gather', got {exchange!r}")
    args = (ak, bk) if av is None else (ak, av, bk, bv)
    if not _res.guard_enabled() or _res.is_tracing(*args):
        return _distributed_merge_impl(ak, av, bk, bv, mesh, axis, exchange)
    idx = _faults.next_index(op)
    if av is None:
        ak, bk = _faults.maybe_nan_lace(op, idx, (ak, bk), (0, 1))
    else:
        ak, av, bk, bv = _faults.maybe_nan_lace(op, idx, (ak, av, bk, bv), (0, 2))

    def run(ex):
        return lambda: _distributed_merge_impl(ak, av, bk, bv, mesh, axis, ex)

    attempts = [("window", run("window"))] if exchange == "window" else []
    attempts.append(("gather", run("gather")))
    attempts.append(("core-resort", lambda: _resort_merge(ak, av, bk, bv)))
    out = _res.guarded_call(
        op, attempts, index=idx, verifier=_res.sorted_verifier(), verify=True
    )
    _record_merge_telemetry(op, ak, bk, mesh, axis, kv=av is not None)
    return out


def distributed_merge(
    a: jax.Array,
    b: jax.Array,
    mesh: Mesh | None = None,
    axis: str = "x",
    exchange: str = "window",
) -> jax.Array:
    """Merge two sorted arrays sharded over a 1-D mesh axis.

    ``exchange="window"`` (default) moves each element once (see the
    module docstring); ``exchange="gather"`` is the all-gather oracle —
    the two are bit-identical.  ``|A|`` and ``|B|`` need not divide evenly
    by the axis size: inputs are sentinel-padded up to the next multiple
    (so each device holds an equal shard), merged length-aware (the pads
    are excluded by count, never by value comparison), and trimmed.

    Eager calls are guarded: a failed or corrupted exchange degrades
    ``window -> gather -> core-resort`` with a :class:`FallbackWarning`
    and health counters (see :mod:`repro.runtime.resilience`).
    """
    keys, _ = _guarded_merge(
        "distributed_merge", a[None, :], None, b[None, :], None, mesh, axis, exchange
    )
    return keys[0]


def distributed_merge_kv(
    ak: jax.Array,
    av: jax.Array,
    bk: jax.Array,
    bv: jax.Array,
    mesh: Mesh | None = None,
    axis: str = "x",
    exchange: str = "window",
) -> Tuple[jax.Array, jax.Array]:
    """Stable key-value merge of two sorted (keys, values) arrays sharded
    over a 1-D mesh axis; values ride the same window exchange as keys.
    Safe for payload keys equal to the sentinel (ranks are length-masked,
    so a shard pad can never shadow a real ``+inf``/``iinfo.max`` key).
    Guarded like :func:`distributed_merge`."""
    if av.shape != ak.shape or bv.shape != bk.shape:
        raise ValueError(
            f"value shapes must match key shapes: keys {ak.shape}/{bk.shape}, "
            f"values {av.shape}/{bv.shape}"
        )
    keys, vals = _guarded_merge(
        "distributed_merge_kv",
        ak[None, :],
        av[None, :],
        bk[None, :],
        bv[None, :],
        mesh,
        axis,
        exchange,
    )
    return keys[0], vals[0]


def distributed_merge_batched(
    a: jax.Array,
    b: jax.Array,
    mesh: Mesh | None = None,
    axis: str = "x",
    exchange: str = "window",
) -> jax.Array:
    """Batched :func:`distributed_merge`: ``(R, na) + (R, nb) -> (R, na+nb)``
    with rows replicated and the merge axis sharded.  Every row has its own
    cut table (the collective bisection carries the batch in its lanes),
    but all rows share the same two all_to_alls.  Guarded like
    :func:`distributed_merge`."""
    keys, _ = _guarded_merge("distributed_merge_batched", a, None, b, None, mesh, axis, exchange)
    return keys


def distributed_merge_kv_batched(
    ak: jax.Array,
    av: jax.Array,
    bk: jax.Array,
    bv: jax.Array,
    mesh: Mesh | None = None,
    axis: str = "x",
    exchange: str = "window",
) -> Tuple[jax.Array, jax.Array]:
    """Batched :func:`distributed_merge_kv` (leading batch axis replicated,
    merge axis sharded) — the vocab-sharded serving building block.
    Guarded like :func:`distributed_merge`."""
    if av.shape != ak.shape or bv.shape != bk.shape:
        raise ValueError(
            f"value shapes must match key shapes: keys {ak.shape}/{bk.shape}, "
            f"values {av.shape}/{bv.shape}"
        )
    return _guarded_merge("distributed_merge_kv_batched", ak, av, bk, bv, mesh, axis, exchange)


def distributed_merge_local(a_shard: jax.Array, b_shard: jax.Array, axis_name: str) -> jax.Array:
    """Per-device all-gather merge body (legacy signature).

    Kept for callers inside their own ``shard_map``: merges
    globally-sharded sorted A and B via one all_gather and returns this
    device's ``N/P`` output slice.  ``|A|`` and ``|B|`` must divide evenly
    by the axis size here; the :func:`distributed_merge` wrapper (which
    also offers the bandwidth-optimal ``exchange="window"`` path) handles
    ragged sizes.
    """
    idx = jax.lax.axis_index(axis_name)
    p = _axis_size(axis_name)
    a = jax.lax.all_gather(a_shard, axis_name, tiled=True)
    b = jax.lax.all_gather(b_shard, axis_name, tiled=True)
    na, nb = a.shape[0], b.shape[0]
    n = na + nb
    seg = n // p
    dtype = jnp.result_type(a, b)
    d0 = idx * seg
    a0 = diagonal_intersections(a, b, d0[None])[0]
    b0 = d0 - a0
    # Window merge: a T-output segment needs at most T from each input
    # (Lemma 16), so slice fixed windows and rank-merge them.
    ap = jnp.concatenate([a.astype(dtype), jnp.full((seg,), max_sentinel(dtype))])
    bp = jnp.concatenate([b.astype(dtype), jnp.full((seg,), max_sentinel(dtype))])
    wa = jax.lax.dynamic_slice(ap, (a0,), (seg,))
    wb = jax.lax.dynamic_slice(bp, (b0,), (seg,))
    valid_a = jnp.clip(na - a0, 0, seg)
    valid_b = jnp.clip(nb - b0, 0, seg)
    ra, rb = _masked_window_ranks(wa, wb, valid_a, valid_b, seg)
    out = jnp.full((seg,), max_sentinel(dtype), dtype)
    out = out.at[ra].set(wa, mode="drop")
    out = out.at[rb].set(wb, mode="drop")
    return out


# ---------------------------------------------------------------------------
# distributed sample sort
# ---------------------------------------------------------------------------

def _pairwise_tree_merge(runs: jax.Array, lens: jax.Array | None = None) -> jax.Array:
    """Deprecated shim: use :func:`repro.core.batched.merge_k` (tournament)
    or :func:`repro.core.batched.merge_k_onepass` (single co-rank pass)
    directly — ``distributed_sort`` now selects between them via its
    ``combine=`` argument, and the distributed merges select their data
    movement via ``exchange=``.  Kept one release for out-of-tree callers.
    """
    return merge_k(runs, lens=lens)


def distributed_sort_local(
    x_shard: jax.Array,
    axis_name: str,
    capacity_factor: float = 2.0,
    local_sort: str = "core",
    combine: str = "onepass",
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Per-device sample sort body.

    Returns ``(sorted_padded, count, overflowed)``: this device's output
    bucket (ascending, sentinel-padded to the fixed capacity), the number
    of valid elements, and a global overflow flag (any element dropped
    anywhere — callers either assert it is false or retry with a larger
    capacity factor).

    One round of data movement: after the local sort and the (tiny)
    splitter all_gather, every element crosses the mesh exactly once in
    the bucket all_to_all; the per-sender bucket counts ride a second,
    scalar-sized all_to_all (each device needs only the counts *destined
    to it* — gathering the full (P, P) count matrix would be a dead
    round-trip).  The received runs are combined locally:
    ``combine="onepass"`` (default) ranks all P ragged runs in a single
    multiway co-rank pass (:func:`repro.core.batched.merge_k_onepass`);
    ``combine="tournament"`` runs the log2(P)-round pairwise tournament —
    on the Pallas ragged kernel (:func:`repro.kernels.ops.merge_k`) when
    ``local_sort="pallas"``, else :func:`repro.core.batched.merge_k`.

    ``local_sort="pallas"`` runs the per-device sort on the bitonic tile
    engine (``repro.kernels.ops.sort``, autotuned ``(tile, leaf)``)
    instead of the pure-JAX rounds — the local sort is the compute-bound
    stage of the sample sort, so it is the one worth a kernel.  The tiny
    splitter-candidate sort (``P*P`` elements) stays on the core path.
    """
    p = _axis_size(axis_name)
    m = x_shard.shape[0]
    cap = int(capacity_factor * m)
    # round capacity up so it is lane-aligned
    cap = -(-cap // 128) * 128
    if local_sort == "pallas":
        from repro.kernels import ops as kops  # deferred: kernels layer optional here

        local = kops.sort(x_shard)
    else:
        local = merge_sort(x_shard)
    # P equispaced local samples as splitter candidates
    samp_idx = (jnp.arange(p) * m) // p
    cands = jax.lax.all_gather(local[samp_idx], axis_name, tiled=True)  # (P*P,)
    cands = merge_sort(cands)
    splitters = cands[jnp.arange(1, p) * p]  # P-1 global splitters
    # Bucket k of the (sorted) local shard is the contiguous run
    # [off[k], off[k+1]); offsets by binary search (merge-path diagonal
    # search against the splitter "array").
    offs = jnp.searchsorted(local, splitters, side="left").astype(jnp.int32)
    offs = jnp.concatenate([jnp.zeros(1, jnp.int32), offs, jnp.full((1,), m, jnp.int32)])
    counts = offs[1:] - offs[:-1]  # (P,)
    overflow_local = jnp.any(counts > cap)
    sentinel = max_sentinel(local.dtype)
    lp = jnp.concatenate([local, jnp.full((cap,), sentinel)])

    def take(k):
        return jax.lax.dynamic_slice(lp, (offs[k],), (cap,))

    send = jax.vmap(take)(jnp.arange(p))  # (P, cap) rows sorted
    # mask out elements beyond each bucket's count
    pos = jnp.arange(cap, dtype=jnp.int32)[None, :]
    send = jnp.where(pos < counts[:, None], send, sentinel)
    recv = jax.lax.all_to_all(send, axis_name, split_axis=0, concat_axis=0, tiled=True)
    recv = recv.reshape(p, cap)  # P sorted runs destined for this device
    # Each sender's bucket count for THIS device, by the same all_to_all
    # (counts[k] on sender j is destined to device k): genuinely ragged
    # valid lengths that thread through the combine so sentinel padding
    # can never pollute the valid prefix, even for int payloads
    # containing ``iinfo.max``.
    recv_lens = jax.lax.all_to_all(
        counts, axis_name, split_axis=0, concat_axis=0, tiled=True
    ).astype(jnp.int32)  # (P,)
    if combine == "onepass":
        out = merge_k_onepass(recv, lens=recv_lens)
    elif combine == "tournament":
        if local_sort == "pallas":
            from repro.kernels import ops as kops

            out = kops.merge_k(recv, lens=recv_lens)
        else:
            out = merge_k(recv, lens=recv_lens)
    else:
        raise ValueError(f"combine must be 'onepass' or 'tournament', got {combine!r}")
    count = jnp.sum(recv_lens)
    overflow = jax.lax.pmax(overflow_local.astype(jnp.int32), axis_name) > 0
    return out, count[None], overflow


@jax.jit
def _resort_sort(x):
    """Terminal sample-sort fallback: single-process total-order sort."""
    _, out = merge_sort_kv(total_order_keys(x), x)
    return out


def _dsort_verifier(n: int):
    """Verifier for the sample sort's ``(sorted_padded, counts, overflow)``.

    Rejects when the global overflow flag is set (elements were dropped),
    when the valid counts do not sum to ``n``, or when the concatenation of
    the per-bucket valid prefixes is not globally nondecreasing in
    total-order space.  Comparisons, not diffs (int64 extremes wrap).
    """

    def check(out):
        s, counts, overflow = out
        if bool(np.asarray(overflow)):
            return "bucket overflow (capacity exceeded)"
        counts_np = np.asarray(counts, dtype=np.int64).reshape(-1)
        total = int(counts_np.sum())
        if total != n:
            return f"valid count {total} != n={n}"
        s_np = np.asarray(s)
        p = counts_np.size
        cap = s_np.shape[0] // p
        rows = s_np.reshape(p, cap)
        valid = np.concatenate([rows[i, : counts_np[i]] for i in range(p)])
        if valid.size >= 2:
            tok = np.asarray(total_order_keys(jnp.asarray(valid))).astype(np.int64)
            if not bool(np.all(tok[:-1] <= tok[1:])):
                return "valid prefixes not globally nondecreasing in total-order space"
        return None

    return check


def distributed_sort(
    x: jax.Array,
    mesh: Mesh | None = None,
    axis: str = "x",
    capacity_factor: float = 2.0,
    local_sort: str = "core",
    combine: str = "onepass",
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Sample-sort a sharded array; see :func:`distributed_sort_local`.

    Eager calls are guarded: attempt 1 runs the requested configuration;
    a launch failure, a corrupted exchange, or a bucket *overflow* (the
    capacity verifier treats ``overflowed=True`` as a failed attempt)
    escalates to ``capacity-2x`` — the same sort at twice the capacity
    factor — and finally to ``core-resort``, a single-process total-order
    sort (counts shape ``(1,)``, capacity ``n``).  Escalation therefore
    **changes the padded output shape**; callers consuming the guarded
    wrapper must slice by the returned counts rather than assume the
    requested capacity.  Under tracing the requested configuration runs
    unguarded (collective-safe).
    """
    if mesh is None:
        mesh = Mesh(jax.devices(), (axis,))

    def run(cf):
        fn = shard_map(
            functools.partial(
                distributed_sort_local,
                axis_name=axis,
                capacity_factor=cf,
                local_sort=local_sort,
                combine=combine,
            ),
            mesh=mesh,
            in_specs=(P(axis),),
            out_specs=(P(axis), P(axis), P()),
            check_vma=False,
        )
        return fn(x)

    if not _res.guard_enabled() or _res.is_tracing(x):
        return run(capacity_factor)
    idx = _faults.next_index("distributed_sort")
    (x,) = _faults.maybe_nan_lace("distributed_sort", idx, (x,), (0,))
    n = int(x.shape[0])
    attempts = [
        ("sample", lambda: run(capacity_factor)),
        ("capacity-2x", lambda: run(2.0 * capacity_factor)),
        (
            "core-resort",
            lambda: (_resort_sort(x), jnp.full((1,), n, jnp.int32), jnp.zeros((), jnp.bool_)),
        ),
    ]
    return _res.guarded_call(
        "distributed_sort", attempts, index=idx, verifier=_dsort_verifier(n), verify=True
    )


# ---------------------------------------------------------------------------
# distributed top-k
# ---------------------------------------------------------------------------

def _butterfly_topk_combine(lk, lv, k, p, axis_name, idx):
    """log2(P)-round butterfly combine of per-device candidate runs.

    ``lk``/``lv`` are this device's ``(R, k)`` ascending flipped-key runs
    and value rows.  Round ``r`` exchanges candidates with the partner
    ``idx ^ 2^r`` (a static ppermute permutation) and keeps the first
    ``k`` of the pairwise merge — the lower-indexed device of each pair
    is the A side, so the tournament bracket (and hence every tie-break)
    is identical to the gather path's adjacent-pairs tree.  After
    ``log2(P)`` rounds every device holds the replicated global top-k,
    having moved ``k * log2(P)`` candidates instead of gather's ``P * k``.
    """
    rounds = p.bit_length() - 1  # p is a power of two
    for r in range(rounds):
        perm = [(i, i ^ (1 << r)) for i in range(p)]
        ok = jax.lax.ppermute(lk, axis_name, perm)
        ov = jax.lax.ppermute(lv, axis_name, perm)
        am_low = (idx & (1 << r)) == 0
        ak = jnp.where(am_low, lk, ok)
        av = jnp.where(am_low, lv, ov)
        bk = jnp.where(am_low, ok, lk)
        bv = jnp.where(am_low, ov, lv)
        mk, mv = merge_kv_batched(ak, av, bk, bv)
        lk, lv = mk[:, :k], mv[:, :k]
    return lk, lv


def _topk_local_body(x_shard, *, k, axis_name, p, exchange, batched):
    """Per-device body shared by the 1-D and batched distributed top-k."""
    idx = jax.lax.axis_index(axis_name)
    xb = x_shard if batched else x_shard[None, :]
    r, m = xb.shape
    idx0 = (idx * m).astype(jnp.int32)
    lv, li = topk_batched(xb, k)
    li = li.astype(jnp.int32) + idx0
    lk = flip_desc(lv)  # ascending keys; exact for ints at iinfo.min
    if exchange == "butterfly":
        gk, gv = _butterfly_topk_combine(lk, li, k, p, axis_name, idx)
    elif exchange == "gather":
        # gather candidate runs; merge on order-flipped keys so ascending
        # merge = descending values.  Pad value slots (pow2 rounds inside
        # merge_k_kv) are excluded by LENGTH, so no pad index can surface.
        keys = jax.lax.all_gather(lk, axis_name, tiled=False)  # (P, R, k)
        idxs = jax.lax.all_gather(li, axis_name, tiled=False)

        def combine_row(kr, vr):  # (P, k) runs for one batch row
            mk, mv = merge_k_kv(kr, vr)
            return mk[:k], mv[:k]

        gk, gv = jax.vmap(combine_row, in_axes=1, out_axes=0)(keys, idxs)
    else:
        raise ValueError(f"exchange must be 'butterfly' or 'gather', got {exchange!r}")
    vals = flip_desc(gk)
    return (vals, gv) if batched else (vals[0], gv[0])


def _resolve_topk_exchange(exchange: str, p: int) -> str:
    if exchange == "auto":
        return "butterfly" if p >= 2 and (p & (p - 1)) == 0 else "gather"
    if exchange == "butterfly" and (p < 2 or (p & (p - 1)) != 0):
        raise ValueError(f"butterfly combine needs a power-of-two axis, got P={p}")
    return exchange


def distributed_topk_local(
    x_shard: jax.Array, k: int, axis_name: str
) -> Tuple[jax.Array, jax.Array]:
    """Per-device body (legacy signature): global (values, indices) top-k of
    a sharded vector via the gather combine.  Indices are global; the
    result is replicated across the axis.  The :func:`distributed_topk`
    wrapper additionally offers the bandwidth-lean butterfly combine."""
    p = _axis_size(axis_name)
    return _topk_local_body(
        x_shard, k=k, axis_name=axis_name, p=p, exchange="gather", batched=False
    )


def distributed_topk(
    x: jax.Array,
    k: int,
    mesh: Mesh | None = None,
    axis: str = "x",
    exchange: str = "auto",
) -> Tuple[jax.Array, jax.Array]:
    """Global (values, indices) top-k of a sharded vector, replicated.

    ``exchange="auto"`` picks the log2(P)-round butterfly combine
    (``k * log2(P)`` candidates moved per device) when the axis size is a
    power of two, else the all_gather tree (``P * k`` per device).  Both
    are bit-identical — same bracket, same tie-breaks.

    Eager calls are guarded: a failed butterfly degrades to ``gather``,
    and both degrade to ``core-topk`` — the single-process batched
    merge-path top-k, which is NaN-exact via the total-order key route.
    """
    if mesh is None:
        mesh = Mesh(jax.devices(), (axis,))
    p = mesh.shape[axis]
    exchange = _resolve_topk_exchange(exchange, p)

    def run(ex):
        fn = shard_map(
            functools.partial(
                _topk_local_body, k=k, axis_name=axis, p=p, exchange=ex, batched=False
            ),
            mesh=mesh,
            in_specs=(P(axis),),
            out_specs=(P(), P()),
            check_vma=False,
        )
        return fn(x)

    if not _res.guard_enabled() or _res.is_tracing(x):
        return run(exchange)
    idx = _faults.next_index("distributed_topk")
    (x,) = _faults.maybe_nan_lace("distributed_topk", idx, (x,), (0,))

    def core():
        v, i = topk_batched(x[None, :], k)
        return v[0], i[0].astype(jnp.int32)

    attempts = [(exchange, lambda: run(exchange))]
    if exchange != "gather":
        attempts.append(("gather", lambda: run("gather")))
    attempts.append(("core-topk", core))
    return _res.guarded_call(
        "distributed_topk", attempts, index=idx, verifier=_res.topk_verifier(), verify=True
    )


def distributed_topk_batched(
    x: jax.Array,
    k: int,
    mesh: Mesh | None = None,
    axis: str = "x",
    exchange: str = "auto",
) -> Tuple[jax.Array, jax.Array]:
    """Row-wise global top-k of ``(R, V)`` logits sharded over the vocab.

    The vocab-sharded serving primitive: every row's shard-local top-k
    candidates ride one combine (butterfly or gather, like
    :func:`distributed_topk`), and the replicated ``(R, k)`` result feeds
    the samplers directly (``repro.serving.sampler`` ``backend=
    "distributed"``).  Indices are global vocab ids; tie-breaking matches
    ``jax.lax.top_k`` (smallest index first).  Guarded like
    :func:`distributed_topk`.
    """
    if mesh is None:
        mesh = Mesh(jax.devices(), (axis,))
    p = mesh.shape[axis]
    exchange = _resolve_topk_exchange(exchange, p)

    def run(ex):
        fn = shard_map(
            functools.partial(
                _topk_local_body, k=k, axis_name=axis, p=p, exchange=ex, batched=True
            ),
            mesh=mesh,
            in_specs=(P(None, axis),),
            out_specs=(P(), P()),
            check_vma=False,
        )
        return fn(x)

    if not _res.guard_enabled() or _res.is_tracing(x):
        return run(exchange)
    idx = _faults.next_index("distributed_topk_batched")
    (x,) = _faults.maybe_nan_lace("distributed_topk_batched", idx, (x,), (0,))

    def core():
        v, i = topk_batched(x, k)
        return v, i.astype(jnp.int32)

    attempts = [(exchange, lambda: run(exchange))]
    if exchange != "gather":
        attempts.append(("gather", lambda: run("gather")))
    attempts.append(("core-topk", core))
    return _res.guarded_call(
        "distributed_topk_batched",
        attempts,
        index=idx,
        verifier=_res.topk_verifier(),
        verify=True,
    )
