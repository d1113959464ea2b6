"""Guarded public wrappers around the Merge Path Pallas kernels.

``merge`` / ``merge_kv`` / ``sort`` / ``sort_kv`` dispatch to the Pallas
SPM kernel when the problem is big enough to tile, and to the pure-JAX
core otherwise.  ``merge_batched`` / ``merge_kv_batched`` are the batched
(leading batch axis) forms on the 2-D ``(batch, tile)`` grid kernel; the
sorts (1-D and ``sort_batched`` / ``sort_kv_batched``) run their wide
rounds on the **flat round kernel** — one launch per round, with the
pow2 + sentinel padding hoisted out of the round loop (built once per
sort; see ``repro.kernels.merge_path.sort_round_pallas``).

**Guarded dispatch**: every public entry point routes through
:func:`repro.runtime.resilience.guarded_call`.  On an eager call (no JAX
tracers among the operands) the wrapper walks the fallback chain
``pallas-<engine> -> pallas-matrix -> core [-> core-resort]``: preflight
validates the call against the ``@kernel_contract`` registry (tile
legality, the A005 VMEM model, length bounds), launch failures are caught
and degrade to the next edge, and — when verification is active (a fault
plan is injected, or ``REPRO_GUARD_VERIFY=1``) — each attempt's output is
checked for total-order sortedness before it is accepted.  The terminal
``core-resort`` edge of the merges *re-sorts* the concatenated inputs
(stable sort == stable A-priority merge), which repairs even a violated
sorted-input precondition, e.g. NaN-laced keys.  Under tracing
(``jit`` / ``grad`` / ``vmap`` / ``eval_shape``) the wrapper dispatches
the primary attempt directly — Python cannot branch on device failures
inside a trace.  See ``docs/robustness.md``.

**Tile/leaf selection**: every wrapper takes ``tile=None`` / ``leaf=None``
and resolves them through :func:`repro.kernels.tune.pick` (a micro-bench
table), so consumers that don't care get measured defaults and consumers
that do (serving sampler, MoE dispatch, distributed sort) can pass their
own.  ``leaf`` is resolved and checked but no longer shapes the kernel.

**Interpret default**: ``interpret=None`` (the default everywhere)
resolves through :func:`default_interpret` when the call is made:
compiled kernels on a TPU backend, the Pallas interpreter on any other.
A non-empty ``REPRO_PALLAS_INTERPRET`` overrides the backend rule
(``0`` compiled, ``1`` interpreted) — tests use it, nothing else needs it.

**NaN keys**: the float sort / top-k paths compare
:func:`repro.core.merge_path.total_order_keys` of the keys (same-width
int keys, NaN last) instead of the raw floats, so NaN keys order
deterministically and identically on every engine.  For NaN-free input
the int key order coincides with the float order — results are
bit-identical to the previous raw-float comparisons.

**Gradients**: the sorts and top-ks here are *permutations* of their
inputs, and Siebert & Träff's stable co-rank partition guarantees the
permutation is well-defined even under duplicate keys — so every sort
defines a ``jax.custom_vjp`` whose forward saves the gather indices (the
stable argsort, computed by the same kernel with an iota payload) and
whose backward is ONE inverse-gather scatter of the cotangents.  That
makes the backward exact in any dtype (each output cotangent lands on
exactly one input slot, no floating-point accumulation), bit-identical
to ``jax.grad`` of the pure-JAX core route, and shields the Pallas
internals from tracing AD.  Ragged / sentinel-masked top-k slots
(``index == -1``) contribute exactly zero.  Integer inputs take the
plain kernel path (no tangents exist for them).
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import dtypes as _jdtypes

from repro.analysis.registry import kernel_contract
from repro.core import batched as _bat
from repro.core import merge_path as _mp
from repro.runtime import faults as _faults
from repro.runtime import resilience as _res
from . import merge_path as _kern
from . import tune as _tune

# single source of truth for the interpret default (compiled on a TPU
# backend, interpreted elsewhere, ``REPRO_PALLAS_INTERPRET`` overrides) —
# the kernel wrappers, tune.autotune and the benchmarks all resolve
# through it (re-exported here because ops is the public dispatch surface)
default_interpret = _kern.default_interpret
_interp = _kern._interp


def _resolve(n: int, dtype, tile: Optional[int], leaf: Optional[int]) -> Tuple[int, int]:
    """Fill unspecified tile/leaf from the autotune table."""
    t, s = _tune.pick(n, dtype)
    tile = t if tile is None else tile
    leaf = s if leaf is None else leaf
    return tile, max(1, min(leaf, tile))


def _sort_tile(n: int, dtype, tile: Optional[int], leaf: Optional[int]) -> Tuple[int, int]:
    """Tile/leaf resolution for the sorts: the flat rounds need
    ``tile | 2 * width`` with pow2 widths, so an explicitly passed tile
    must be a power of two — reject it loudly rather than silently
    running a different tile than the caller measured.  (The autotune
    table only ever emits powers of two.)"""
    tile, leaf = _resolve(n, dtype, tile, leaf)
    if tile & (tile - 1):
        raise ValueError(
            f"sort tile must be a power of two (flat sort rounds require "
            f"tile | 2 * width), got {tile}"
        )
    return tile, leaf


_JIT = functools.partial(
    jax.jit, static_argnames=("tile", "leaf", "engine", "interpret")
)
_JITK = functools.partial(
    jax.jit, static_argnames=("k", "tile", "leaf", "engine", "interpret")
)


# ---------------------------------------------------------------------------
# guarded dispatch plumbing
# ---------------------------------------------------------------------------


def _meta(n, dtype, tile=None, leaf=None, batch=1, ragged=False) -> dict:
    """Concrete call geometry for preflight (see resilience.preflight)."""
    return {
        "n": int(n),
        "batch": int(batch),
        "dtype": str(jnp.dtype(dtype)),
        "tile": None if tile is None else int(tile),
        "leaf": None if leaf is None else int(leaf),
        "ragged": bool(ragged),
    }


def _guard(
    op: str,
    args: tuple,
    *,
    engine: str,
    interpret: Optional[bool],
    launch: Callable,
    core: Callable,
    resort: Optional[Callable] = None,
    keys: Sequence[int] = (),
    meta: Optional[dict] = None,
    verifier: Optional[Callable] = None,
):
    """Route one public-op call through the guarded dispatch chain.

    ``launch(args, engine, interp)`` runs the jitted kernel body;
    ``core`` is the pure-JAX twin and ``resort`` (merges only) the
    precondition-repairing re-sort of the concatenated inputs.  ``keys``
    lists the positions of key operands in ``args`` for NaN lacing.
    Bypasses (primary attempt only) under tracing or ``REPRO_GUARD=0``.
    """
    interp = _interp(interpret)
    if not _res.guard_enabled() or _res.is_tracing(*args):
        return launch(args, engine, interp)
    idx = _faults.next_index(op)
    args = _faults.maybe_nan_lace(op, idx, args, keys)
    attempts = [(f"pallas-{engine}", lambda: launch(args, engine, interp))]
    if engine != "matrix":
        attempts.append(("pallas-matrix", lambda: launch(args, "matrix", interp)))
    attempts.append(("core", lambda: core(*args)))
    if resort is not None:
        attempts.append(("core-resort", lambda: resort(*args)))
    return _res.guarded_call(op, attempts, index=idx, meta=meta, verifier=verifier)


# core twins, jitted once at module level (the chain's oracle edges)
_core_merge = jax.jit(_mp.merge)
_core_merge_kv = jax.jit(_mp.merge_kv)
_core_merge_batched = jax.jit(_bat.merge_batched)
_core_merge_kv_batched = jax.jit(_bat.merge_kv_batched)
_core_merge_batched_ragged = jax.jit(_bat.merge_batched_ragged)
_core_merge_kv_batched_ragged = jax.jit(_bat.merge_kv_batched_ragged)
_core_sort = jax.jit(_mp.merge_sort)
_core_sort_kv = jax.jit(_mp.merge_sort_kv)
_core_sort_batched = jax.jit(_bat.merge_sort_batched)
_core_sort_kv_batched = jax.jit(_bat.merge_sort_kv_batched)
_core_topk_batched = jax.jit(_bat.topk_batched, static_argnums=(1,))
_core_topk_batched_ragged = jax.jit(_bat.topk_batched_ragged, static_argnums=(1,))
_core_merge_k = jax.jit(_bat.merge_k)


# re-sort fallbacks: a stable sort of the row-concatenation [a; b] IS the
# stable A-priority merge (position order gives A priority), and — unlike
# every merge route — needs no sorted-input precondition, so it even
# repairs NaN-laced keys (total-order: NaN sorts last, deterministically).


@jax.jit
def _resort_merge(a, b):
    dt = jnp.result_type(a, b)
    cat = jnp.concatenate([a.astype(dt), b.astype(dt)])
    _, out = _mp.merge_sort_kv(_mp.total_order_keys(cat), cat)
    return out


@jax.jit
def _resort_merge_kv(ak, av, bk, bv):
    kd = jnp.result_type(ak, bk)
    vd = jnp.result_type(av, bv)
    k = jnp.concatenate([ak.astype(kd), bk.astype(kd)])
    v = jnp.concatenate([av.astype(vd), bv.astype(vd)])
    _, perm = _mp.merge_sort_kv(
        _mp.total_order_keys(k), jnp.arange(k.shape[0], dtype=jnp.int32)
    )
    return jnp.take(k, perm), jnp.take(v, perm)


@jax.jit
def _resort_merge_batched(a, b):
    dt = jnp.result_type(a, b)
    cat = jnp.concatenate([a.astype(dt), b.astype(dt)], axis=1)
    _, out = _bat.merge_sort_kv_batched(_mp.total_order_keys(cat), cat)
    return out


@jax.jit
def _resort_merge_kv_batched(ak, av, bk, bv):
    kd = jnp.result_type(ak, bk)
    vd = jnp.result_type(av, bv)
    k = jnp.concatenate([ak.astype(kd), bk.astype(kd)], axis=1)
    v = jnp.concatenate([av.astype(vd), bv.astype(vd)], axis=1)
    _, perm = _bat.merge_sort_kv_batched(_mp.total_order_keys(k), _iota_like(k))
    rows = jnp.arange(k.shape[0], dtype=jnp.int32)[:, None]
    return k[rows, perm], v[rows, perm]


def _ragged_valid(bsz: int, na: int, nb: int, a_lens, b_lens):
    """(valid mask over the concat row, merged lengths) for ragged resorts."""
    col = jnp.arange(na + nb, dtype=jnp.int32)[None, :]
    valid = jnp.where(col < na, col < a_lens[:, None], (col - na) < b_lens[:, None])
    return col, valid, a_lens + b_lens


@jax.jit
def _resort_merge_batched_ragged(a, b, a_lens, b_lens):
    dt = jnp.result_type(a, b)
    bsz, na = a.shape
    nb = b.shape[1]
    a_lens = _bat._as_lens(a_lens, bsz, na)
    b_lens = _bat._as_lens(b_lens, bsz, nb)
    cat = jnp.concatenate([a.astype(dt), b.astype(dt)], axis=1)
    col, valid, merged = _ragged_valid(bsz, na, nb, a_lens, b_lens)
    # mask pads in int total-order key space: the int sentinel is strictly
    # above every real key (incl. NaN / +inf), so pads can never interleave
    tok = _mp.total_order_keys(cat)
    tok = jnp.where(valid, tok, _mp.max_sentinel(tok.dtype))
    _, perm = _bat.merge_sort_kv_batched(tok, _iota_like(cat))
    rows = jnp.arange(bsz, dtype=jnp.int32)[:, None]
    out = cat[rows, perm]
    return jnp.where(col < merged[:, None], out, _mp.max_sentinel(dt))


@jax.jit
def _resort_merge_kv_batched_ragged(ak, av, bk, bv, a_lens, b_lens):
    kd = jnp.result_type(ak, bk)
    vd = jnp.result_type(av, bv)
    bsz, na = ak.shape
    nb = bk.shape[1]
    a_lens = _bat._as_lens(a_lens, bsz, na)
    b_lens = _bat._as_lens(b_lens, bsz, nb)
    k = jnp.concatenate([ak.astype(kd), bk.astype(kd)], axis=1)
    v = jnp.concatenate([av.astype(vd), bv.astype(vd)], axis=1)
    col, valid, merged = _ragged_valid(bsz, na, nb, a_lens, b_lens)
    tok = _mp.total_order_keys(k)
    tok = jnp.where(valid, tok, _mp.max_sentinel(tok.dtype))
    _, perm = _bat.merge_sort_kv_batched(tok, _iota_like(k))
    rows = jnp.arange(bsz, dtype=jnp.int32)[:, None]
    in_row = col < merged[:, None]
    ks = jnp.where(in_row, k[rows, perm], _mp.max_sentinel(kd))
    vs = jnp.where(in_row, v[rows, perm], jnp.zeros((), vd))
    return ks, vs


def _ragged_lens_np(a_lens, b_lens, bsz: int, na: int, nb: int) -> np.ndarray:
    """Host merged lengths for the ragged verifiers (guard-active path only)."""
    la = np.clip(np.asarray(a_lens, dtype=np.int64).reshape(-1), 0, na)
    lb = np.clip(np.asarray(b_lens, dtype=np.int64).reshape(-1), 0, nb)
    return la + lb


# ---------------------------------------------------------------------------
# merges
# ---------------------------------------------------------------------------


@_JIT
def _merge_launch(a, b, *, tile, leaf, engine, interpret):
    n = a.shape[0] + b.shape[0]
    if n <= tile:
        return _mp.merge(a, b)
    return _kern.merge_pallas(
        a, b, tile=tile, leaf=leaf, engine=engine, interpret=interpret
    )


@kernel_contract(
    kind="merge",
    tie_safe="keys-only: a window pad tied with a real sentinel-valued key "
             "is bit-identical to it, so any rank assignment among the tie "
             "yields the same output sequence",
)
def merge(
    a: jax.Array,
    b: jax.Array,
    *,
    tile: Optional[int] = None,
    leaf: Optional[int] = None,
    engine: str = _kern.DEFAULT_ENGINE,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Stable merge of two sorted 1-D arrays (Pallas SPM kernel)."""
    n = a.shape[0] + b.shape[0]
    tile, leaf = _resolve(n, jnp.result_type(a, b), tile, leaf)
    return _guard(
        "merge", (a, b), engine=engine, interpret=interpret,
        launch=lambda ar, eng, itp: _merge_launch(
            ar[0], ar[1], tile=tile, leaf=leaf, engine=eng, interpret=itp
        ),
        core=_core_merge, resort=_resort_merge, keys=(0, 1),
        meta=_meta(n, jnp.result_type(a, b), tile, leaf),
        verifier=_res.sorted_verifier(),
    )


@_JIT
def _merge_kv_launch(ak, av, bk, bv, *, tile, leaf, engine, interpret):
    n = ak.shape[0] + bk.shape[0]
    if n <= tile:
        return _mp.merge_kv(ak, av, bk, bv)
    return _kern.merge_kv_pallas(
        ak, av, bk, bv, tile=tile, leaf=leaf, engine=engine, interpret=interpret
    )


@kernel_contract(kind="merge", carries_values=True, masked_ranks=True)
def merge_kv(
    ak: jax.Array,
    av: jax.Array,
    bk: jax.Array,
    bv: jax.Array,
    *,
    tile: Optional[int] = None,
    leaf: Optional[int] = None,
    engine: str = _kern.DEFAULT_ENGINE,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Stable key-value merge (Pallas SPM kernel)."""
    n = ak.shape[0] + bk.shape[0]
    tile, leaf = _resolve(n, jnp.result_type(ak, bk), tile, leaf)
    return _guard(
        "merge_kv", (ak, av, bk, bv), engine=engine, interpret=interpret,
        launch=lambda ar, eng, itp: _merge_kv_launch(
            *ar, tile=tile, leaf=leaf, engine=eng, interpret=itp
        ),
        core=_core_merge_kv, resort=_resort_merge_kv, keys=(0, 2),
        meta=_meta(n, jnp.result_type(ak, bk), tile, leaf),
        verifier=_res.sorted_verifier(),
    )


@_JIT
def _merge_batched_launch(a, b, *, tile, leaf, engine, interpret):
    n = a.shape[1] + b.shape[1]
    if n <= tile:
        return _bat.merge_batched(a, b)
    return _kern.merge_batched_pallas(
        a, b, tile=tile, leaf=leaf, engine=engine, interpret=interpret
    )


@kernel_contract(
    kind="merge",
    batched=True,
    tie_safe="keys-only: sentinel-tied pads are value-identical to the real "
             "key, so the merged row is unchanged whichever wins the tie",
)
def merge_batched(
    a: jax.Array,
    b: jax.Array,
    *,
    tile: Optional[int] = None,
    leaf: Optional[int] = None,
    engine: str = _kern.DEFAULT_ENGINE,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Stable merge of ``B`` row pairs: ``(B, na) + (B, nb) -> (B, na+nb)``.

    One 2-D-grid kernel launch for the whole batch when rows are wide
    enough to tile; the fused pure-JAX batched merge otherwise.
    """
    n = a.shape[1] + b.shape[1]
    tile, leaf = _resolve(n, jnp.result_type(a, b), tile, leaf)
    return _guard(
        "merge_batched", (a, b), engine=engine, interpret=interpret,
        launch=lambda ar, eng, itp: _merge_batched_launch(
            ar[0], ar[1], tile=tile, leaf=leaf, engine=eng, interpret=itp
        ),
        core=_core_merge_batched, resort=_resort_merge_batched, keys=(0, 1),
        meta=_meta(n, jnp.result_type(a, b), tile, leaf, batch=a.shape[0]),
        verifier=_res.sorted_verifier(),
    )


@_JIT
def _merge_kv_batched_launch(ak, av, bk, bv, *, tile, leaf, engine, interpret):
    n = ak.shape[1] + bk.shape[1]
    if n <= tile:
        return _bat.merge_kv_batched(ak, av, bk, bv)
    return _kern.merge_kv_batched_pallas(
        ak, av, bk, bv, tile=tile, leaf=leaf, engine=engine, interpret=interpret
    )


@kernel_contract(kind="merge", batched=True, carries_values=True, masked_ranks=True)
def merge_kv_batched(
    ak: jax.Array,
    av: jax.Array,
    bk: jax.Array,
    bv: jax.Array,
    *,
    tile: Optional[int] = None,
    leaf: Optional[int] = None,
    engine: str = _kern.DEFAULT_ENGINE,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Stable batched key-value merge (2-D-grid Pallas kernel when wide)."""
    n = ak.shape[1] + bk.shape[1]
    tile, leaf = _resolve(n, jnp.result_type(ak, bk), tile, leaf)
    return _guard(
        "merge_kv_batched", (ak, av, bk, bv), engine=engine, interpret=interpret,
        launch=lambda ar, eng, itp: _merge_kv_batched_launch(
            *ar, tile=tile, leaf=leaf, engine=eng, interpret=itp
        ),
        core=_core_merge_kv_batched, resort=_resort_merge_kv_batched, keys=(0, 2),
        meta=_meta(n, jnp.result_type(ak, bk), tile, leaf, batch=ak.shape[0]),
        verifier=_res.sorted_verifier(),
    )


@_JIT
def _merge_batched_ragged_launch(a, b, a_lens, b_lens, *, tile, leaf, engine, interpret):
    n = a.shape[1] + b.shape[1]
    if n <= tile:
        return _bat.merge_batched_ragged(a, b, a_lens, b_lens)
    return _kern.merge_batched_ragged_pallas(
        a, b, a_lens, b_lens, tile=tile, leaf=leaf, engine=engine,
        interpret=interpret,
    )


@kernel_contract(kind="merge", batched=True, ragged=True, masked_ranks=True)
def merge_batched_ragged(
    a: jax.Array,
    b: jax.Array,
    a_lens: jax.Array,
    b_lens: jax.Array,
    *,
    tile: Optional[int] = None,
    leaf: Optional[int] = None,
    engine: str = _kern.DEFAULT_ENGINE,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Ragged batched merge: per-row valid lengths, sentinel-padded tails.

    Dispatches like :func:`merge_batched`: the fused pure-JAX ragged merge
    for narrow rows, the 2-D-grid ragged kernel (lengths via scalar
    prefetch) when rows are wide enough to tile.
    """
    bsz, na = a.shape
    nb = b.shape[1]
    n = na + nb
    tile, leaf = _resolve(n, jnp.result_type(a, b), tile, leaf)
    tracing = _res.is_tracing(a, b, a_lens, b_lens)
    return _guard(
        "merge_batched_ragged", (a, b, a_lens, b_lens),
        engine=engine, interpret=interpret,
        launch=lambda ar, eng, itp: _merge_batched_ragged_launch(
            *ar, tile=tile, leaf=leaf, engine=eng, interpret=itp
        ),
        core=_core_merge_batched_ragged, resort=_resort_merge_batched_ragged,
        keys=(0, 1),
        meta=_meta(n, jnp.result_type(a, b), tile, leaf, batch=bsz, ragged=True),
        verifier=None if tracing else _res.sorted_verifier(
            _ragged_lens_np(a_lens, b_lens, bsz, na, nb)
        ),
    )


@_JIT
def _merge_kv_batched_ragged_launch(
    ak, av, bk, bv, a_lens, b_lens, *, tile, leaf, engine, interpret
):
    n = ak.shape[1] + bk.shape[1]
    if n <= tile:
        return _bat.merge_kv_batched_ragged(ak, av, bk, bv, a_lens, b_lens)
    return _kern.merge_kv_batched_ragged_pallas(
        ak, av, bk, bv, a_lens, b_lens, tile=tile, leaf=leaf, engine=engine,
        interpret=interpret,
    )


@kernel_contract(
    kind="merge", batched=True, ragged=True, carries_values=True, masked_ranks=True
)
def merge_kv_batched_ragged(
    ak: jax.Array,
    av: jax.Array,
    bk: jax.Array,
    bv: jax.Array,
    a_lens: jax.Array,
    b_lens: jax.Array,
    *,
    tile: Optional[int] = None,
    leaf: Optional[int] = None,
    engine: str = _kern.DEFAULT_ENGINE,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Ragged batched key-value merge (2-D-grid ragged kernel when wide)."""
    bsz, na = ak.shape
    nb = bk.shape[1]
    n = na + nb
    tile, leaf = _resolve(n, jnp.result_type(ak, bk), tile, leaf)
    tracing = _res.is_tracing(ak, av, bk, bv, a_lens, b_lens)
    return _guard(
        "merge_kv_batched_ragged", (ak, av, bk, bv, a_lens, b_lens),
        engine=engine, interpret=interpret,
        launch=lambda ar, eng, itp: _merge_kv_batched_ragged_launch(
            *ar, tile=tile, leaf=leaf, engine=eng, interpret=itp
        ),
        core=_core_merge_kv_batched_ragged,
        resort=_resort_merge_kv_batched_ragged, keys=(0, 2),
        meta=_meta(n, jnp.result_type(ak, bk), tile, leaf, batch=bsz, ragged=True),
        verifier=None if tracing else _res.sorted_verifier(
            _ragged_lens_np(a_lens, b_lens, bsz, na, nb)
        ),
    )


# ---------------------------------------------------------------------------
# Sorts: flat rounds, padding hoisted out of the loop
# ---------------------------------------------------------------------------


def _sort_rounds(flat: jax.Array, m: int, tile: int, leaf: int, engine: str, interpret: bool) -> jax.Array:
    """Bottom-up merge-sort rounds over a flat ``(B * m,)`` buffer
    (``m`` = per-row pow2 width; pairs never straddle a row because ``m``
    is a multiple of every round width).

    The first ``log2(tile)`` rounds — runs narrower than one tile — are
    one stable sort of every ``tile``-wide block (a stable sort of a block
    IS its bottom-up merge, so results are bit-identical); merged
    round by round in XLA they needed GiBs of temporaries at 2^24 keys.
    Wide rounds are flat-kernel launches sharing ONE sentinel tail
    appended here, once.
    """
    width = min(tile, m)
    flat = jax.lax.sort(flat.reshape(-1, width), dimension=1, is_stable=True).reshape(-1)
    if width < m:
        total = flat.shape[0]
        xf = jnp.concatenate(
            [flat, jnp.full((_kern.sort_tail(tile),), _mp.max_sentinel(flat.dtype), flat.dtype)]
        )
        while width < m:
            xf = _kern.sort_round_pallas(
                xf, width, tile=tile, leaf=leaf, engine=engine, interpret=interpret
            )
            width *= 2
        flat = xf[:total]
    return flat


def _sort_rounds_kv(
    kflat: jax.Array, vflat: jax.Array, m: int, tile: int, leaf: int, engine: str, interpret: bool
) -> Tuple[jax.Array, jax.Array]:
    """Key-value :func:`_sort_rounds` (values' hoisted tail is zeros)."""
    width = min(tile, m)
    kflat, vflat = jax.lax.sort(
        (kflat.reshape(-1, width), vflat.reshape(-1, width)), dimension=1, is_stable=True, num_keys=1
    )
    kflat, vflat = kflat.reshape(-1), vflat.reshape(-1)
    if width < m:
        total = kflat.shape[0]
        kf = jnp.concatenate(
            [kflat, jnp.full((_kern.sort_tail(tile),), _mp.max_sentinel(kflat.dtype), kflat.dtype)]
        )
        vf = jnp.concatenate([vflat, jnp.zeros((_kern.sort_tail(tile),), vflat.dtype)])
        while width < m:
            kf, vf = _kern.sort_round_kv_pallas(
                kf, vf, width, tile=tile, leaf=leaf, engine=engine, interpret=interpret
            )
            width *= 2
        kflat, vflat = kf[:total], vf[:total]
    return kflat, vflat


# --- raw (non-differentiable) sort bodies -----------------------------------


def _keyed(k: jax.Array) -> jax.Array:
    """Keys the merge network actually compares: int total-order keys for
    floats (NaN-deterministic), the raw keys otherwise."""
    return _mp.total_order_keys(k) if _inexact(k.dtype) else k


def _sort_impl(x, n, tile, leaf, engine, interp):
    if _inexact(x.dtype):
        # kv-carry: compare int total-order keys, ride the floats as values
        _, out = _sort_kv_impl(
            _mp.total_order_keys(x), x, n, tile, leaf, engine, interp
        )
        return out
    xp = _mp._pad_pow2(x, _mp.max_sentinel(x.dtype))
    return _sort_rounds(xp, xp.shape[0], tile, leaf, engine, interp)[:n]


def _sort_kv_impl(keys, values, n, tile, leaf, engine, interp):
    kp = _mp._pad_pow2(keys, _mp.max_sentinel(keys.dtype))
    vp = _mp._pad_pow2(values, jnp.zeros((), values.dtype))
    ks, vs = _sort_rounds_kv(kp, vp, kp.shape[0], tile, leaf, engine, interp)
    return ks[:n], vs[:n]


def _sort_batched_impl(x, n, tile, leaf, engine, interp):
    bsz = x.shape[0]
    if _inexact(x.dtype):
        _, out = _sort_kv_batched_impl(
            _mp.total_order_keys(x), x, n, tile, leaf, engine, interp
        )
        return out
    xp = _bat._pad_rows_pow2(x, _mp.max_sentinel(x.dtype))
    m = xp.shape[1]
    out = _sort_rounds(xp.reshape(-1), m, tile, leaf, engine, interp)
    return out.reshape(bsz, m)[:, :n]


def _sort_kv_batched_impl(keys, values, n, tile, leaf, engine, interp):
    bsz = keys.shape[0]
    kp = _bat._pad_rows_pow2(keys, _mp.max_sentinel(keys.dtype))
    vp = _bat._pad_rows_pow2(values, jnp.zeros((), values.dtype))
    m = kp.shape[1]
    ks, vs = _sort_rounds_kv(
        kp.reshape(-1), vp.reshape(-1), m, tile, leaf, engine, interp
    )
    return ks.reshape(bsz, m)[:, :n], vs.reshape(bsz, m)[:, :n]


# --- permutation-transpose VJP glue -----------------------------------------


def _inexact(dtype) -> bool:
    return jnp.issubdtype(jnp.dtype(dtype), jnp.inexact)


def _float0(shape):
    """float0 cotangent zeros — what custom_vjp requires for int primals."""
    return np.zeros(shape, _jdtypes.float0)


def _iota_like(x) -> jax.Array:
    """Row-index payload whose sorted order IS the stable argsort."""
    if x.ndim == 1:
        return jnp.arange(x.shape[0], dtype=jnp.int32)
    return jnp.broadcast_to(
        jnp.arange(x.shape[-1], dtype=jnp.int32)[None, :], x.shape
    )


def _scatter_inverse(perm: jax.Array, ct: jax.Array) -> jax.Array:
    """Permutation transpose: route output cotangents back to input slots.

    ``perm`` is a (batched) permutation — each source index appears
    exactly once — so the scatter is an exact inverse gather in any
    dtype (no accumulation happens).
    """
    if perm.ndim == 1:
        return jnp.zeros(perm.shape, ct.dtype).at[perm].set(ct)
    rows = jnp.arange(perm.shape[0], dtype=jnp.int32)[:, None]
    return jnp.zeros(perm.shape, ct.dtype).at[rows, perm].set(ct)


# --- jitted sort bodies (the guarded wrappers' primary attempts) ------------


@_JIT
def _sort(x, *, tile, leaf, engine, interpret):
    n = x.shape[0]
    if not _inexact(x.dtype):
        return _sort_impl(x, n, tile, leaf, engine, interpret)

    @jax.custom_vjp
    def f(xx):
        return _sort_impl(xx, n, tile, leaf, engine, interpret)

    def fwd(xx):
        _, perm = _sort_kv_impl(
            _mp.total_order_keys(xx), _iota_like(xx), n, tile, leaf, engine, interpret
        )
        # stability makes xx[perm] bit-identical to the kernel's key output
        return jnp.take(xx, perm), perm

    def bwd(perm, dy):
        return (_scatter_inverse(perm, dy),)

    f.defvjp(fwd, bwd)
    return f(x)


@_JIT
def _sort_kv(keys, values, *, tile, leaf, engine, interpret):
    n = keys.shape[0]
    kx, vx = _inexact(keys.dtype), _inexact(values.dtype)
    if not (kx or vx):
        return _sort_kv_impl(keys, values, n, tile, leaf, engine, interpret)

    @jax.custom_vjp
    def f(k, v):
        if kx:
            # float keys: permute through the int total-order keys and
            # gather the original bit patterns (NaN-deterministic)
            _, perm = _sort_kv_impl(
                _keyed(k), _iota_like(k), n, tile, leaf, engine, interpret
            )
            return jnp.take(k, perm), jnp.take(v, perm)
        return _sort_kv_impl(k, v, n, tile, leaf, engine, interpret)

    def fwd(k, v):
        _, perm = _sort_kv_impl(
            _keyed(k), _iota_like(k), n, tile, leaf, engine, interpret
        )
        # stability makes the perm-gathers bit-identical to the kernel output
        return (jnp.take(k, perm), jnp.take(v, perm)), perm

    def bwd(perm, cts):
        dks, dvs = cts
        dk = _scatter_inverse(perm, dks) if kx else _float0((n,))
        dv = _scatter_inverse(perm, dvs) if vx else _float0((n,))
        return dk, dv

    f.defvjp(fwd, bwd)
    return f(keys, values)


@_JIT
def _sort_batched(x, *, tile, leaf, engine, interpret):
    bsz, n = x.shape
    if not _inexact(x.dtype):
        return _sort_batched_impl(x, n, tile, leaf, engine, interpret)

    @jax.custom_vjp
    def f(xx):
        return _sort_batched_impl(xx, n, tile, leaf, engine, interpret)

    def fwd(xx):
        _, perm = _sort_kv_batched_impl(
            _mp.total_order_keys(xx), _iota_like(xx), n, tile, leaf, engine, interpret
        )
        rows = jnp.arange(bsz, dtype=jnp.int32)[:, None]
        return xx[rows, perm], perm

    def bwd(perm, dy):
        return (_scatter_inverse(perm, dy),)

    f.defvjp(fwd, bwd)
    return f(x)


@_JIT
def _sort_kv_batched(keys, values, *, tile, leaf, engine, interpret):
    bsz, n = keys.shape
    kx, vx = _inexact(keys.dtype), _inexact(values.dtype)
    if not (kx or vx):
        return _sort_kv_batched_impl(keys, values, n, tile, leaf, engine, interpret)

    @jax.custom_vjp
    def f(k, v):
        if kx:
            _, perm = _sort_kv_batched_impl(
                _keyed(k), _iota_like(k), n, tile, leaf, engine, interpret
            )
            rows = jnp.arange(bsz, dtype=jnp.int32)[:, None]
            return k[rows, perm], v[rows, perm]
        return _sort_kv_batched_impl(k, v, n, tile, leaf, engine, interpret)

    def fwd(k, v):
        _, perm = _sort_kv_batched_impl(
            _keyed(k), _iota_like(k), n, tile, leaf, engine, interpret
        )
        rows = jnp.arange(bsz, dtype=jnp.int32)[:, None]
        # stability makes the perm-gathers bit-identical to the kernel output
        return (k[rows, perm], v[rows, perm]), perm

    def bwd(perm, cts):
        dks, dvs = cts
        dk = _scatter_inverse(perm, dks) if kx else _float0((bsz, n))
        dv = _scatter_inverse(perm, dvs) if vx else _float0((bsz, n))
        return dk, dv

    f.defvjp(fwd, bwd)
    return f(keys, values)


@kernel_contract(kind="sort", masked_ranks=True, pow2_tile=True, differentiable=True)
def sort(
    x: jax.Array,
    *,
    tile: Optional[int] = None,
    leaf: Optional[int] = None,
    engine: str = _kern.DEFAULT_ENGINE,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Bottom-up merge sort whose wide rounds run on the flat round kernel.

    Every round is ONE call: narrow rounds (2*width <= tile) use the fused
    pure-JAX batched merge, wide rounds the flat ``(pair, tile)`` kernel —
    no Python-level loop over run pairs, and the pow2 + sentinel padding
    is built once per sort, not re-appended every round.

    Differentiable: under AD the forward runs the kv kernel with an iota
    payload to capture the stable argsort, and the backward is one
    inverse-gather scatter — the exact permutation transpose.
    """
    n = x.shape[0]
    if n <= 1:
        return x
    tile, leaf = _sort_tile(n, x.dtype, tile, leaf)
    return _guard(
        "sort", (x,), engine=engine, interpret=interpret,
        launch=lambda ar, eng, itp: _sort(
            ar[0], tile=tile, leaf=leaf, engine=eng, interpret=itp
        ),
        core=_core_sort, keys=(0,),
        meta=_meta(n, x.dtype, tile, leaf),
        verifier=_res.sorted_verifier(),
    )


@kernel_contract(
    kind="sort", carries_values=True, masked_ranks=True, pow2_tile=True,
    differentiable=True,
)
def sort_kv(
    keys: jax.Array,
    values: jax.Array,
    *,
    tile: Optional[int] = None,
    leaf: Optional[int] = None,
    engine: str = _kern.DEFAULT_ENGINE,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Stable key-value merge sort; wide rounds on the flat round kernel.

    Differentiable in both keys and values via the permutation-transpose
    VJP (int operands get float0 cotangents, per custom_vjp convention).
    """
    n = keys.shape[0]
    if n <= 1:
        return keys, values
    tile, leaf = _sort_tile(n, keys.dtype, tile, leaf)
    return _guard(
        "sort_kv", (keys, values), engine=engine, interpret=interpret,
        launch=lambda ar, eng, itp: _sort_kv(
            ar[0], ar[1], tile=tile, leaf=leaf, engine=eng, interpret=itp
        ),
        core=_core_sort_kv, keys=(0,),
        meta=_meta(n, keys.dtype, tile, leaf),
        verifier=_res.sorted_verifier(),
    )


@kernel_contract(
    kind="sort", batched=True, masked_ranks=True, pow2_tile=True,
    differentiable=True,
)
def sort_batched(
    x: jax.Array,
    *,
    tile: Optional[int] = None,
    leaf: Optional[int] = None,
    engine: str = _kern.DEFAULT_ENGINE,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Sort every row of ``(B, n)`` ascending; rows ride the same flat
    rounds as :func:`sort` (the batch axis is folded into the run-pair
    axis, so per-round launch count is independent of ``B``).
    Differentiable via the per-row permutation-transpose VJP."""
    bsz, n = x.shape
    if n <= 1:
        return x
    tile, leaf = _sort_tile(n, x.dtype, tile, leaf)
    return _guard(
        "sort_batched", (x,), engine=engine, interpret=interpret,
        launch=lambda ar, eng, itp: _sort_batched(
            ar[0], tile=tile, leaf=leaf, engine=eng, interpret=itp
        ),
        core=_core_sort_batched, keys=(0,),
        meta=_meta(n, x.dtype, tile, leaf, batch=bsz),
        verifier=_res.sorted_verifier(),
    )


@kernel_contract(
    kind="sort", batched=True, carries_values=True, masked_ranks=True,
    pow2_tile=True, differentiable=True,
)
def sort_kv_batched(
    keys: jax.Array,
    values: jax.Array,
    *,
    tile: Optional[int] = None,
    leaf: Optional[int] = None,
    engine: str = _kern.DEFAULT_ENGINE,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Row-wise stable key-value sort of ``(B, n)`` keys (ascending),
    kernel-backed like :func:`sort_batched` and differentiable in both
    operands via the per-row permutation-transpose VJP."""
    bsz, n = keys.shape
    if n <= 1:
        return keys, values
    tile, leaf = _sort_tile(n, keys.dtype, tile, leaf)
    return _guard(
        "sort_kv_batched", (keys, values), engine=engine, interpret=interpret,
        launch=lambda ar, eng, itp: _sort_kv_batched(
            ar[0], ar[1], tile=tile, leaf=leaf, engine=eng, interpret=itp
        ),
        core=_core_sort_kv_batched, keys=(0,),
        meta=_meta(n, keys.dtype, tile, leaf, batch=bsz),
        verifier=_res.sorted_verifier(),
    )


def _merge_k_rounds(runs, lens, tile, leaf, engine, interpret):
    """The k-way tournament body: ``ceil(log2 k)`` ragged batched rounds."""
    k, n = runs.shape
    sent = _mp.max_sentinel(runs.dtype)
    run_lens = (
        jnp.full((k,), n, jnp.int32) if lens is None else _bat._as_lens(lens, k, n)
    )
    stacked = _bat._mask_rows(runs, run_lens, sent)
    target = 1 << max(0, (k - 1).bit_length())
    if target != k:
        pad = jnp.full((target - k, n), sent, stacked.dtype)
        stacked = jnp.concatenate([stacked, pad], axis=0)
        run_lens = jnp.concatenate([run_lens, jnp.zeros((target - k,), jnp.int32)])
    while stacked.shape[0] > 1:
        stacked = merge_batched_ragged(
            stacked[0::2],
            stacked[1::2],
            run_lens[0::2],
            run_lens[1::2],
            tile=tile,
            leaf=leaf,
            engine=engine,
            interpret=interpret,
        )
        run_lens = run_lens[0::2] + run_lens[1::2]
    # pow2 pad rows only ever append sentinels, so the (k * n,) prefix
    # holds every valid element — same output width as the core forms
    return stacked[0][: k * n]


@kernel_contract(kind="merge_k", ragged=True, masked_ranks=True)
def merge_k(
    runs: jax.Array,
    lens: Optional[jax.Array] = None,
    *,
    tile: Optional[int] = None,
    leaf: Optional[int] = None,
    engine: str = _kern.DEFAULT_ENGINE,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """k-way tournament merge whose rounds run on the ragged batched kernel.

    Same contract as :func:`repro.core.batched.merge_k` restricted to a
    stacked ``(k, n)`` runs array (stable with lower-run priority; ``lens``
    optionally gives per-run valid lengths; output is always the
    ``(k * n,)`` merged valid prefix followed by sentinel padding — a
    traced ``lens`` forbids trimming further).  Each of the
    ``ceil(log2 k)`` tournament rounds is one :func:`merge_batched_ragged`
    call, i.e. the bitonic tile engine once the runs are wide enough
    to tile — this is ``distributed_sort``'s bucket combine for
    ``local_sort="pallas", combine="tournament"``.

    The rounds are themselves guarded calls, so this wrapper's own chain
    only adds the direct core tournament as a terminal oracle.
    """
    runs = jnp.asarray(runs)
    if runs.ndim != 2:
        raise ValueError(f"expected (k, n) runs, got shape {runs.shape}")
    k, n = runs.shape
    if not _res.guard_enabled() or _res.is_tracing(runs, lens):
        return _merge_k_rounds(runs, lens, tile, leaf, engine, interpret)
    idx = _faults.next_index("merge_k")
    if lens is None:
        total = k * n
    else:
        total = int(np.clip(np.asarray(lens, dtype=np.int64).reshape(-1), 0, n).sum())
    return _res.guarded_call(
        "merge_k",
        [
            (f"rounds-{engine}",
             lambda: _merge_k_rounds(runs, lens, tile, leaf, engine, interpret)),
            ("core", lambda: _core_merge_k(runs, lens)),
        ],
        index=idx,
        meta=_meta(k * n, runs.dtype, batch=k, ragged=True),
        verifier=_res.sorted_verifier(np.asarray([total])),
    )


@_JITK
def _topk_batched(x, *, k, tile, leaf, engine, interpret):
    bsz, n = x.shape

    def _primal(xx):
        _, perm = _sort_kv_batched_impl(
            _keyed(_mp.flip_desc(xx)), _iota_like(xx), n, tile, leaf, engine, interpret
        )
        top_idx = perm[:, :k]
        return jnp.take_along_axis(xx, top_idx, axis=1), top_idx

    if not _inexact(x.dtype):
        return _primal(x)

    @jax.custom_vjp
    def f(xx):
        return _primal(xx)

    def fwd(xx):
        vals, top_idx = _primal(xx)
        return (vals, top_idx), top_idx

    def bwd(top_idx, cts):
        dvals, _ = cts  # index cotangent is float0
        rows = jnp.arange(bsz, dtype=jnp.int32)[:, None]
        return (jnp.zeros((bsz, n), dvals.dtype).at[rows, top_idx].set(dvals),)

    f.defvjp(fwd, bwd)
    return f(x)


@kernel_contract(
    kind="topk", batched=True, carries_values=True, masked_ranks=True,
    pow2_tile=True, differentiable=True,
)
def topk_batched(
    x: jax.Array,
    k: int,
    *,
    tile: Optional[int] = None,
    leaf: Optional[int] = None,
    engine: str = _kern.DEFAULT_ENGINE,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Row-wise descending top-k on the kernel-backed batched kv-sort.

    Same contract as :func:`repro.core.batched.topk_batched` (stable,
    ``lax.top_k`` tie-breaking, exact at ``iinfo.min`` via
    ``flip_desc``), but the sort rounds run on the flat round kernel
    with tuned ``(tile, leaf)`` — the serving sampler's wide-vocab path.
    NaN candidates rank below every real value (total-order keys).
    Differentiable: the backward scatters the k value-cotangents back to
    their source columns (one exact inverse gather).
    """
    bsz, n = x.shape
    k = min(k, n)
    tile, leaf = _sort_tile(n, x.dtype, tile, leaf)
    return _guard(
        "topk_batched", (x,), engine=engine, interpret=interpret,
        launch=lambda ar, eng, itp: _topk_batched(
            ar[0], k=k, tile=tile, leaf=leaf, engine=eng, interpret=itp
        ),
        core=lambda xx: _core_topk_batched(xx, k), keys=(0,),
        meta=_meta(n, x.dtype, tile, leaf, batch=bsz),
        verifier=_res.topk_verifier(),
    )


@_JITK
def _topk_batched_ragged(x, lens, *, k, tile, leaf, engine, interpret):
    bsz, n = x.shape

    def _primal(xx, ln):
        keys = _keyed(_mp.flip_desc(xx))
        keys = _bat._mask_rows(keys, ln, _mp.max_sentinel(keys.dtype))
        _, perm = _sort_kv_batched_impl(
            keys, _iota_like(xx), n, tile, leaf, engine, interpret
        )
        top_idx = perm[:, :k]
        vals = jnp.take_along_axis(xx, top_idx, axis=1)
        slot_valid = jnp.arange(k, dtype=jnp.int32)[None, :] < ln[:, None]
        vals = jnp.where(slot_valid, vals, _mp.min_sentinel(xx.dtype))
        top_idx = jnp.where(slot_valid, top_idx, -1)
        return vals, top_idx

    if not _inexact(x.dtype):
        return _primal(x, lens)

    @jax.custom_vjp
    def f(xx, ln):
        return _primal(xx, ln)

    def fwd(xx, ln):
        vals, top_idx = _primal(xx, ln)
        return (vals, top_idx), top_idx

    def bwd(top_idx, cts):
        dvals, _ = cts
        valid = top_idx >= 0
        safe_idx = jnp.where(valid, top_idx, 0)
        contrib = jnp.where(valid, dvals, jnp.zeros((), dvals.dtype))
        rows = jnp.arange(bsz, dtype=jnp.int32)[:, None]
        # .add (not .set): masked slots alias column 0 with zero contribution
        dx = jnp.zeros((bsz, n), dvals.dtype).at[rows, safe_idx].add(contrib)
        return dx, _float0((bsz,))

    f.defvjp(fwd, bwd)
    return f(x, lens)


@kernel_contract(
    kind="topk", batched=True, ragged=True, carries_values=True,
    masked_ranks=True, pow2_tile=True, differentiable=True,
)
def topk_batched_ragged(
    x: jax.Array,
    k: int,
    lens: jax.Array,
    *,
    tile: Optional[int] = None,
    leaf: Optional[int] = None,
    engine: str = _kern.DEFAULT_ENGINE,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Ragged row-wise descending top-k, kernel-backed.

    Contract matches :func:`repro.core.batched.topk_batched_ragged`
    exactly (masked slots: index ``-1``, dtype-min value); the underlying
    sort is the same sentinel-mask-then-sort reduction the core ragged
    kv-sort uses, so padded rows are bit-identical to their truncations.
    Differentiable: cotangents of masked (sentinel) slots are provably
    zeroed — only valid slots scatter back, so rows shorter than ``k``
    get exactly the gradient their truncation would.
    """
    bsz, n = x.shape
    k = min(k, n)
    lens = _bat._as_lens(lens, bsz, n)
    tile, leaf = _sort_tile(n, x.dtype, tile, leaf)
    return _guard(
        "topk_batched_ragged", (x, lens), engine=engine, interpret=interpret,
        launch=lambda ar, eng, itp: _topk_batched_ragged(
            ar[0], ar[1], k=k, tile=tile, leaf=leaf, engine=eng, interpret=itp
        ),
        core=lambda xx, ln: _core_topk_batched_ragged(xx, k, ln), keys=(0,),
        meta=_meta(n, x.dtype, tile, leaf, batch=bsz, ragged=True),
        verifier=_res.topk_verifier(),
    )
