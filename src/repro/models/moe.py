"""Mixture-of-Experts with **merge-path sorted dispatch**.

This is the paper's technique as a first-class framework feature: token →
expert routing is a *stable key-value merge sort* (``repro.core``) of the
flat (expert_id, slot) assignment list.  Stability gives a deterministic,
position-ordered drop policy under finite expert capacity — the property
GPU MoE stacks get from radix/merge-path sorts (cf. the paper's §5 GPU
lineage) and that one-hot-einsum dispatch pays O(tokens·E·C) memory for.

Pipeline (per batch row, vmapped so the batch axis stays data-sharded):

1. router logits -> top-k experts per token (k small: lax.top_k)
2. flat assignment keys ``expert_id`` with values ``slot = token*k + j``
3. stable merge-path kv-sort groups assignments by expert, preserving
   token order within each expert
4. position-in-expert = sorted_rank - expert_offset (offsets by binary
   search over the sorted keys — a cross-diagonal search, Alg. 2 again)
5. scatter token embeddings into (E, capacity, d); batched expert matmul;
   combine with router weights.

``moe_dispatch="cumsum"`` selects the conventional one-hot-cumsum
position computation as the ablation baseline (benchmarks table 2).

Routing (``route``) is softmax top-k with the chosen probabilities
renormalised, or, with ``router_scoring="sigmoid"`` (DeepSeek-V3
``noaux_tc``), independent sigmoid scores: the top-k experts are chosen
on score plus a per-expert ``e_score_correction_bias``, which chooses and
never weights; the chosen unbiased scores are renormalised and scaled by
``routed_scaling``.  A shared expert (``shared_expert_ff``) sees every
token.

**Gradients.** Every dispatch route is differentiable, including
``"merge_path_pallas"``: the sort acts on integer (expert_id, slot) pairs
— a pure permutation with no float tangents — and the float scatter /
gather / combine steps are plain ``.at[]`` indexing with exact transpose
rules (the kernel-backed float sorts in ``repro.kernels.ops`` carry their
own permutation-transpose ``custom_vjp``).  ``train/steps.py`` therefore
trains on the kernel path directly; there is no oracle-route fallback
under ``forward_train``.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import (
    max_sentinel,
    merge_sort_kv_batched,
    merge_sort_kv_batched_ragged,
    searchsorted_batched,
)
from repro.core.batched import _mask_rows
from repro.parallel.sharding import constrain
from .layers import dense_init, mlp_apply, mlp_init, _act


def moe_init(key, cfg: ModelConfig, dtype) -> Dict:
    d, e, fe = cfg.d_model, cfg.num_experts, cfg.d_ff
    keys = jax.random.split(key, 5)
    p = {
        "router": dense_init(keys[0], (d, e), d, jnp.float32),
        "wg": dense_init(keys[1], (e, d, fe), d, dtype),
        "wi": dense_init(keys[2], (e, d, fe), d, dtype),
        "wo": dense_init(keys[3], (e, fe, d), fe, dtype),
    }
    if cfg.router_scoring == "sigmoid":
        p["e_score_correction_bias"] = jnp.zeros((e,), jnp.float32)
    if cfg.shared_expert_ff:
        p["shared"] = mlp_init(keys[4], d, cfg.shared_expert_ff, "silu", dtype)
    return p


def capacity(cfg: ModelConfig, tokens_per_row: int) -> int:
    c = int(math.ceil(tokens_per_row * cfg.experts_per_token * cfg.capacity_factor / cfg.num_experts))
    return max(8, -(-c // 8) * 8)  # pad to lane-friendly multiple


def _positions_merge_path_batched(
    flat_expert: jax.Array,
    e: int,
    slot_lens: jax.Array | None = None,
    backend: str = "core",
) -> jax.Array:
    """Merge-path dispatch for the whole batch: position-in-expert per slot.

    flat_expert: (B, N) int32 expert ids (N = tokens*k per row).  Returns
    (B, N) position_in_expert aligned with the input slots.

    One batched stable kv-sort (``repro.core.batched``) groups every row's
    assignments by expert simultaneously — all rows, runs and diagonal
    searches share a single fused Algorithm 2 pass instead of a vmapped
    per-row sort.  Expert start offsets fall out of a batched binary
    search over the sorted ids (the same cross-diagonal search).

    ``slot_lens`` makes the dispatch **ragged**: only the first
    ``slot_lens[r]`` slots of row ``r`` (= ``valid_tokens * k``, padding
    tokens sit at the sequence tail) are routed.  The ragged kv-sort
    pushes masked slots past every real assignment, so padding tokens
    can never consume expert capacity and every valid token keeps the
    position it would have in an unpadded batch.  Masked slots report
    an over-capacity position, so the usual ``pos < capacity``
    test drops them with no extra mask.

    ``backend="pallas"`` (``moe_dispatch="merge_path_pallas"``) routes the
    routing sort through the bitonic tile engine
    (``repro.kernels.ops.sort_kv_batched``, autotuned ``(tile, leaf)``)
    — same stable-sort contract, wide rows ride the flat round kernel.
    The ragged form masks the expert keys to the sentinel first, exactly
    the reduction ``merge_sort_kv_batched_ragged`` applies internally.
    """
    b, n = flat_expert.shape
    slots = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None, :], (b, n))
    if backend == "pallas":
        from repro.kernels import ops as kops  # deferred: kernels layer is optional here

        keys = flat_expert
        if slot_lens is not None:
            keys = _mask_rows(keys, slot_lens, max_sentinel(keys.dtype))
        sorted_e, sorted_slot = kops.sort_kv_batched(keys, slots)  # stable
    elif slot_lens is None:
        sorted_e, sorted_slot = merge_sort_kv_batched(flat_expert, slots)  # stable
    else:
        sorted_e, sorted_slot = merge_sort_kv_batched_ragged(
            flat_expert, slots, slot_lens
        )
    experts = jnp.broadcast_to(jnp.arange(e, dtype=flat_expert.dtype)[None, :], (b, e))
    offsets = searchsorted_batched(sorted_e, experts, side="left")  # (B, E)
    pos_sorted = jnp.arange(n, dtype=jnp.int32)[None, :] - jnp.take_along_axis(
        offsets, jnp.clip(sorted_e.astype(jnp.int32), 0, e - 1), axis=1
    )
    if slot_lens is not None:
        # masked slots (rank >= row length) always report an over-capacity
        # position; real slots are unaffected
        pos_sorted = jnp.where(
            jnp.arange(n, dtype=jnp.int32)[None, :] < slot_lens[:, None],
            pos_sorted,
            jnp.int32(2**30),
        )
    # scatter positions back to original slot order
    rows = jnp.arange(b, dtype=jnp.int32)[:, None]
    return jnp.zeros((b, n), jnp.int32).at[rows, sorted_slot].set(pos_sorted)


def _positions_merge_path(flat_expert: jax.Array, e: int) -> jax.Array:
    """Single-row form of :func:`_positions_merge_path_batched` (tests/ablation)."""
    return _positions_merge_path_batched(flat_expert[None, :], e)[0]


def _positions_cumsum(flat_expert: jax.Array, e: int) -> jax.Array:
    """Ablation baseline: one-hot cumsum position-in-expert (O(N*E))."""
    onehot = jax.nn.one_hot(flat_expert, e, dtype=jnp.int32)  # (N,E)
    pos = jnp.cumsum(onehot, axis=0) - 1
    return jnp.take_along_axis(pos, flat_expert[:, None], axis=1)[:, 0]


def route(params: Dict, x: jax.Array, cfg: ModelConfig):
    """x (..., d) -> (weights (..., k) float32, experts (..., k) int32)."""
    k = cfg.experts_per_token
    router_logits = x.astype(jnp.float32) @ params["router"]  # (..., E)
    if cfg.router_scoring == "sigmoid":
        scores = jax.nn.sigmoid(router_logits)
        _, top_e = jax.lax.top_k(scores + params["e_score_correction_bias"], k)
        top_p = jnp.take_along_axis(scores, top_e, axis=-1)
    else:
        top_p, top_e = jax.lax.top_k(jax.nn.softmax(router_logits, axis=-1), k)
    top_p = top_p / jnp.maximum(jnp.sum(top_p, axis=-1, keepdims=True), 1e-9)
    if cfg.routed_scaling != 1.0:
        top_p = top_p * cfg.routed_scaling
    return top_p, top_e


def experts_reached(top_e: jax.Array, e: int, live: jax.Array | None = None) -> jax.Array:
    """How many of the ``e`` experts ``top_e`` (B, ...) gives a slot of the
    rows ``live`` (B,) marks (default all)."""
    idx = top_e.reshape(top_e.shape[0], -1)
    if live is not None:
        idx = jnp.where(live[:, None], idx, e)  # out of range: dropped
    return jnp.sum(jnp.zeros((e,), bool).at[idx.reshape(-1)].set(True, mode="drop"), dtype=jnp.int32)


def moe_apply(
    params: Dict,
    x: jax.Array,
    cfg: ModelConfig,
    token_counts: jax.Array | None = None,
) -> jax.Array:
    """x (B,S,d) -> (B,S,d): :func:`moe_layer`'s output alone."""
    return moe_layer(params, x, cfg, token_counts)[0]


def moe_layer(
    params: Dict,
    x: jax.Array,
    cfg: ModelConfig,
    token_counts: jax.Array | None = None,
):
    """x (B,S,d) -> (y (B,S,d), chosen experts (B,S,k)).  Batch axis stays
    sharded; experts tensor-sharded.

    ``token_counts`` (optional, ``(B,)`` int32) marks each row's valid
    token count — padding tokens occupy the sequence tail.  With it, the
    merge-path dispatch runs **ragged**: padded tokens are masked out of
    the routing sort, never consume expert capacity, and contribute zero
    output, so every valid token gets exactly the capacity position it
    would get in an unpadded batch.
    """
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    cap = capacity(cfg, s)
    top_p, top_e = route(params, x, cfg)  # (B,S,k)

    # Position-in-expert for ALL batch rows at once: the merge-path path is
    # one batched stable kv-sort (a single fused Alg. 2 pass across the
    # whole batch) rather than a vmapped per-row sort.
    flat_e = top_e.reshape(b, s * k).astype(jnp.int32)  # (B, S*k)
    slot_lens = None
    if token_counts is not None:
        # slots are token-major, so valid slots form the prefix tokens*k
        slot_lens = jnp.clip(jnp.asarray(token_counts, jnp.int32), 0, s) * k
    if cfg.moe_dispatch in ("merge_path", "merge_path_pallas"):
        backend = "pallas" if cfg.moe_dispatch == "merge_path_pallas" else "core"
        pos = _positions_merge_path_batched(flat_e, e, slot_lens, backend)  # (B, S*k)
    else:
        pos = jax.vmap(lambda fe: _positions_cumsum(fe, e))(flat_e)
        if slot_lens is not None:
            slot_ids = jnp.arange(s * k, dtype=jnp.int32)[None, :]
            pos = jnp.where(slot_ids < slot_lens[:, None], pos, jnp.int32(2**30))
    kept = pos < cap
    tok = jnp.broadcast_to(
        jnp.repeat(jnp.arange(s, dtype=jnp.int32), k)[None, :], (b, s * k)
    )

    def dispatch_row(xrow, flat_e_r, pos_r, kept_r, tok_r):
        # scatter embeddings into (E, cap, d); dropped slots go nowhere
        buf = jnp.zeros((e, cap, d), xrow.dtype)
        return buf.at[flat_e_r, jnp.where(kept_r, pos_r, cap)].set(
            xrow[tok_r], mode="drop"
        )

    buf = jax.vmap(dispatch_row)(x, flat_e, pos, kept, tok)
    buf = constrain(buf, "act_batch", "act_experts", None, None)
    # batched expert MLP: (B,E,C,d) x (E,d,f) -> (B,E,C,f)
    up = jnp.einsum("becd,edf->becf", buf, params["wi"])
    gate = jnp.einsum("becd,edf->becf", buf, params["wg"])
    h = _act("silu", gate, up)
    h = constrain(h, "act_batch", "act_experts", None, None)
    out_buf = jnp.einsum("becf,efd->becd", h, params["wo"])  # (B,E,C,d)

    def combine_row(obuf, flat_e_r, pos_r, kept_r, tok_r, prow):
        # gather expert outputs back to token slots, weight, and sum over k
        vals = obuf[flat_e_r, jnp.minimum(pos_r, cap - 1)]  # (S*k, d)
        w = prow.reshape(-1)[:, None].astype(vals.dtype) * kept_r[:, None]
        y = jnp.zeros((s, d), vals.dtype).at[tok_r].add(vals * w)
        return y

    y = jax.vmap(combine_row)(out_buf, flat_e, pos, kept, tok, top_p)
    if "shared" in params:
        y = y + mlp_apply(params["shared"], x, "silu")
    return y.astype(x.dtype), top_e


def aux_load_balance_loss(router_logits: jax.Array, top_e: jax.Array, e: int) -> jax.Array:
    """Switch-style load-balance auxiliary loss (available to train cfg)."""
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    me = jnp.mean(probs.reshape(-1, e), axis=0)
    ce = jnp.mean(
        jax.nn.one_hot(top_e.reshape(-1), e, dtype=jnp.float32), axis=0
    )
    return e * jnp.sum(me * ce)
