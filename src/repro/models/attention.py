"""Attention: full / blockwise (online-softmax) / sliding-window / decode.

All variants share one set of projections; the score/softmax path is
chosen by sequence length and window config so that every assigned
shape cell lowers with bounded live memory:

* ``seq <= full_threshold``: dense masked attention (train_4k).
* longer: blockwise attention — ``lax.scan`` over KV chunks with a
  running (max, denom, acc) online softmax (prefill_32k).
* ``window > 0``: sliding-window mask (and a ring-buffer cache on the
  decode path), used by gemma3 local layers and hymba.
* decode: single-query attention over a cache; optionally
  context-parallel over the ``model`` axis (see serving.engine).

Multi-head latent attention (MLA, DeepSeek-V2/V3) has two forms over the
same weights.  Prefill and training run the expanded form: each head's
keys and values are rebuilt from the shared latent.  Decode runs the
absorbed form over a cache of latent rows alone: the key half of
``wkv_b`` is folded into the query and its value half is applied after
the weighted sum, so a step reads ``kv_lora_rank + qk_rope_head_dim``
numbers per cached position and never expands keys or values per head.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.parallel.sharding import constrain
from .layers import apply_rope, make_rope, rms_norm

NEG_INF = -1e30
FULL_ATTENTION_THRESHOLD = 8192


def qkv_proj(params: Dict, x: jax.Array, num_heads: int, num_kv: int, head_dim: int):
    """x (B,S,d) -> q (B,S,H,hd), k/v (B,S,K,hd)."""
    b, s, _ = x.shape
    q = (x @ params["wq"]).reshape(b, s, num_heads, head_dim)
    k = (x @ params["wk"]).reshape(b, s, num_kv, head_dim)
    v = (x @ params["wv"]).reshape(b, s, num_kv, head_dim)
    q = constrain(q, "act_batch", "act_seq", "act_heads", None)
    k = constrain(k, "act_batch", "act_seq", None, None)
    v = constrain(v, "act_batch", "act_seq", None, None)
    return q, k, v


def _mask(
    qpos: jax.Array,  # (Sq,) absolute positions of queries
    kpos: jax.Array,  # (Sk,)
    kind: str,  # causal | full | prefix
    window: int,
    prefix_len: int,
) -> jax.Array:
    m = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    if kind == "causal" or kind == "prefix":
        m = kpos[None, :] <= qpos[:, None]
        if kind == "prefix":
            m = m | (kpos[None, :] < prefix_len)
    if window > 0:
        m = m & (kpos[None, :] > qpos[:, None] - window)
    return m


def _sdpa(q, k, v, mask, softcap: float) -> jax.Array:
    """q (B,Sq,K,G,hd), k/v (B,Sk,K,hd), mask (Sq,Sk) -> (B,Sq,K,G,hd)."""
    hd = q.shape[-1]
    scores = jnp.einsum("bqkgh,bskh->bkgqs", q, k).astype(jnp.float32) / math.sqrt(hd)
    if softcap > 0:
        scores = jnp.tanh(scores / softcap) * softcap
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bkgqs,bskh->bqkgh", probs, v)


def _blockwise(q, k, v, qpos, kind, window, prefix_len, chunk, softcap) -> jax.Array:
    """Online-softmax over KV chunks; q (B,Sq,K,G,hd), k/v (B,Sk,K,hd)."""
    b, sq, kh, g, hd = q.shape
    sk, hv = k.shape[1], v.shape[-1]
    from repro.utils.costmode import cost_exact

    if cost_exact():
        # bound unrolled chunk count: flops identical, compile stays small
        chunk = max(chunk, -(-sk // 8))
    nchunks = -(-sk // chunk)
    pad = nchunks * chunk - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(b, nchunks, chunk, kh, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, nchunks, chunk, kh, hv).transpose(1, 0, 2, 3, 4)
    qf = q.astype(jnp.float32)

    def step(carry, xs):
        m_run, l_run, acc = carry
        kb, vb, ci = xs
        kpos = ci * chunk + jnp.arange(chunk)
        msk = _mask(qpos, kpos, kind, window, prefix_len) & (kpos < sk)[None, :]
        s = jnp.einsum("bqkgh,bskh->bkgqs", qf, kb.astype(jnp.float32)) / math.sqrt(hd)
        if softcap > 0:
            s = jnp.tanh(s / softcap) * softcap
        s = jnp.where(msk[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        scale = jnp.exp(m_run - m_new)
        l_new = l_run * scale + jnp.sum(p, axis=-1)
        acc = acc * scale[..., None] + jnp.einsum(
            "bkgqs,bskh->bkgqh", p, vb.astype(jnp.float32)
        )
        return (m_new, l_new, acc), None

    m0 = jnp.full((b, kh, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kh, g, sq), jnp.float32)
    acc0 = jnp.zeros((b, kh, g, sq, hv), jnp.float32)
    from repro.utils.costmode import scan_unroll

    (m_f, l_f, acc), _ = jax.lax.scan(
        step, (m0, l0, acc0), (kc, vc, jnp.arange(nchunks)), unroll=scan_unroll(nchunks)
    )
    out = acc / jnp.maximum(l_f[..., None], 1e-30)
    return out.transpose(0, 3, 1, 2, 4).astype(q.dtype)  # (B,Sq,K,G,hv)


def _attend(qh, k, v, positions, kpos, kind, window, prefix_len, chunk, softcap, force_blockwise):
    """Dense masked attention up to FULL_ATTENTION_THRESHOLD keys, blockwise
    beyond; qh (B,Sq,K,G,hd), k (B,Sk,K,hd), v (B,Sk,K,hv) -> (B,Sq,K,G,hv)."""
    if k.shape[1] <= FULL_ATTENTION_THRESHOLD and not force_blockwise:
        mask = _mask(positions, kpos, kind, window, prefix_len)
        return _sdpa(qh, k, v, mask, softcap)
    return _blockwise(qh, k, v, positions, kind, window, prefix_len, chunk, softcap)


def attention(
    params: Dict,
    x: jax.Array,
    *,
    num_heads: int,
    num_kv: int,
    head_dim: int,
    rope_theta: float,
    positions: jax.Array,  # (S,) absolute positions
    kind: str = "causal",
    window: int = 0,
    prefix_len: int = 0,
    chunk: int = 1024,
    softcap: float = 0.0,
    kv: Optional[Tuple[jax.Array, jax.Array]] = None,  # cross-attention K/V source
    force_blockwise: bool = False,
) -> jax.Array:
    """Self (or cross, if kv given) attention; x (B,S,d) -> (B,S,d)."""
    b, s, d = x.shape
    g = num_heads // num_kv
    if kv is None:
        q, k, v = qkv_proj(params, x, num_heads, num_kv, head_dim)
        if rope_theta > 0:
            sin, cos = make_rope(positions, head_dim, rope_theta)
            q = apply_rope(q, sin, cos)
            k = apply_rope(k, sin, cos)
        kpos = positions
    else:
        k, v = kv  # (B,Sk,K,hd) precomputed (encoder output projections)
        q = (x @ params["wq"]).reshape(b, s, num_heads, head_dim)
        kind = "full"
        kpos = jnp.arange(k.shape[1])
    qh = q.reshape(b, s, num_kv, g, head_dim)
    out = _attend(qh, k, v, positions, kpos, kind, window, prefix_len, chunk, softcap, force_blockwise)
    out = out.reshape(b, s, num_heads * head_dim)
    out = constrain(out, "act_batch", "act_seq", "act_heads")
    return out @ params["wo"]


# ---------------------------------------------------------------------------
# decode path (single new token against a cache)
# ---------------------------------------------------------------------------

def decode_attention(
    params: Dict,
    x: jax.Array,  # (B, 1, d)
    cache_k: jax.Array,  # (B, S_cache, K, hd) — ring buffer if window > 0
    cache_v: jax.Array,
    pos: jax.Array,  # (B,) absolute position of the new token
    *,
    num_heads: int,
    num_kv: int,
    head_dim: int,
    rope_theta: float,
    window: int = 0,
    softcap: float = 0.0,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (out (B,1,d), new_cache_k, new_cache_v)."""
    b = x.shape[0]
    s_cache = cache_k.shape[1]
    g = num_heads // num_kv
    q = (x @ params["wq"]).reshape(b, num_heads, head_dim)
    k = (x @ params["wk"]).reshape(b, num_kv, head_dim)
    v = (x @ params["wv"]).reshape(b, num_kv, head_dim)
    if rope_theta > 0:
        sin, cos = make_rope(pos[:, None], head_dim, rope_theta)  # (B,1,half)
        q = apply_rope(q.reshape(b, 1, num_heads, head_dim), sin, cos).reshape(b, num_heads, head_dim)
        k = apply_rope(k.reshape(b, 1, num_kv, head_dim), sin, cos).reshape(b, num_kv, head_dim)
    if window > 0:
        slot = pos % s_cache
    else:
        slot = jnp.minimum(pos, s_cache - 1)
    bidx = jnp.arange(b)
    cache_k = cache_k.at[bidx, slot].set(k.astype(cache_k.dtype))
    cache_v = cache_v.at[bidx, slot].set(v.astype(cache_v.dtype))
    cache_k = constrain(cache_k, "act_batch", "act_kv_seq", None, None)
    cache_v = constrain(cache_v, "act_batch", "act_kv_seq", None, None)
    # absolute position held by each cache slot
    ridx = jnp.arange(s_cache)[None, :]
    if window > 0:
        kpos = pos[:, None] - ((pos[:, None] - ridx) % s_cache)
    else:
        kpos = ridx * jnp.ones((b, 1), jnp.int32)
    valid = (kpos <= pos[:, None]) & (kpos >= 0)
    if window > 0:
        valid = valid & (kpos > pos[:, None] - window)
    qh = q.reshape(b, num_kv, g, head_dim)
    scores = jnp.einsum("bkgh,bskh->bkgs", qh, cache_k).astype(jnp.float32) / math.sqrt(head_dim)
    if softcap > 0:
        scores = jnp.tanh(scores / softcap) * softcap
    scores = jnp.where(valid[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("bkgs,bskh->bkgh", probs, cache_v)
    out = out.reshape(b, 1, num_heads * head_dim)
    return out @ params["wo"], cache_k, cache_v


def cross_decode_attention(params, x, xk, xv, *, num_heads, num_kv, head_dim):
    """Cross-attention for one decode step; xk/xv (B,Senc,K,hd)."""
    b = x.shape[0]
    g = num_heads // num_kv
    q = (x @ params["wq"]).reshape(b, num_kv, g, head_dim)
    scores = jnp.einsum("bkgh,bskh->bkgs", q, xk).astype(jnp.float32) / math.sqrt(head_dim)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("bkgs,bskh->bkgh", probs, xv).reshape(b, 1, num_heads * head_dim)
    return out @ params["wo"]


# ---------------------------------------------------------------------------
# multi-head latent attention (MLA)
# ---------------------------------------------------------------------------

def _rope_pairs(x: jax.Array) -> jax.Array:
    """Reorder the last axis from interleaved rotary pairs (x0, x1), (x2, x3),
    ... to the two halves ``apply_rope`` rotates, as the published
    DeepSeek-V3 code does before its ``rotate_half``."""
    *lead, d = x.shape
    return x.reshape(*lead, d // 2, 2).swapaxes(-1, -2).reshape(*lead, d)


def mla_project(params: Dict, x: jax.Array, positions: jax.Array, *, num_heads: int, nope: int,
                rope: int, rope_theta: float, eps: float):
    """x (B,S,d) -> q_nope (B,S,H,nope), roped q_pe (B,S,H,rope) and the
    latent rows (B,S,r+rope) a cache holds: [RMSNorm(c), roped k_pe].

    ``positions`` is (S,), or (B,1) for one decode token."""
    b, s, _ = x.shape
    q = (x @ params["wq"]).reshape(b, s, num_heads, nope + rope)
    kv = x @ params["wkv_a"]  # (B,S,r+rope): the latent c and the shared k_pe
    r = kv.shape[-1] - rope
    c = rms_norm(kv[..., :r], params["kv_norm"], eps)
    sin, cos = make_rope(positions, rope, rope_theta)
    q_pe = apply_rope(_rope_pairs(q[..., nope:]), sin, cos)
    k_pe = apply_rope(_rope_pairs(kv[..., None, r:]), sin, cos)[..., 0, :]
    return q[..., :nope], q_pe, jnp.concatenate([c, k_pe], axis=-1)


def mla_attention(
    params: Dict,
    x: jax.Array,
    *,
    num_heads: int,
    nope: int,
    rope: int,
    v_dim: int,
    rope_theta: float,
    eps: float,
    positions: jax.Array,
    kind: str = "causal",
    prefix_len: int = 0,
    chunk: int = 1024,
    softcap: float = 0.0,
    force_blockwise: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Expanded MLA (prefill, training): x (B,S,d) -> (out (B,S,d), latent rows (B,S,r+rope)).

    Per head i: ``[k_nope, v] = c W_kv_b,i``, keys ``[k_nope, k_pe]`` with
    the one k_pe shared by the heads, scores over 1/sqrt(nope + rope)."""
    b, s, _ = x.shape
    h = num_heads
    q_nope, q_pe, latent = mla_project(params, x, positions, num_heads=h, nope=nope, rope=rope,
                                       rope_theta=rope_theta, eps=eps)
    r = latent.shape[-1] - rope
    kv = (latent[..., :r] @ params["wkv_b"]).reshape(b, s, h, nope + v_dim)
    k_pe = jnp.broadcast_to(latent[:, :, None, r:], (b, s, h, rope))
    q = constrain(jnp.concatenate([q_nope, q_pe], axis=-1), "act_batch", "act_seq", "act_heads", None)
    k = jnp.concatenate([kv[..., :nope], k_pe], axis=-1)
    out = _attend(q[:, :, :, None], k, kv[..., nope:], positions, positions, kind, 0, prefix_len,
                  chunk, softcap, force_blockwise)
    out = constrain(out.reshape(b, s, h * v_dim), "act_batch", "act_seq", "act_heads")
    return out @ params["wo"], latent


def mla_decode_attention(
    params: Dict,
    x: jax.Array,  # (B, 1, d)
    cache: jax.Array,  # (B, S_cache, r+rope) latent rows
    pos: jax.Array,  # (B,) absolute position of the new token
    *,
    num_heads: int,
    nope: int,
    rope: int,
    v_dim: int,
    rope_theta: float,
    eps: float,
) -> Tuple[jax.Array, jax.Array]:
    """Absorbed MLA for one token; returns (out (B,1,d), new cache).

    The token's latent row is written at ``pos``; then per head
    ``q_lat = W_UK,i^T q_nope``, scores ``[q_lat, q_pe] . [c_s, k_pe_s]``
    over positions ``<= pos``, and ``out = W_UV,i (sum_s p_s c_s)``."""
    b, s_cache, width = cache.shape
    h = num_heads
    q_nope, q_pe, row = mla_project(params, x, pos[:, None], num_heads=h, nope=nope, rope=rope,
                                    rope_theta=rope_theta, eps=eps)
    slot = jnp.minimum(pos, s_cache - 1)
    cache = cache.at[jnp.arange(b), slot].set(row[:, 0].astype(cache.dtype))
    cache = constrain(cache, "act_batch", "act_kv_seq", None)
    r = width - rope
    w_b = params["wkv_b"].reshape(r, h, nope + v_dim)
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], w_b[..., :nope])
    qcat = jnp.concatenate([q_lat, q_pe[:, 0]], axis=-1).astype(cache.dtype)  # (B,H,r+rope)
    scores = jnp.einsum("bhc,bsc->bhs", qcat, cache, preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(nope + rope)
    valid = jnp.arange(s_cache)[None, :] <= pos[:, None]
    scores = jnp.where(valid[:, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(cache.dtype)
    o_lat = jnp.einsum("bhs,bsc->bhc", probs, cache)[..., :r]  # the whole row read once
    o = jnp.einsum("bhr,rhv->bhv", o_lat.astype(x.dtype), w_b[..., nope:])
    return o.reshape(b, 1, h * v_dim) @ params["wo"], cache
