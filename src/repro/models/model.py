"""Top-level model API: embed -> stack -> logits, all modes, all families."""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, SSM, HYBRID, VLM, AUDIO
from repro.parallel.sharding import constrain
from . import attention as attn_mod
from . import ssm as ssm_mod
from .layers import rms_norm, sinusoidal_positions
from .transformer import (
    _dtype,
    _group_windows,
    _stack_params,
    dense_cfg,
    init_params,
    abstract_params,
    stack_forward,
)

__all__ = [
    "init_params",
    "abstract_params",
    "forward_train",
    "forward_prefill",
    "forward_decode",
    "init_caches",
    "encoder_forward",
]


def _embed(cfg: ModelConfig, params: Dict, tokens: jax.Array) -> jax.Array:
    x = params["embed"]["table"][tokens]
    x = x * jnp.sqrt(jnp.array(cfg.d_model, x.dtype))
    return constrain(x, "act_batch", "act_seq", "act_embed")


def _logits(cfg: ModelConfig, params: Dict, x: jax.Array) -> jax.Array:
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    if cfg.tie_embeddings:
        logits = x @ params["embed"]["table"].T
    else:
        logits = x @ params["unembed"]
    return constrain(logits, "act_batch", "act_seq", "act_vocab")


def encoder_forward(cfg: ModelConfig, params: Dict, frames: jax.Array):
    """Whisper encoder over precomputed frame embeddings (stub frontend).

    frames (B, S_enc, d) -> per-decoder-layer cross K/V (L, B, S_enc, K, hd).
    """
    enc = params["encoder"]
    x = frames @ enc["frame_proj"]
    x = x + sinusoidal_positions(frames.shape[1], cfg.d_model).astype(x.dtype)[None]
    positions = jnp.arange(frames.shape[1])
    # encoder scan reuses the decoder group machinery with kind=full
    import dataclasses

    enc_cfg = dataclasses.replace(
        cfg, num_layers=cfg.encoder_layers, global_every=0, sliding_window=0,
        num_experts=0, experts_per_token=0,
    )
    x, _ = stack_forward(enc_cfg, enc["layers"], x, positions, kind="full")
    x = rms_norm(x, enc["final_norm"], cfg.rms_eps)
    # per-decoder-layer cross projections
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    b, s, _ = x.shape

    def proj(cross_p):
        k = (x @ cross_p["wk"]).reshape(b, s, kv, hd)
        v = (x @ cross_p["wv"]).reshape(b, s, kv, hd)
        return k, v

    ek, ev = jax.vmap(proj)(params["layers"]["cross"])  # (L,B,S,K,hd)
    return ek, ev


def _prepare_inputs(cfg: ModelConfig, params: Dict, batch: Dict):
    """Embed tokens (+ modality prefixes); returns (x, positions, kind, prefix_len, enc_kv)."""
    kind = "causal"
    prefix_len = 0
    enc_kv = None
    if cfg.family == AUDIO:
        enc_kv = encoder_forward(cfg, params, batch["frames"])
        x = _embed(cfg, params, batch["tokens"])
        x = x + sinusoidal_positions(x.shape[1], cfg.d_model).astype(x.dtype)[None]
        positions = jnp.arange(x.shape[1])
        return x, positions, kind, prefix_len, enc_kv
    if cfg.family == VLM and "prefix_emb" in batch:
        pre = (batch["prefix_emb"] @ params["prefix_proj"]).astype(_dtype(cfg))
        tok = _embed(cfg, params, batch["tokens"])
        x = jnp.concatenate([pre, tok], axis=1)
        kind = "prefix"
        prefix_len = pre.shape[1]
    else:
        x = _embed(cfg, params, batch["tokens"])
    positions = jnp.arange(x.shape[1])
    return x, positions, kind, prefix_len, enc_kv


def forward_train(cfg: ModelConfig, params: Dict, batch: Dict) -> jax.Array:
    """Returns logits aligned with batch['labels']."""
    x, positions, kind, prefix_len, enc_kv = _prepare_inputs(cfg, params, batch)
    x, _ = stack_forward(
        cfg, params["layers"], x, positions, kind=kind, prefix_len=prefix_len,
        enc_kv_layers=enc_kv, dense_layers=params.get("dense_layers"),
    )
    if cfg.family == VLM and prefix_len:
        x = x[:, prefix_len:]
    return _logits(cfg, params, x)


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def init_caches(cfg: ModelConfig, batch: int, cache_len: int) -> Dict:
    """Zero caches for decode: {'sub<i>': tree with leading (num_groups,)},
    and {'dense_layers': ...} with leading (first_k_dense,).

    Attention keeps K and V per head; latent attention keeps one row a
    position, ``latent`` (..., cache_len, kv_lora_rank + qk_rope_head_dim)
    = [RMSNorm(c), roped k_pe]."""
    dtype = _dtype(cfg)
    gp = cfg.layer_group
    ng = cfg.stack_layers // gp
    windows = _group_windows(cfg)
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    caches: Dict[str, Any] = {}
    if cfg.is_mla:
        width = cfg.kv_lora_rank + cfg.qk_rope_head_dim
        caches["sub0"] = {"latent": jnp.zeros((ng, batch, cache_len, width), dtype)}
        if cfg.first_k_dense:
            caches["dense_layers"] = {"latent": jnp.zeros((cfg.first_k_dense, batch, cache_len, width), dtype)}
        return caches
    for i in range(gp):
        sub: Dict[str, Any] = {}
        if cfg.family != SSM:
            w = windows[i]
            clen = min(cache_len, w) if w else cache_len
            sub["k"] = jnp.zeros((ng, batch, clen, kv, hd), dtype)
            sub["v"] = jnp.zeros((ng, batch, clen, kv, hd), dtype)
        if cfg.family in (SSM, HYBRID):
            sub["conv"] = jnp.zeros((ng, batch, cfg.ssm_conv - 1, cfg.d_inner), dtype)
            sub["ssm"] = jnp.zeros((ng, batch, cfg.d_inner, cfg.ssm_state), jnp.float32)
        caches[f"sub{i}"] = sub
    return caches


def abstract_caches(cfg: ModelConfig, batch: int, cache_len: int):
    return jax.eval_shape(lambda: init_caches(cfg, batch, cache_len))


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------

def forward_prefill(cfg: ModelConfig, params: Dict, batch: Dict, cache_len: int = 0):
    """Run the prompt; returns (last-position logits, caches, [enc_kv])."""
    x, positions, kind, prefix_len, enc_kv = _prepare_inputs(cfg, params, batch)
    cache_len = cache_len or x.shape[1]
    x, caches = stack_forward(
        cfg, params["layers"], x, positions, kind=kind, prefix_len=prefix_len,
        enc_kv_layers=enc_kv, collect_caches=True, cache_len=cache_len,
        dense_layers=params.get("dense_layers"),
    )
    logits = _logits(cfg, params, x[:, -1:])[:, 0]
    return logits, caches, enc_kv


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def _layer_decode(cfg: ModelConfig, p: Dict, x, cache: Dict, pos, window: int, enc_kv, live=None):
    """One layer for one token; returns (x, new cache, experts reached by
    the slots ``live`` marks)."""
    new_cache: Dict[str, Any] = {}
    reached = jnp.int32(0)
    h = rms_norm(x, p["attn_norm"], cfg.rms_eps)
    mix = jnp.zeros_like(x)
    if "attn" in p and cfg.is_mla:
        out, new_cache["latent"] = attn_mod.mla_decode_attention(
            p["attn"], h, cache["latent"], pos, num_heads=cfg.num_heads, nope=cfg.qk_nope_head_dim,
            rope=cfg.qk_rope_head_dim, v_dim=cfg.v_head_dim, rope_theta=cfg.rope_theta, eps=cfg.rms_eps,
        )
        mix = mix + out
    elif "attn" in p:
        out, ck, cv = attn_mod.decode_attention(
            p["attn"], h, cache["k"], cache["v"], pos,
            num_heads=cfg.num_heads, num_kv=cfg.num_kv_heads,
            head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta,
            window=window, softcap=cfg.attn_logit_softcap,
        )
        mix = mix + out
        new_cache["k"], new_cache["v"] = ck, cv
    if "mamba" in p:
        y, conv_st, ssm_st = ssm_mod.mamba_decode(
            p["mamba"], h, cache["conv"], cache["ssm"], cfg
        )
        mix = mix + y
        new_cache["conv"], new_cache["ssm"] = conv_st, ssm_st
    x = x + mix
    if "cross" in p and enc_kv is not None:
        hc = rms_norm(x, p["cross_norm"], cfg.rms_eps)
        x = x + attn_mod.cross_decode_attention(
            p["cross"], hc, enc_kv[0], enc_kv[1],
            num_heads=cfg.num_heads, num_kv=cfg.num_kv_heads,
            head_dim=cfg.resolved_head_dim,
        )
    if "ffn_norm" in p:
        hf = rms_norm(x, p["ffn_norm"], cfg.rms_eps)
        if "moe" in p:
            from . import moe as moe_mod

            y, top_e = moe_mod.moe_layer(p["moe"], hf, cfg)
            x = x + y
            reached = moe_mod.experts_reached(top_e, cfg.num_experts, live)
        else:
            from .layers import mlp_apply

            x = x + mlp_apply(p["mlp"], hf, cfg.act)
    return x, new_cache, reached


def forward_decode(
    cfg: ModelConfig,
    params: Dict,
    caches: Dict,
    token: jax.Array,  # (B, 1) int32
    pos: jax.Array,  # (B,) absolute position of `token`
    enc_kv=None,  # (L,B,Senc,K,hd) x2 for enc-dec
    stats: bool = False,
    live=None,  # (B,) bool: the slots that carry a real token (default all)
):
    """One decode step; returns (logits (B,V), new caches), and with
    ``stats`` also the call's counts (int32 scalars) over the ``live``
    slots alone:

    * ``moe.experts_reached``: distinct experts given at least one routed
      live slot, summed over the MoE layers;
    * ``mla.latent_positions``: cached positions attended (the token's own
      included), summed over live slots and latent-attention layers."""
    x = _embed(cfg, params, token)
    if cfg.family == AUDIO:
        # absolute sinusoidal position for the new token
        table = sinusoidal_positions(int(caches["sub0"]["k"].shape[2]) + 1, cfg.d_model)
        x = x + table[pos][:, None].astype(x.dtype)
    new_dense = []
    if cfg.first_k_dense:
        dcfg = dense_cfg(cfg)
        for i in range(cfg.first_k_dense):
            p_i = jax.tree.map(lambda t: t[i], params["dense_layers"])
            c_i = jax.tree.map(lambda t: t[i], caches["dense_layers"])
            x, nc, _ = _layer_decode(dcfg, p_i, x, c_i, pos, 0, None)
            new_dense.append(nc)
    gp = cfg.layer_group
    windows = _group_windows(cfg)
    stacked = _stack_params(cfg, params["layers"])
    xs = [stacked, {k: c for k, c in caches.items() if k != "dense_layers"}]
    ng = cfg.stack_layers // gp
    if enc_kv is not None:
        ek = enc_kv[0].reshape(ng, gp, *enc_kv[0].shape[1:])
        ev = enc_kv[1].reshape(ng, gp, *enc_kv[1].shape[1:])
        xs.append((ek, ev))

    def body(xcarry, xs_g):
        if enc_kv is not None:
            gparams, caches_g, (ekg, evg) = xs_g
        else:
            gparams, caches_g = xs_g
            ekg = evg = None
        new_g = {}
        n = jnp.int32(0)
        for i in range(gp):
            p_i = jax.tree.map(lambda t: t[i], gparams)
            ekv = (ekg[i], evg[i]) if ekg is not None else None
            xcarry, nc, r = _layer_decode(cfg, p_i, xcarry, caches_g[f"sub{i}"], pos, windows[i], ekv, live)
            new_g[f"sub{i}"] = nc
            n = n + r
        return xcarry, (new_g, n)

    from repro.utils.costmode import scan_unroll

    x, (new_caches, reached_g) = jax.lax.scan(body, x, tuple(xs), unroll=scan_unroll(ng))
    if new_dense:
        new_caches["dense_layers"] = jax.tree.map(lambda *c: jnp.stack(c), *new_dense)
    logits = _logits(cfg, params, x)[:, 0]
    if not stats:
        return logits, new_caches
    counts = {"moe.experts_reached": jnp.sum(reached_g, dtype=jnp.int32), "mla.latent_positions": jnp.int32(0)}
    if cfg.is_mla:
        s_cache = caches["sub0"]["latent"].shape[2]
        attended = jnp.minimum(pos + 1, s_cache)
        if live is not None:
            attended = jnp.where(live, attended, 0)
        counts["mla.latent_positions"] = cfg.num_layers * jnp.sum(attended, dtype=jnp.int32)
    return logits, new_caches, counts
