"""Decoder stacks for all 10 assigned architectures.

One functional implementation; families differ in the per-layer mixer
(attention / latent attention / mamba / both) and FFN (dense MLP /
merge-path MoE).  Layers are scanned in homogeneous *groups*
(``cfg.layer_group``): gemma3 scans groups of 6 (5 sliding + 1 global),
hymba groups of 8 (7 sliding + 1 global), everything else groups of 1 —
keeping compiled HLO size O(group), not O(L).  Leading dense layers
(``cfg.first_k_dense``) live in ``params["dense_layers"]`` and run one by
one before the scan over the ``cfg.stack_layers`` uniform layers.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, SSM, HYBRID, VLM, AUDIO
from repro.parallel.sharding import constrain
from . import attention as attn_mod
from . import moe as moe_mod
from . import ssm as ssm_mod
from .layers import dense_init, mlp_apply, mlp_init, rms_norm, sinusoidal_positions


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def dense_cfg(cfg: ModelConfig) -> ModelConfig:
    """A leading dense layer's configuration: the expert FFN replaced by a
    dense SwiGLU of ``cfg.dense_ff``."""
    return dataclasses.replace(cfg, num_experts=0, experts_per_token=0, shared_expert_ff=0,
                               d_ff=cfg.dense_ff, first_k_dense=0)


def _attn_init(key, cfg: ModelConfig, dtype) -> Dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, kv = cfg.num_heads, cfg.num_kv_heads
    k1, k2, k3, k4 = jax.random.split(key, 4)
    if cfg.is_mla:
        r, nope, rope, v = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        return {
            "wq": dense_init(k1, (d, h * (nope + rope)), d, dtype),
            "wkv_a": dense_init(k2, (d, r + rope), d, dtype),
            "kv_norm": jnp.zeros((r,), jnp.float32),
            "wkv_b": dense_init(k3, (r, h * (nope + v)), r, dtype),
            "wo": dense_init(k4, (h * v, d), h * v, dtype),
        }
    return {
        "wq": dense_init(k1, (d, h * hd), d, dtype),
        "wk": dense_init(k2, (d, kv * hd), d, dtype),
        "wv": dense_init(k3, (d, kv * hd), d, dtype),
        "wo": dense_init(k4, (h * hd, d), h * hd, dtype),
    }


def _layer_init(key, cfg: ModelConfig, dtype, cross: bool) -> Dict:
    d = cfg.d_model
    keys = jax.random.split(key, 6)
    p: Dict[str, Any] = {"attn_norm": jnp.zeros((d,), jnp.float32)}
    if cfg.family != SSM:
        p["attn"] = _attn_init(keys[0], cfg, dtype)
    if cfg.family in (SSM, HYBRID):
        p["mamba"] = ssm_mod.mamba_init(keys[1], cfg, dtype)
    if cross:
        p["cross"] = _attn_init(keys[2], cfg, dtype)
        p["cross_norm"] = jnp.zeros((d,), jnp.float32)
    if cfg.num_experts:
        p["moe"] = moe_mod.moe_init(keys[3], cfg, dtype)
        p["ffn_norm"] = jnp.zeros((d,), jnp.float32)
    elif cfg.d_ff:
        p["mlp"] = mlp_init(keys[4], d, cfg.d_ff, cfg.act, dtype)
        p["ffn_norm"] = jnp.zeros((d,), jnp.float32)
    return p


def init_params(cfg: ModelConfig, key: jax.Array) -> Dict:
    """Full parameter pytree (fp32 masters are handled by the optimizer)."""
    dtype = _dtype(cfg)
    d = cfg.d_model
    keys = jax.random.split(key, 8)
    params: Dict[str, Any] = {
        "embed": {"table": dense_init(keys[0], (cfg.vocab_size, d), d, dtype)},
        "final_norm": jnp.zeros((d,), jnp.float32),
    }
    cross = cfg.is_encoder_decoder
    lkeys = jax.random.split(keys[1], cfg.stack_layers)
    params["layers"] = jax.vmap(lambda k: _layer_init(k, cfg, dtype, cross))(lkeys)
    if cfg.first_k_dense:
        dkeys = jax.random.split(keys[6], cfg.first_k_dense)
        params["dense_layers"] = jax.vmap(lambda k: _layer_init(k, dense_cfg(cfg), dtype, False))(dkeys)
    if not cfg.tie_embeddings:
        params["unembed"] = dense_init(keys[2], (d, cfg.vocab_size), d, dtype)
    if cfg.num_prefix_tokens:
        params["prefix_proj"] = dense_init(keys[3], (d, d), d, dtype)
    if cfg.is_encoder_decoder:
        ekeys = jax.random.split(keys[4], cfg.encoder_layers)
        enc_cfg = cfg  # same dims
        params["encoder"] = {
            "layers": jax.vmap(lambda k: _layer_init(k, enc_cfg, dtype, False))(ekeys),
            "final_norm": jnp.zeros((d,), jnp.float32),
            "frame_proj": dense_init(keys[5], (d, d), d, dtype),
        }
    return params


def abstract_params(cfg: ModelConfig) -> Dict:
    """ShapeDtypeStruct tree (no allocation) — dry-run / sharding planning."""
    return jax.eval_shape(lambda k: init_params(cfg, k), jax.random.key(0))


# ---------------------------------------------------------------------------
# layer forward (train / prefill)
# ---------------------------------------------------------------------------

def _layer_fwd(
    cfg: ModelConfig,
    p: Dict,
    x: jax.Array,
    positions: jax.Array,
    *,
    window: int,
    kind: str,
    prefix_len: int,
    enc_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
    collect_cache: bool = False,
    cache_len: int = 0,
):
    cache = {}
    h = rms_norm(x, p["attn_norm"], cfg.rms_eps)
    mix = jnp.zeros_like(x)
    if "attn" in p and cfg.is_mla:
        out, latent = attn_mod.mla_attention(
            p["attn"], h, num_heads=cfg.num_heads, nope=cfg.qk_nope_head_dim, rope=cfg.qk_rope_head_dim,
            v_dim=cfg.v_head_dim, rope_theta=cfg.rope_theta, eps=cfg.rms_eps, positions=positions,
            kind=kind, prefix_len=prefix_len, chunk=cfg.attn_chunk, softcap=cfg.attn_logit_softcap,
            force_blockwise=cfg.train_attn_blockwise and x.shape[1] > 1024,
        )
        mix = mix + out
        if collect_cache:
            take = min(x.shape[1], cache_len)
            cache["latent"] = jnp.zeros((x.shape[0], cache_len, latent.shape[-1]), latent.dtype).at[
                :, positions[-take:] % cache_len].set(latent[:, -take:])
    elif "attn" in p:
        akw = dict(
            num_heads=cfg.num_heads,
            num_kv=cfg.num_kv_heads,
            head_dim=cfg.resolved_head_dim,
            rope_theta=cfg.rope_theta,
            positions=positions,
            kind=kind,
            window=window,
            prefix_len=prefix_len,
            chunk=cfg.attn_chunk,
            softcap=cfg.attn_logit_softcap,
            force_blockwise=cfg.train_attn_blockwise and x.shape[1] > 1024,
        )
        mix = mix + attn_mod.attention(p["attn"], h, **akw)
        if collect_cache:
            b, s, _ = h.shape
            kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
            k = (h @ p["attn"]["wk"]).reshape(b, s, kv, hd)
            v = (h @ p["attn"]["wv"]).reshape(b, s, kv, hd)
            if cfg.rope_theta > 0:
                sin, cos = attn_mod.make_rope(positions, hd, cfg.rope_theta)
                k = attn_mod.apply_rope(k, sin, cos)
            w = window if window > 0 else 0
            clen = min(cache_len, w) if w else cache_len
            take = min(s, clen)
            kw = jnp.zeros((b, clen, kv, hd), k.dtype)
            slots = (positions[-take:] % clen) if w else positions[-take:] % max(clen, 1)
            kw = kw.at[:, slots].set(k[:, -take:])
            vw = jnp.zeros((b, clen, kv, hd), v.dtype)
            vw = vw.at[:, slots].set(v[:, -take:])
            cache["k"], cache["v"] = kw, vw
    if "mamba" in p:
        if collect_cache:
            y, conv_st, ssm_st = _mamba_with_state(p["mamba"], h, cfg)
            cache["conv"], cache["ssm"] = conv_st, ssm_st
            mix = mix + y
        else:
            mix = mix + ssm_mod.mamba_forward(p["mamba"], h, cfg)
    x = x + mix
    if "cross" in p and enc_kv is not None:
        hc = rms_norm(x, p["cross_norm"], cfg.rms_eps)
        x = x + attn_mod.attention(
            p["cross"],
            hc,
            num_heads=cfg.num_heads,
            num_kv=cfg.num_kv_heads,
            head_dim=cfg.resolved_head_dim,
            rope_theta=0.0,
            positions=positions,
            kv=enc_kv,
        )
    if "ffn_norm" in p:
        hf = rms_norm(x, p["ffn_norm"], cfg.rms_eps)
        if "moe" in p:
            x = x + moe_mod.moe_apply(p["moe"], hf, cfg)
        else:
            x = x + mlp_apply(p["mlp"], hf, cfg.act)
    return x, cache


def _mamba_with_state(p, h, cfg):
    """mamba_forward that also returns final (conv, ssm) states for caching."""
    b, s, d = h.shape
    di, st, r = cfg.d_inner, cfg.ssm_state, cfg.dt_rank
    xz = h @ p["in_proj"]
    xin, z = jnp.split(xz, 2, axis=-1)
    xc, _ = ssm_mod._causal_conv(xin, p["conv_w"], None)
    kk = cfg.ssm_conv
    conv_state = xin[:, -(kk - 1) :] if s >= kk - 1 else jnp.pad(xin, ((0, 0), (kk - 1 - s, 0), (0, 0)))
    xc = jax.nn.silu(xc)
    proj = xc @ p["x_proj"]
    dt_r, bmat, cmat = jnp.split(proj, [r, r + st], axis=-1)
    dt = jax.nn.softplus(dt_r @ p["dt_proj"] + p["dt_bias"]).astype(jnp.float32)
    a = -jnp.exp(p["A_log"])
    y, h_final = ssm_mod.ssm_apply(dt, xc, bmat, cmat, a, cfg)
    y = (y + p["D"][None, None] * xc.astype(jnp.float32)).astype(h.dtype)
    y = y * jax.nn.silu(z)
    return y @ p["out_proj"], conv_state, h_final


def _group_windows(cfg: ModelConfig):
    """Per-sublayer sliding window (0 = global) inside one scan group."""
    gp = cfg.layer_group
    if gp == 1:
        return (cfg.sliding_window,) if cfg.sliding_window and not cfg.global_every else (0,)
    return tuple(cfg.sliding_window if i < gp - 1 else 0 for i in range(gp))


def _stack_params(cfg: ModelConfig, layers: Dict):
    gp = cfg.layer_group
    ng = cfg.stack_layers // gp
    return jax.tree.map(lambda t: t.reshape(ng, gp, *t.shape[1:]), layers)


def stack_forward(
    cfg: ModelConfig,
    layers: Dict,
    x: jax.Array,
    positions: jax.Array,
    *,
    kind: str = "causal",
    prefix_len: int = 0,
    enc_kv_layers=None,  # (L, B, Senc, K, hd) x2 for enc-dec decoders
    collect_caches: bool = False,
    cache_len: int = 0,
    dense_layers: Optional[Dict] = None,  # leading dense layers, stacked (first_k_dense, ...)
):
    """Run the leading dense layers, then scan the layer stack; optionally
    collect decode caches (the dense layers' under ``"dense_layers"``)."""
    dense_caches = []
    if dense_layers is not None:
        dcfg = dense_cfg(cfg)

        def dense_layer(xc, p_i):
            return _layer_fwd(dcfg, p_i, xc, positions, window=0, kind=kind, prefix_len=prefix_len,
                              collect_cache=collect_caches, cache_len=cache_len)

        fn = jax.checkpoint(dense_layer) if cfg.remat else dense_layer
        for i in range(cfg.first_k_dense):
            x, cache = fn(x, jax.tree.map(lambda t: t[i], dense_layers))
            dense_caches.append(cache)
    gp = cfg.layer_group
    windows = _group_windows(cfg)
    stacked = _stack_params(cfg, layers)
    if enc_kv_layers is not None:
        ek, ev = enc_kv_layers
        ng = cfg.stack_layers // gp
        ek = ek.reshape(ng, gp, *ek.shape[1:])
        ev = ev.reshape(ng, gp, *ev.shape[1:])
        xs = (stacked, ek, ev)
    else:
        xs = (stacked,)

    def body(xcarry, xs_g):
        if enc_kv_layers is not None:
            gparams, ekg, evg = xs_g
        else:
            (gparams,) = xs_g
            ekg = evg = None
        caches_g = {}
        for i in range(gp):
            p_i = jax.tree.map(lambda t: t[i], gparams)
            enc_kv = (ekg[i], evg[i]) if ekg is not None else None
            xcarry, cache = _layer_fwd(
                cfg,
                p_i,
                xcarry,
                positions,
                window=windows[i],
                kind=kind,
                prefix_len=prefix_len,
                enc_kv=enc_kv,
                collect_cache=collect_caches,
                cache_len=cache_len,
            )
            if collect_caches:
                caches_g[f"sub{i}"] = cache
        return xcarry, caches_g if collect_caches else None

    if cfg.remat:
        if cfg.remat_policy == "dots":
            body_fn = jax.checkpoint(
                body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            )
        else:
            body_fn = jax.checkpoint(body)
    else:
        body_fn = body
    from repro.utils.costmode import scan_unroll

    ng = cfg.stack_layers // gp
    x, caches = jax.lax.scan(body_fn, x, xs, unroll=scan_unroll(ng))
    if collect_caches and dense_caches:
        caches["dense_layers"] = jax.tree.map(lambda *c: jnp.stack(c), *dense_caches)
    return x, caches
