"""ShapeDtypeStruct input stand-ins + sharding specs for every
(arch x shape x mode) cell — the dry-run's contract with the model.

Nothing here allocates device memory.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig, TrainConfig, AUDIO, VLM
from repro.models import abstract_caches
from repro.parallel.sharding import (
    MeshRules,
    param_pspec_tree,
    sanitize_spec,
    sanitized_sharding_tree,
)
from repro.train.steps import abstract_train_state


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


# ---------------------------------------------------------------------------
# abstract inputs
# ---------------------------------------------------------------------------

def batch_specs(cfg: ModelConfig, shape: ShapeConfig, mode: str) -> Dict[str, Any]:
    """Abstract batch for train/prefill."""
    b, s = shape.global_batch, shape.seq_len
    dt = cfg.dtype
    out: Dict[str, Any] = {}
    if cfg.family == VLM:
        p = cfg.num_prefix_tokens
        out["prefix_emb"] = sds((b, p, cfg.d_model), dt)
        out["tokens"] = sds((b, s - p), jnp.int32)
        if mode == "train":
            out["labels"] = sds((b, s - p), jnp.int32)
        return out
    if cfg.family == AUDIO:
        out["frames"] = sds((b, cfg.encoder_seq, cfg.d_model), dt)
    out["tokens"] = sds((b, s), jnp.int32)
    if mode == "train":
        out["labels"] = sds((b, s), jnp.int32)
    return out


def decode_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """Abstract (caches, token, pos[, enc_kv]) for one decode step with a
    cache of ``seq_len``."""
    b, s = shape.global_batch, shape.seq_len
    caches = abstract_caches(cfg, b, s)
    out = {
        "caches": caches,
        "token": sds((b, 1), jnp.int32),
        "pos": sds((b,), jnp.int32),
    }
    if cfg.is_encoder_decoder:
        kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        ek = sds((cfg.num_layers, b, cfg.encoder_seq, kv, hd), cfg.dtype)
        out["enc_kv"] = (ek, ek)
    return out


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """Public entry: abstract model inputs for the cell's mode."""
    if shape.mode in ("train", "prefill"):
        return batch_specs(cfg, shape, shape.mode)
    return decode_specs(cfg, shape)


# ---------------------------------------------------------------------------
# shardings
# ---------------------------------------------------------------------------

def batch_pspec(rules: MeshRules) -> P:
    return P(rules.batch)


def batch_shardings(cfg: ModelConfig, shape: ShapeConfig, mode: str, mesh: Mesh, rules: MeshRules):
    bp = rules.batch
    specs = batch_specs(cfg, shape, mode)
    out: Dict[str, Any] = {}
    for name, leaf in specs.items():
        spec = P(*([bp] + [None] * (len(leaf.shape) - 1)))
        out[name] = NamedSharding(mesh, sanitize_spec(spec, leaf.shape, mesh))
    return out


def cache_pspec_tree(cfg: ModelConfig, caches, rules: MeshRules):
    """PartitionSpecs for decode caches.

    KV and latent sequence dims are context-parallel over the model axis
    (rules.context); SSM/conv states shard d_inner over the model axis.
    """
    ctx = rules.context if rules.context else None
    bp = rules.batch

    def one(path, leaf):
        name = str(getattr(path[-1], "key", path[-1]))
        if name in ("k", "v"):
            return P(None, bp, ctx, None, None)
        if name == "latent":
            return P(None, bp, ctx, None)
        if name == "conv":
            return P(None, bp, None, rules.tensor)
        if name == "ssm":
            return P(None, bp, rules.tensor, None)
        return P()

    return jax.tree_util.tree_map_with_path(one, caches)


def decode_shardings(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh, rules: MeshRules, caches):
    dspec = decode_specs(cfg, shape)
    san = lambda spec, leaf: NamedSharding(mesh, sanitize_spec(spec, leaf.shape, mesh))
    out = {
        "caches": sanitized_sharding_tree(caches, cache_pspec_tree(cfg, caches, rules), mesh),
        "token": san(P(rules.batch, None), dspec["token"]),
        "pos": san(P(rules.batch), dspec["pos"]),
    }
    if cfg.is_encoder_decoder:
        ek = dspec["enc_kv"][0]
        ekv = san(P(None, rules.batch, None, None, None), ek)
        out["enc_kv"] = (ekv, ekv)
    return out


def _cfg_spec_overrides(cfg: ModelConfig, pspecs, rules: MeshRules):
    """Config-aware sharding-rule overrides (beyond the name-based rules).

    replicate_kv_proj: with MQA/GQA where kv_heads < tensor degree, a
    tensor-sharded wk/wv splits a single head across devices and XLA
    reshards K/V with collective-permutes every layer (measured: 34 GB/dev
    on paligemma prefill_32k).  Replicating the small output dim removes
    the storm at negligible flops cost.
    """
    if not cfg.replicate_kv_proj:
        return pspecs
    fsdp = rules.fsdp if rules.fsdp else None

    def fix(block):
        for w in ("wk", "wv"):
            if isinstance(block, dict) and w in block:
                nd = len(tuple(block[w]))
                block[w] = P(*([None] * (nd - 2) + [fsdp, None]))

    for scope in (pspecs.get("layers", {}),):
        for key in ("attn", "cross"):
            if key in scope:
                fix(scope[key])
    if "encoder" in pspecs and "attn" in pspecs["encoder"].get("layers", {}):
        fix(pspecs["encoder"]["layers"]["attn"])
    return pspecs


def state_shardings(cfg: ModelConfig, tcfg: TrainConfig, mesh: Mesh, rules: MeshRules):
    """Shardings for the full train state (params fp32 + opt m/v [+ err])."""
    state = abstract_train_state(cfg, tcfg)
    pspecs = _cfg_spec_overrides(cfg, param_pspec_tree(state["params"], rules), rules)
    tree_ns = lambda t: sanitized_sharding_tree(state["params"], t, mesh)
    shardings = {
        "step": NamedSharding(mesh, P()),
        "params": tree_ns(pspecs),
        "opt": {"m": tree_ns(pspecs), "v": tree_ns(pspecs)},
    }
    if "err" in state:
        shardings["err"] = tree_ns(pspecs)
    return state, shardings


def params_shardings(cfg: ModelConfig, mesh: Mesh, rules: MeshRules):
    from repro.models import abstract_params

    params = abstract_params(cfg)
    pspecs = _cfg_spec_overrides(cfg, param_pspec_tree(params, rules), rules)
    return params, sanitized_sharding_tree(params, pspecs, mesh)
