"""Sharding rules: logical activation/param axes -> mesh axes.

Parallelism layout (see DESIGN.md §6):

* batch        -> (pod, data)   (pod axis only on the multi-pod mesh)
* FSDP         -> data          (or (pod, data) when cfg.fsdp_over_pod —
                                 nemotron-340B's optimizer state needs it)
* tensor       -> model         (heads / ff / vocab / experts / d_inner)
* context      -> model         (long-context decode KV cache sequence dim)

Activations are annotated through :func:`constrain`, a no-op unless a
:class:`ShardingEnv` is active — smoke tests run the exact same model
code with no mesh at all.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _canon_entry(mesh_axes):
    """Canonical PartitionSpec entry: () -> None, ("x",) -> "x".

    Newer jax normalizes singleton tuples inside PartitionSpec itself;
    0.4.x keeps them verbatim, so normalize here for version-stable specs.
    """
    if not mesh_axes:
        return None
    if isinstance(mesh_axes, tuple) and len(mesh_axes) == 1:
        return mesh_axes[0]
    return mesh_axes


@dataclasses.dataclass(frozen=True)
class MeshRules:
    """Logical-axis -> mesh-axis mapping."""

    batch: Tuple[str, ...] = ("data",)
    fsdp: Tuple[str, ...] = ("data",)
    tensor: Tuple[str, ...] = ("model",)
    context: Tuple[str, ...] = ()  # set to ("model",) for context-parallel decode

    def spec(self, *logical: Optional[str]) -> P:
        out = []
        for ax in logical:
            if ax is None:
                out.append(None)
            else:
                mesh_axes = getattr(self, ax)
                out.append(_canon_entry(mesh_axes))
        return P(*out)


# Logical names used by model code for activations:
#   act_batch, act_seq, act_heads, act_ff, act_vocab, act_embed, act_experts, act_kv_seq
_ACT_AXIS = {
    "act_batch": "batch",
    "act_seq": None,
    "act_kv_seq": "context",
    "act_heads": "tensor",
    "act_ff": "tensor",
    "act_vocab": "tensor",
    "act_embed": None,
    "act_experts": "tensor",
    "none": None,
}


@dataclasses.dataclass
class ShardingEnv:
    mesh: Mesh
    rules: MeshRules


_STATE = threading.local()


def current_env() -> Optional[ShardingEnv]:
    return getattr(_STATE, "env", None)


@contextlib.contextmanager
def sharding_env(mesh: Mesh, rules: MeshRules):
    prev = current_env()
    _STATE.env = ShardingEnv(mesh, rules)
    try:
        yield _STATE.env
    finally:
        _STATE.env = prev


def constrain(x: jax.Array, *act_axes: Optional[str]) -> jax.Array:
    """with_sharding_constraint on logical activation axes (no-op w/o env)."""
    env = current_env()
    if env is None:
        return x
    logical = [_ACT_AXIS.get(a) if a is not None else None for a in act_axes]
    spec = env.rules.spec(*logical)
    return jax.lax.with_sharding_constraint(x, NamedSharding(env.mesh, spec))


def make_rules(mesh: Mesh, fsdp_over_pod: bool = False, context_parallel: bool = False) -> MeshRules:
    axes = mesh.axis_names
    multi_pod = "pod" in axes
    batch = ("pod", "data") if multi_pod else ("data",)
    fsdp = (("pod", "data") if (multi_pod and fsdp_over_pod) else ("data",))
    return MeshRules(
        batch=batch,
        fsdp=fsdp,
        tensor=("model",),
        context=("model",) if context_parallel else (),
    )


# ---------------------------------------------------------------------------
# Parameter sharding: leaf-name pattern -> logical axes per dim (trailing dims)
# ---------------------------------------------------------------------------

# name -> logical axes for the *trailing* dims of the leaf (leading stacked
# layer dims get None automatically).
_PARAM_RULES = {
    # embeddings
    "table": ("tensor", "fsdp"),  # (V, d)
    "unembed": ("fsdp", "tensor"),  # (d, V)
    "prefix_proj": ("fsdp", None),  # (d_in, d)
    "pos_embed": (None, "fsdp"),  # (S, d)
    # attention
    "wq": ("fsdp", "tensor"),
    "wk": ("fsdp", "tensor"),
    "wv": ("fsdp", "tensor"),
    "wo": ("tensor", "fsdp"),
    "xq": ("fsdp", "tensor"),
    "xk": ("fsdp", "tensor"),
    "xv": ("fsdp", "tensor"),
    "xo": ("tensor", "fsdp"),
    # latent attention: the shared down-projection, the per-head up-projection
    "wkv_a": ("fsdp", None),  # (d, kv_lora_rank + rope)
    "wkv_b": (None, "tensor"),  # (kv_lora_rank, H * (nope + v))
    # dense mlp
    "wi": ("fsdp", "tensor"),
    "wg": ("fsdp", "tensor"),
    # moe (leaves live under 'moe' and get expert-leading rules below)
    "router": ("fsdp", None),  # (d, E)
    # mamba
    "in_proj": ("fsdp", "tensor"),  # (d, 2*di)
    "conv_w": ("tensor", None),  # (di, k)
    "x_proj": ("tensor", None),  # (di, r+2s)
    "dt_proj": (None, "tensor"),  # (r, di)
    "dt_bias": ("tensor",),  # (di,)
    "A_log": ("tensor", None),  # (di, s)
    "D": ("tensor",),  # (di,)
    "out_proj": ("tensor", "fsdp"),  # (di, d)
}

_MOE_RULES = {
    # (E, d, ff_e) / (E, ff_e, d): experts over tensor, d over fsdp
    "wi": ("tensor", "fsdp", None),
    "wg": ("tensor", "fsdp", None),
    "wo": ("tensor", None, "fsdp"),
    "router": ("fsdp", None),
}


def param_spec(path: Tuple, leaf) -> P:
    """PartitionSpec for one parameter leaf given its tree path."""
    keys = [getattr(k, "key", getattr(k, "name", None)) for k in path]
    name = keys[-1]
    in_moe = "moe" in keys and "shared" not in keys
    rules = _MOE_RULES if in_moe else _PARAM_RULES
    logical = rules.get(name)
    if logical is None:
        if name in ("scale", "attn_norm", "ffn_norm", "cross_norm", "final_norm", "norm", "kv_norm"):
            logical = (None,) * 1
        else:
            logical = ()
    ndim = leaf.ndim
    pad = ndim - len(logical)
    if pad < 0:  # leaf smaller than rule (e.g. reduced config squeezed) — replicate
        return P()
    return tuple([None] * pad + list(logical)), name


def param_pspec_tree(params, rules: MeshRules):
    """Tree of PartitionSpec matching ``params``."""

    def one(path, leaf):
        logical, _ = param_spec(path, leaf)
        # map logical to mesh axes
        axes = []
        for ax in logical:
            if ax is None:
                axes.append(None)
            else:
                axes.append(_canon_entry(getattr(rules, ax)))
        return P(*axes)

    return jax.tree_util.tree_map_with_path(one, params)


def sanitize_spec(spec: P, shape, mesh: Mesh) -> P:
    """Drop sharding on dims not divisible by the mesh-axis degree.

    jit in_shardings require exact divisibility (unlike constraint
    annotations); e.g. hymba's vocab 32001 and whisper's 51866 cannot
    shard 16-way — those dims fall back to replicated.
    """
    import math as _math

    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    out = []
    for i, entry in enumerate(tuple(spec)):
        if entry is None or i >= len(shape):
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        deg = _math.prod(sizes[a] for a in axes)
        out.append(entry if shape[i] % deg == 0 else None)
    return P(*out)


def sanitized_sharding_tree(tree, spec_tree, mesh: Mesh):
    """NamedSharding tree from (abstract) value tree + PartitionSpec tree."""
    return jax.tree.map(
        lambda leaf, s: NamedSharding(mesh, sanitize_spec(s, leaf.shape, mesh)),
        tree,
        spec_tree,
    )


def param_sharding_tree(params, mesh: Mesh, rules: MeshRules):
    specs = param_pspec_tree(params, rules)
    return sanitized_sharding_tree(params, specs, mesh)
