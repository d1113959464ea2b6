"""Moonlight-16B-A3B (a DeepSeek-V3 block) as the repository computes it:
weights made from the seed, a plain float32 reference of the forward pass,
and the mapping of this file's keys onto the program's configuration.

The block, for token t and head i: c_t = RMSNorm(W_DKV x_t) over
``kv_lora_rank``; k^R_t = RoPE(W_KR x_t) over ``qk_rope_head_dim``, one
for all heads; [q^C, q^R]_{t,i} = W_Q x_t with RoPE on q^R; k^C_{s,i} =
W_UK,i c_s and v_{s,i} = W_UV,i c_s; score = (q^C.k^C + q^R.k^R) /
sqrt(qk_nope_head_dim + qk_rope_head_dim); out = W_O concat_i(sum_s p_s
v_{s,i}).  Layer 0 then has a dense SwiGLU; the others route over
``n_routed_experts`` by s = sigmoid(x W_r), choose the top
``num_experts_per_tok`` of s + b (b the per-expert correction bias),
weight them by s / sum(s) x ``routed_scaling_factor``, and add a shared
SwiGLU of ``n_shared_experts`` x ``moe_intermediate_size``.

``weights(cfg, key)`` makes every weight on the device in one jitted
call, in the program's parameter layout and in the types they are served
in (bfloat16; the final norm gain float32).  ``logits(cfg, w, tokens,
precision)`` is the causal forward pass over one sequence in plain
``jax.numpy`` at float32 with ``HIGHEST`` matmul precision, in the
expanded form, a layer at a time and an expert at a time, every expert
computed densely and weighted by the router.  ``precision="fp8"`` is the
control: every projection, expert and LM-head matmul takes operands
rounded to float8_e4m3 (absmax-scaled per row of activations and per
output column of weights), the step below bfloat16.
``precision="bfloat16"`` is a witness of what bfloat16 alone does: every
matmul and attention operand, the router's input and the residual stream
after each addition rounded to bfloat16 (products summed in float32), as
a bfloat16 server stores them.  ``router_flips`` says where the top
experts of the float32 pass change when the router reads its input in
bfloat16.  Rotary pairs are rotated in place, (x[2i], x[2i+1]), as
published.  Nothing here imports the program but ``program_config``.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
BIAS_STD = 0.1  # the correction bias drawn from the seed (assumed)


def _dims(cfg: dict):
    return (cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["n_routed_experts"], cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
            cfg["vocab_size"], cfg["first_k_dense_replace"], cfg["num_hidden_layers"] - cfg["first_k_dense_replace"])


@functools.partial(jax.jit, static_argnums=(0,))
def _weights(dims, key):
    d, h, r, nope, rope, vd, f, fe, e, fs, v, nd, nm = dims
    bf = jnp.bfloat16
    ks = iter(jax.random.split(key, 32))

    def dense(shape, fan_in):
        return (jax.random.normal(next(ks), shape, bf) * bf(1.0 / math.sqrt(fan_in))).astype(bf)

    def gain(shape, dtype=bf):  # the stacked gains are served in bfloat16
        return (0.1 * jax.random.normal(next(ks), shape, jnp.float32)).astype(dtype)

    def attn(n):
        return {
            "wq": dense((n, d, h * (nope + rope)), d),
            "wkv_a": dense((n, d, r + rope), d),
            "kv_norm": gain((n, r)),
            "wkv_b": dense((n, r, h * (nope + vd)), r),
            "wo": dense((n, h * vd, d), h * vd),
        }

    def swiglu(lead, width):
        return {"wg": dense((*lead, d, width), d), "wi": dense((*lead, d, width), d),
                "wo": dense((*lead, width, d), width)}

    return {
        "embed": {"table": dense((v, d), d)},
        "unembed": dense((d, v), d),
        "final_norm": gain((d,), jnp.float32),
        "dense_layers": {"attn_norm": gain((nd, d)), "attn": attn(nd), "ffn_norm": gain((nd, d)),
                         "mlp": swiglu((nd,), f)},
        "layers": {
            "attn_norm": gain((nm, d)),
            "attn": attn(nm),
            "ffn_norm": gain((nm, d)),
            "moe": {
                "router": dense((nm, d, e), d),
                "e_score_correction_bias": (BIAS_STD * jax.random.normal(next(ks), (nm, e), jnp.float32)).astype(bf),
                **swiglu((nm, e), fe),
                "shared": swiglu((nm,), fs),
            },
        },
    }


def _refuse_unless_published_block(cfg: dict) -> None:
    """The settings this reference and the program compute, and no other."""
    want = {"q_lora_rank": None, "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
            "topk_group": 1, "norm_topk_prob": True, "hidden_act": "silu", "moe_layer_freq": 1,
            "attention_bias": False, "tie_word_embeddings": False}
    bad = {k: cfg.get(k) for k, v in want.items() if cfg.get(k) != v}
    if bad or cfg.get("rope_scaling"):
        raise ValueError(f"{cfg['name']}: no reference for {bad or {'rope_scaling': cfg['rope_scaling']}}")


def weights(cfg: dict, key):
    _refuse_unless_published_block(cfg)
    return _weights(_dims(cfg), key)


def program_config(cfg: dict, log=None):
    """The program's preset for ``cfg["program"]["arch"]`` with every size
    taken from this file's keys (the file holds what is run)."""
    from repro.configs import get_config

    _refuse_unless_published_block(cfg)
    prog = cfg["program"]
    base = get_config(prog["arch"])
    sizes = {
        "num_layers": cfg["num_hidden_layers"], "first_k_dense": cfg["first_k_dense_replace"],
        "d_model": cfg["hidden_size"], "dense_ff": cfg["intermediate_size"],
        "num_heads": cfg["num_attention_heads"], "num_kv_heads": cfg["num_key_value_heads"],
        "kv_lora_rank": cfg["kv_lora_rank"], "qk_nope_head_dim": cfg["qk_nope_head_dim"],
        "qk_rope_head_dim": cfg["qk_rope_head_dim"], "v_head_dim": cfg["v_head_dim"],
        "d_ff": cfg["moe_intermediate_size"], "num_experts": cfg["n_routed_experts"],
        "experts_per_token": cfg["num_experts_per_tok"],
        "shared_expert_ff": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        "router_scoring": cfg["scoring_func"], "routed_scaling": cfg["routed_scaling_factor"],
        "vocab_size": cfg["vocab_size"], "rms_eps": cfg["rms_norm_eps"], "rope_theta": float(cfg["rope_theta"]),
        "tie_embeddings": cfg["tie_word_embeddings"], "dtype": cfg["torch_dtype"],
        "capacity_factor": prog["capacity_factor"],
    }
    changed = {k: (getattr(base, k), v) for k, v in sizes.items() if getattr(base, k) != v}
    pc = dataclasses.replace(base, **sizes)
    if pc.sliding_window or pc.attn_logit_softcap or pc.act != "silu":
        raise ValueError(f"{prog['arch']}: the reference has no sliding window, softcap or {pc.act}")
    if log:
        log(f"program: {prog['arch']} (preset values changed by the file: {changed}), "
            f"moe_dispatch {pc.moe_dispatch}, capacity_factor {pc.capacity_factor}")
    return pc


# -- the reference ------------------------------------------------------


def _q8(x, axis):
    """Round to float8_e4m3 with an absmax scale along ``axis``."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30) / F8_MAX
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def _bf(x, mode: str):
    """``x`` in float32, rounded to bfloat16 first in the ``bfloat16`` pass."""
    if mode == "bfloat16":
        x = x.astype(jnp.bfloat16)
    return x.astype(jnp.float32)


def _mm(a, w, mode: str):
    a, w = _bf(a, mode), _bf(w, mode)
    if mode == "fp8":
        a, w = _q8(a, -1), _q8(w, -2)
    return jnp.matmul(a, w, precision=HIGHEST)


def _norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + gain.astype(jnp.float32))


def _rope(x, pos, theta):
    """Rotate the interleaved pairs (x[2i], x[2i+1]) by pos * theta^(-2i/d); x (T, ..., d)."""
    d = x.shape[-1]
    freqs = jnp.exp(-math.log(theta) * jnp.arange(d // 2, dtype=jnp.float32) * 2.0 / d)
    ang = pos.astype(jnp.float32).reshape((-1,) + (1,) * (x.ndim - 1)) * freqs
    s, c = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).reshape(x.shape)


def _swiglu(h, p, mode):
    return _mm(jax.nn.silu(_mm(h, p["wg"], mode)) * _mm(h, p["wi"], mode), p["wo"], mode)


def _top(hn, router, bias, k):
    """Sigmoid scores of ``hn`` and the top ``k`` experts of score + bias."""
    scores = jax.nn.sigmoid(jnp.matmul(hn, router.astype(jnp.float32), precision=HIGHEST))
    return scores, jax.lax.top_k(scores + bias.astype(jnp.float32), k)[1]


@functools.partial(jax.jit, static_argnums=(0, 4, 5))
def _layer(static, x, lw, l, moe: bool, mode: str):
    """One layer; returns (x, where the router's choice flips when it reads
    bfloat16 (T,), or None for a dense layer)."""
    (d, h, r, nope, rope, vd, f, fe, e, fs, v, nd, nm), eps, theta, k, scaling = static
    t = x.shape[0]
    pos = jnp.arange(t)
    a = jax.tree.map(lambda p: p[l], lw["attn"])
    hn = _norm(x, lw["attn_norm"][l], eps)
    q = _mm(hn, a["wq"], mode).reshape(t, h, nope + rope)
    kv = _mm(hn, a["wkv_a"], mode)
    c = _bf(_norm(kv[:, :r], a["kv_norm"], eps), mode)
    k_pe = _bf(_rope(kv[:, r:], pos, theta), mode)  # (T, rope), one for all heads
    q_pe = _bf(_rope(q[..., nope:], pos, theta), mode)
    kvb = _mm(c, a["wkv_b"], mode).reshape(t, h, nope + vd)
    s = (jnp.einsum("qhn,shn->hqs", _bf(q[..., :nope], mode), _bf(kvb[..., :nope], mode), precision=HIGHEST)
         + jnp.einsum("qhr,sr->hqs", q_pe, k_pe, precision=HIGHEST)) / math.sqrt(nope + rope)
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    o = jnp.einsum("hqs,shv->qhv", _bf(jax.nn.softmax(s, axis=-1), mode), _bf(kvb[..., nope:], mode),
                   precision=HIGHEST)
    x = _bf(x + _mm(o.reshape(t, h * vd), a["wo"], mode), mode)

    hn = _norm(x, lw["ffn_norm"][l], eps)
    if not moe:
        return _bf(x + _swiglu(hn, jax.tree.map(lambda p: p[l], lw["mlp"]), mode), mode), None
    m = lw["moe"]
    router, bias = m["router"][l], m["e_score_correction_bias"][l]
    scores, top_e = _top(_bf(hn, mode), router, bias, k)
    _, lo_e = _top(_bf(hn, "bfloat16"), router, bias, k)
    flips = jnp.any(jnp.sort(lo_e, axis=-1) != jnp.sort(top_e, axis=-1), axis=-1)
    top_s = jnp.take_along_axis(scores, top_e, axis=-1)
    top_w = top_s / jnp.sum(top_s, axis=-1, keepdims=True) * scaling

    def expert(j, y):  # one expert's weights in float32 at a time
        gate = jnp.sum(jnp.where(top_e == j, top_w, 0.0), axis=-1, keepdims=True)
        p = {n: m[n][l, j] for n in ("wg", "wi", "wo")}
        return y + gate * _swiglu(hn, p, mode)

    y = jax.lax.fori_loop(0, e, expert, jnp.zeros_like(x))
    return _bf(x + y + _swiglu(hn, jax.tree.map(lambda p: p[l], m["shared"]), mode), mode), flips


@functools.partial(jax.jit, static_argnums=(0,))
def _embed(d, table, tokens):
    return table[tokens].astype(jnp.float32) * math.sqrt(d)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _head(eps, x, final_norm, unembed, mode):
    return _mm(_norm(x, final_norm, eps), unembed, mode)


def _forward(cfg: dict, w: dict, tokens, precision: str):
    if precision not in ("float32", "bfloat16", "fp8"):
        raise ValueError(f"no {precision} pass")
    dims = _dims(cfg)
    eps = float(cfg["rms_norm_eps"])
    static = (dims, eps, float(cfg["rope_theta"]), cfg["num_experts_per_tok"], float(cfg["routed_scaling_factor"]))
    x = _bf(_embed(dims[0], w["embed"]["table"], jnp.asarray(tokens, jnp.int32)), precision)
    for l in range(dims[-2]):
        x, _ = _layer(static, x, w["dense_layers"], l, False, precision)
    flips = []
    for l in range(dims[-1]):
        x, f = _layer(static, x, w["layers"], l, True, precision)
        flips.append(f)
    return _head(eps, x, w["final_norm"], w["unembed"], precision), jnp.stack(flips, axis=-1)


def logits(cfg: dict, w: dict, tokens, precision: str = "float32"):
    """(T, vocab) float32 logits of the causal forward pass over ``tokens``."""
    return _forward(cfg, w, tokens, precision)[0]


def router_flips(cfg: dict, w: dict, tokens):
    """(T, MoE layers) bool: where the float32 pass's top experts change
    when the router reads its (float32 pass's) input in bfloat16."""
    return _forward(cfg, w, tokens, "float32")[1]
