"""Phi-3.5-MoE as the repository's block computes it: weights made from the
seed, and a plain float32 reference of the forward pass.

``weights(cfg, key)`` makes every weight on the device in one jitted call,
in the program's parameter layout and in the types they are served in
(bfloat16 matrices, router, embedding, untied LM head and layer norm gains;
the final norm gain in float32).  ``logits(cfg, w, tokens, precision)`` is
the causal forward pass over one sequence in plain ``jax.numpy`` at float32
with ``HIGHEST`` matmul precision, a layer at a time and an expert at a
time, every expert computed densely and weighted by the router's
renormalised top-2 probabilities.  ``precision="fp8"`` is the control: the
same pass with every projection, expert and LM-head matmul taking operands
rounded to float8_e4m3 (absmax-scaled per row of activations and per
output column of weights), the step below bfloat16 that a faster serving
path might take.  ``router_flips`` says where the top-2 experts of the
reference change when the router reads its input in bfloat16, as the
program does.  Nothing here imports the program.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _dims(cfg: dict):
    return (cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["num_local_experts"], cfg["vocab_size"], cfg["num_hidden_layers"])


@functools.partial(jax.jit, static_argnums=(0,))
def _weights(dims, key):
    d, h, kv, hd, f, e, v, n = dims
    bf = jnp.bfloat16
    ks = iter(jax.random.split(key, 16))

    def dense(shape, fan_in):
        return (jax.random.normal(next(ks), shape, bf) * bf(1.0 / math.sqrt(fan_in))).astype(bf)

    def gain(shape, dtype=bf):  # the stacked layer gains are served in bfloat16
        return (0.1 * jax.random.normal(next(ks), shape, jnp.float32)).astype(dtype)

    return {
        "embed": {"table": dense((v, d), d)},
        "unembed": dense((d, v), d),
        "final_norm": gain((d,), jnp.float32),
        "layers": {
            "attn_norm": gain((n, d)),
            "attn": {
                "wq": dense((n, d, h * hd), d),
                "wk": dense((n, d, kv * hd), d),
                "wv": dense((n, d, kv * hd), d),
                "wo": dense((n, h * hd, d), h * hd),
            },
            "ffn_norm": gain((n, d)),
            "moe": {
                "router": dense((n, d, e), d),
                "wg": dense((n, e, d, f), d),
                "wi": dense((n, e, d, f), d),
                "wo": dense((n, e, f, d), f),
            },
        },
    }


def weights(cfg: dict, key):
    if cfg["tie_word_embeddings"]:
        raise ValueError("this reference serves an untied LM head, as published")
    return _weights(_dims(cfg), key)


# -- the reference ------------------------------------------------------


def _q8(x, axis):
    """Round to float8_e4m3 with an absmax scale along ``axis``."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30) / F8_MAX
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def _mm(a, w, fp8: bool):
    a = a.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if fp8:
        a, w = _q8(a, -1), _q8(w, -2)
    return jnp.matmul(a, w, precision=HIGHEST)


def _norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + gain.astype(jnp.float32))


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * freqs
    s, c = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _layer(dims_eps_theta, x, layers, l, fp8):
    (d, h, kv, hd, f, e, v, n), eps, theta = dims_eps_theta
    t = x.shape[0]
    a = jax.tree.map(lambda p: p[l], layers["attn"])
    hn = _norm(x, layers["attn_norm"][l], eps)
    q = _mm(hn, a["wq"], fp8).reshape(t, h, hd)
    k = _mm(hn, a["wk"], fp8).reshape(t, kv, hd)
    val = _mm(hn, a["wv"], fp8).reshape(t, kv, hd)
    pos = jnp.arange(t)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    q = q.reshape(t, kv, h // kv, hd)  # query head i reads kv head i // (h // kv)
    s = jnp.einsum("qkgh,skh->kgqs", q, k, precision=HIGHEST) / math.sqrt(hd)
    s = jnp.where(pos[None, None, :, None] >= pos[None, None, None, :], s, -jnp.inf)
    o = jnp.einsum("kgqs,skh->qkgh", jax.nn.softmax(s, axis=-1), val, precision=HIGHEST)
    x = x + _mm(o.reshape(t, h * hd), a["wo"], fp8)

    m = layers["moe"]
    hn = _norm(x, layers["ffn_norm"][l], eps)
    probs = jax.nn.softmax(jnp.matmul(hn, m["router"][l].astype(jnp.float32), precision=HIGHEST), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, 2)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    def expert(j, y):  # one expert's weights in float32 at a time
        gate = jnp.sum(jnp.where(top_e == j, top_p, 0.0), axis=-1, keepdims=True)
        hid = jax.nn.silu(_mm(hn, m["wg"][l, j], fp8)) * _mm(hn, m["wi"][l, j], fp8)
        return y + gate * _mm(hid, m["wo"][l, j], fp8)

    return x + jax.lax.fori_loop(0, e, expert, jnp.zeros_like(x)), _flips(hn, m["router"][l], top_e)


def _flips(hn, router, top_e):
    """Per position, whether the top-2 experts change when the router reads
    its input rounded to bfloat16 at the default matmul precision."""
    lo = jnp.matmul(hn.astype(jnp.bfloat16), router.astype(jnp.bfloat16), preferred_element_type=jnp.float32)
    _, lo_e = jax.lax.top_k(lo, 2)
    return jnp.any(jnp.sort(lo_e, axis=-1) != jnp.sort(top_e, axis=-1), axis=-1)


@functools.partial(jax.jit, static_argnums=(0,))
def _embed(d, table, tokens):
    return table[tokens].astype(jnp.float32) * math.sqrt(d)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _head(eps, x, final_norm, unembed, fp8):
    return _mm(_norm(x, final_norm, eps), unembed, fp8)


def _forward(cfg: dict, w: dict, tokens, precision: str):
    fp8 = {"float32": False, "fp8": True}[precision]
    dims = _dims(cfg)
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    x = _embed(dims[0], w["embed"]["table"], jnp.asarray(tokens, jnp.int32))
    flips = []
    for l in range(dims[-1]):
        x, f = _layer((dims, eps, theta), x, w["layers"], l, fp8)
        flips.append(f)
    return _head(eps, x, w["final_norm"], w["unembed"], fp8), jnp.stack(flips, axis=-1)


def logits(cfg: dict, w: dict, tokens, precision: str = "float32"):
    """(T, vocab) float32 logits of the causal forward pass over ``tokens``."""
    return _forward(cfg, w, tokens, precision)[0]


def router_flips(cfg: dict, w: dict, tokens):
    """(T, layers) bool: where the reference's top-2 experts change when the
    router reads its (reference) input in bfloat16."""
    return _forward(cfg, w, tokens, "float32")[1]
