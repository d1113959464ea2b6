"""Latent attention, sigmoid routing and the leading dense layer of the
Moonlight (DeepSeek-V3) block, at the reduced size on the CPU, jitted.

Float32 throughout: a tolerance of 1e-5 (relative to the largest output)
is summation order in float32 (~1e-7 a term over tens of terms); bfloat16
rounds at ~4e-3, and the bfloat16 case below shows that it fails it."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.models import forward_decode, forward_prefill, forward_train, init_caches, init_params
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod

CFG = get_config("moonshot-v1-16b-a3b").reduced()
TOL = 1e-5  # float32 summation order, relative to the largest output (module docstring)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def _attn_params(key, dtype=jnp.float32):
    params = init_params(CFG, key)
    p = jax.tree.map(lambda t: t[0], params["layers"]["attn"])
    # non-zero norm gains, so the (1 + g) gain is exercised
    p["kv_norm"] = 0.1 * jax.random.normal(jax.random.fold_in(key, 1), p["kv_norm"].shape)
    return jax.tree.map(lambda t: t.astype(dtype), p)


KW = dict(num_heads=CFG.num_heads, nope=CFG.qk_nope_head_dim, rope=CFG.qk_rope_head_dim,
          v_dim=CFG.v_head_dim, rope_theta=CFG.rope_theta, eps=CFG.rms_eps)


@jax.jit
def _expanded(p, x):
    return attn_mod.mla_attention(p, x, positions=jnp.arange(x.shape[1]), **KW)


@jax.jit
def _absorbed(p, x, cache, pos):
    return attn_mod.mla_decode_attention(p, x, cache, pos, **KW)


def _expanded_then_absorbed(dtype):
    """The expanded outputs over 11 positions, and the absorbed outputs of
    each slot's last position from a cache of the earlier rows, with
    garbage in the cache at and past the slot's position."""
    b, t, s_cache = 2, 11, 16
    p = _attn_params(jax.random.key(0), dtype)
    x = jax.random.normal(jax.random.key(1), (b, t, CFG.d_model)).astype(dtype)
    out, latent = _expanded(p, x)
    pos = jnp.array([t - 1, t - 4], jnp.int32)
    garbage = 3.0 * jax.random.normal(jax.random.key(2), (b, s_cache, latent.shape[-1]))
    keep = jnp.arange(s_cache)[None, :, None] < pos[:, None, None]
    cache = jnp.where(keep, jnp.pad(latent, ((0, 0), (0, s_cache - t), (0, 0))), garbage).astype(dtype)
    x_last = x[jnp.arange(b), pos][:, None]
    dec, new_cache = _absorbed(p, x_last, cache, pos)
    want = out[jnp.arange(b), pos][:, None]
    return dec, want, new_cache, latent, pos


def test_absorbed_decode_matches_expanded():
    dec, want, new_cache, latent, pos = _expanded_then_absorbed(jnp.float32)
    assert _rel(dec, want) < TOL
    # the token's own latent row was written at its position, nothing else moved
    rows = new_cache[jnp.arange(2), pos]
    assert _rel(rows, latent[jnp.arange(2), pos]) < TOL


def test_absorbed_decode_in_bfloat16_misses_the_float32_tolerance():
    """The tolerance above separates float32 from bfloat16: the same
    comparison with weights and activations in bfloat16 fails it."""
    dec, want, *_ = _expanded_then_absorbed(jnp.bfloat16)
    ref, *_ = _expanded_then_absorbed(jnp.float32)
    assert _rel(dec, ref[1]) > 10 * TOL


def test_mla_cache_holds_one_latent_row_a_position():
    full = get_config("moonshot-v1-16b-a3b")
    caches = jax.eval_shape(lambda: init_caches(full, 16, 1280))
    assert set(caches) == {"sub0", "dense_layers"}
    assert caches["sub0"]["latent"].shape == (26, 16, 1280, 576)
    assert caches["dense_layers"]["latent"].shape == (1, 16, 1280, 576)
    leaves = jax.tree.leaves(caches)
    per_token = sum(a.size * a.dtype.itemsize for a in leaves) // (16 * 1280 * 27)
    assert per_token == 1152  # (512 + 64) bfloat16 values a layer


def _moe_params(key, e=8, k=3):
    cfg = dataclasses.replace(CFG, num_experts=e, experts_per_token=k)
    p = moe_mod.moe_init(key, cfg, jnp.float32)
    return cfg, p


def test_sigmoid_routing_bias_chooses_but_does_not_weight():
    cfg, p = _moe_params(jax.random.key(3))
    x = jax.random.normal(jax.random.key(4), (5, cfg.d_model))
    route = jax.jit(lambda p, x: moe_mod.route(p, x, cfg))
    w0, e0 = route(p, x)
    scores = jax.nn.sigmoid(x @ p["router"])
    np.testing.assert_allclose(np.sum(np.asarray(w0), -1), cfg.routed_scaling, rtol=1e-6)
    np.testing.assert_array_equal(np.sort(np.asarray(e0), -1),
                                  np.sort(np.asarray(jax.lax.top_k(scores, cfg.experts_per_token)[1]), -1))
    # a bias that lifts expert 0 and sinks each token's best unbiased expert
    best = np.asarray(e0)[:, 0]
    for t in range(5):
        p_t = dict(p, e_score_correction_bias=jnp.zeros((cfg.num_experts,)).at[0].set(10.0).at[best[t]].add(-10.0))
        w, e = route(p_t, x[t : t + 1])
        chosen = set(np.asarray(e)[0].tolist())
        assert 0 in chosen and (best[t] == 0 or best[t] not in chosen)
        s = np.asarray(scores)[t, np.asarray(e)[0]]
        np.testing.assert_allclose(np.asarray(w)[0], s / s.sum() * cfg.routed_scaling, rtol=1e-6)
    assert CFG.routed_scaling == 2.446


def test_softmax_routing_is_the_renormalised_top_k():
    cfg = get_config("phi3.5-moe-42b-a6.6b").reduced()
    p = moe_mod.moe_init(jax.random.key(5), cfg, jnp.float32)
    assert "e_score_correction_bias" not in p
    x = jax.random.normal(jax.random.key(6), (7, cfg.d_model))
    w, e = jax.jit(lambda p, x: moe_mod.route(p, x, cfg))(p, x)
    probs = jax.nn.softmax(x @ p["router"], axis=-1)
    top_p, top_e = jax.lax.top_k(probs, cfg.experts_per_token)
    np.testing.assert_array_equal(np.asarray(e), np.asarray(top_e))
    np.testing.assert_array_equal(np.asarray(w), np.asarray(top_p / jnp.sum(top_p, -1, keepdims=True)))


def test_dense_layer_runs_before_the_expert_stack():
    params = init_params(CFG, jax.random.key(7))
    assert params["dense_layers"]["mlp"]["wi"].shape == (1, CFG.d_model, CFG.dense_ff)
    assert "moe" not in params["dense_layers"] and "mlp" not in params["layers"]
    assert params["layers"]["moe"]["wi"].shape[0] == CFG.num_layers - 1
    toks = jax.random.randint(jax.random.key(8), (2, 9), 0, CFG.vocab_size)
    fwd = jax.jit(lambda p: forward_train(CFG, p, {"tokens": toks}))
    base = fwd(params)
    # the dense layer's MLP is on the path: zeroing its output projection changes the logits
    zeroed = jax.tree.map(lambda t: t, params)
    zeroed["dense_layers"]["mlp"]["wo"] = jnp.zeros_like(params["dense_layers"]["mlp"]["wo"])
    assert _rel(fwd(zeroed), base) > 1e-3


def test_decode_counts(monkeypatch):
    cfg = dataclasses.replace(CFG, capacity_factor=8.0)
    params = init_params(cfg, jax.random.key(9))
    b, s = 3, 10
    toks = jax.random.randint(jax.random.key(10), (b, s), 0, cfg.vocab_size)
    _, caches, _ = forward_prefill(cfg, params, {"tokens": toks[:, :6]}, cache_len=s)
    pos = jnp.array([6, 2, 9], jnp.int32)
    chosen = []
    real = moe_mod.moe_layer

    def spy(*args, **kw):
        y, top_e = real(*args, **kw)
        chosen.append(np.asarray(top_e))
        return y, top_e

    monkeypatch.setattr(moe_mod, "moe_layer", spy)
    with jax.disable_jit():  # the layer scan runs as a Python loop, so the spy sees each layer's routing
        _, _, counts = forward_decode(cfg, params, caches, toks[:, 6:7], pos, stats=True)
    assert len(chosen) == cfg.stack_layers
    assert int(counts["moe.experts_reached"]) == sum(len(np.unique(e)) for e in chosen)
    assert int(counts["mla.latent_positions"]) == cfg.num_layers * (7 + 3 + 10)
    e = jnp.array([[0, 1], [1, 3], [0, 1]])
    assert int(moe_mod.experts_reached(e, cfg.num_experts)) == 3
    assert int(moe_mod.experts_reached(e, cfg.num_experts, jnp.array([False, True, False]))) == 2


def test_prompt_call_counts_its_one_slot():
    """A prompt-token call carries a real token in one slot and token 0 in
    the others: its counts are those of that slot decoded alone."""
    cfg = dataclasses.replace(CFG, capacity_factor=8.0)
    params = init_params(cfg, jax.random.key(11))
    b, s, slot = 3, 10, 1
    toks = jax.random.randint(jax.random.key(12), (b, s), 0, cfg.vocab_size)
    _, caches, _ = forward_prefill(cfg, params, {"tokens": toks[:, :6]}, cache_len=s)
    pos = jnp.array([0, 6, 0], jnp.int32)
    token = jnp.zeros((b, 1), jnp.int32).at[slot].set(toks[slot, 6])
    step = jax.jit(lambda c, t, p, live: forward_decode(cfg, params, c, t, p, stats=True, live=live)[2])
    prompt = step(caches, token, pos, jnp.arange(b) == slot)
    one = jax.tree.map(lambda t: t[:, slot:slot + 1], caches)
    alone = step(one, token[slot:slot + 1], pos[slot:slot + 1], jnp.ones((1,), bool))
    assert {k: int(v) for k, v in prompt.items()} == {k: int(v) for k, v in alone.items()}
    assert int(alone["mla.latent_positions"]) == cfg.num_layers * 7
    every = step(caches, token, pos, jnp.ones((b,), bool))
    assert int(every["mla.latent_positions"]) == cfg.num_layers * (1 + 7 + 1)


def test_engine_counts_only_slots_with_a_real_token():
    """One request in a 3-slot engine: its 4 prompt-token calls attend 1..4
    positions and its one lockstep call 5, in every layer; the idle slots,
    which carry token 0, count nothing."""
    from repro.serving.engine import Request, ServingEngine
    from repro.telemetry import get_telemetry

    params = init_params(CFG, jax.random.key(13))
    engine = ServingEngine(CFG, params, batch=3, max_seq=16)
    names = ("serving.decode_calls", "moe.experts_reached", "mla.latent_positions")
    before = {n: get_telemetry().counter(n).value for n in names}
    engine.submit(Request(uid=0, prompt=np.array([5, 6, 7, 8], np.int32), max_new_tokens=2, temperature=0.0))
    assert engine.run_until_done().ok()
    got = {n: get_telemetry().counter(n).value - before[n] for n in names}
    assert got["serving.decode_calls"] == 5
    # the prompt calls' counts are read with the next lockstep logits, so all 5 calls are in
    assert got["mla.latent_positions"] == CFG.num_layers * (1 + 2 + 3 + 4 + 5)
    moe_layers = CFG.num_layers - CFG.first_k_dense
    assert moe_layers <= got["moe.experts_reached"] <= 5 * moe_layers * CFG.experts_per_token
