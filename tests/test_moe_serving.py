"""MoE dispatch invariants, samplers, serving engine end-to-end."""

import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.models import init_params
from repro.models.moe import _positions_cumsum, _positions_merge_path, capacity, moe_apply
from repro.serving.engine import Request, ServingEngine
from repro.serving.sampler import greedy, topk_sample, topp_sample

# jitted once per shape: eager calls re-trace the merge-path loops each time
_moe_apply = jax.jit(moe_apply, static_argnums=2)
_topk_sample = jax.jit(topk_sample, static_argnames=("k",))
_topp_sample = jax.jit(topp_sample, static_argnames=("k_max",))


# --- MoE dispatch ------------------------------------------------------------

_positions_mp = jax.jit(_positions_merge_path, static_argnums=1)
_positions_cs = jax.jit(_positions_cumsum, static_argnums=1)


# lengths from a few fixed sizes (one, non-pow2, pow2, long) so examples
# share compiles; the expert ids are drawn per example
@settings(max_examples=30, deadline=None)
@given(st.sampled_from([1, 7, 64, 200]).flatmap(
    lambda n: st.lists(st.integers(0, 7), min_size=n, max_size=n)))
def test_merge_path_positions_match_cumsum(assignments):
    """The merge-path dispatch computes exactly the one-hot-cumsum
    position-in-expert (the O(N*E) baseline)."""
    flat = jnp.array(assignments, jnp.int32)
    pos_mp = np.asarray(_positions_mp(flat, 8))
    pos_cs = np.asarray(_positions_cs(flat, 8))
    np.testing.assert_array_equal(pos_mp, pos_cs)


def test_moe_conservation_no_drops():
    """With no capacity pressure, expert outputs combine to all tokens:
    output must be finite and routing weights sum to 1."""
    cfg = dataclasses.replace(get_config("phi3.5-moe-42b-a6.6b").reduced(),
                              capacity_factor=8.0)
    params = init_params(cfg, jax.random.key(0))
    layer0 = jax.tree.map(lambda t: t[0], params["layers"])
    x = jax.random.normal(jax.random.key(1), (2, 16, cfg.d_model))
    y = _moe_apply(layer0["moe"], x, cfg)
    assert y.shape == x.shape
    assert np.isfinite(np.asarray(y)).all()


def test_moe_drop_determinism():
    """Capacity drops are deterministic and position-ordered (stability of
    the merge-path sort): two identical calls give identical outputs."""
    cfg = dataclasses.replace(get_config("moonshot-v1-16b-a3b").reduced(),
                              capacity_factor=0.5)
    params = init_params(cfg, jax.random.key(0))
    layer0 = jax.tree.map(lambda t: t[0], params["layers"])
    x = jax.random.normal(jax.random.key(1), (2, 32, cfg.d_model))
    y1 = _moe_apply(layer0["moe"], x, cfg)
    y2 = _moe_apply(layer0["moe"], x, cfg)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))


def test_moe_dispatch_modes_agree():
    """merge_path and cumsum dispatch produce identical layer outputs."""
    base = get_config("phi3.5-moe-42b-a6.6b").reduced()
    x = jax.random.normal(jax.random.key(1), (2, 24, base.d_model))
    outs = {}
    for mode in ("merge_path", "cumsum"):
        cfg = dataclasses.replace(base, moe_dispatch=mode)
        params = init_params(cfg, jax.random.key(0))
        layer0 = jax.tree.map(lambda t: t[0], params["layers"])
        outs[mode] = np.asarray(_moe_apply(layer0["moe"], x, cfg))
    np.testing.assert_allclose(outs["merge_path"], outs["cumsum"], rtol=1e-5, atol=1e-5)


def test_capacity_lane_aligned():
    cfg = get_config("moonshot-v1-16b-a3b")
    c = capacity(cfg, 4096)
    assert c % 8 == 0
    assert c >= 4096 * cfg.experts_per_token / cfg.num_experts


# --- samplers ----------------------------------------------------------------

def test_greedy_matches_argmax():
    logits = jax.random.normal(jax.random.key(0), (4, 100))
    np.testing.assert_array_equal(np.asarray(greedy(logits)),
                                  np.asarray(jnp.argmax(logits, -1)))


def test_topk_sample_support():
    """Samples only come from the top-k set."""
    logits = jnp.asarray(np.random.default_rng(0).standard_normal((3, 64)), jnp.float32)
    topk_sets = [set(np.asarray(jax.lax.top_k(logits[i], 5)[1]).tolist()) for i in range(3)]
    for seed in range(20):
        s = _topk_sample(logits, jax.random.key(seed), k=5, temperature=1.0)
        for i in range(3):
            assert int(s[i]) in topk_sets[i]


def test_topp_always_keeps_best():
    logits = jnp.asarray([[10.0] + [0.0] * 63], jnp.float32)
    for seed in range(5):
        s = _topp_sample(logits, jax.random.key(seed), p=0.01, k_max=8)
        assert int(s[0]) == 0


# --- serving engine ----------------------------------------------------------

def test_serving_engine_end_to_end():
    cfg = get_config("tinyllama-1.1b").reduced()
    params = init_params(cfg, jax.random.key(0))
    eng = ServingEngine(cfg, params, batch=2, max_seq=48)
    rng = np.random.default_rng(0)
    for uid in range(5):
        eng.submit(Request(uid=uid, prompt=rng.integers(1, cfg.vocab_size, 6).astype(np.int32),
                           max_new_tokens=4, temperature=0.0))
    eng.run_until_done()
    assert len(eng.done) == 5
    for r in eng.done.values():
        assert len(r.generated) == 4
        assert all(0 <= t < cfg.vocab_size for t in r.generated)


def test_serving_greedy_matches_manual_decode():
    """Engine greedy output == manual prefill+decode loop."""
    from repro.models import forward_prefill, forward_decode

    cfg = get_config("tinyllama-1.1b").reduced()
    params = init_params(cfg, jax.random.key(0))
    prompt = np.asarray([3, 14, 15, 9], np.int32)

    eng = ServingEngine(cfg, params, batch=1, max_seq=32)
    eng.submit(Request(uid=0, prompt=prompt, max_new_tokens=3, temperature=0.0))
    eng.run_until_done()
    got = eng.done[0].generated

    last, caches, _ = forward_prefill(
        cfg, jax.tree.map(lambda p: p, params), {"tokens": jnp.asarray(prompt)[None]},
        cache_len=32,
    )
    toks = []
    cur = int(jnp.argmax(last[0]))
    toks.append(cur)
    pos = len(prompt)
    for _ in range(2):
        logits, caches = forward_decode(
            cfg, params, caches, jnp.asarray([[cur]], jnp.int32),
            jnp.asarray([pos], jnp.int32),
        )
        cur = int(jnp.argmax(logits[0]))
        toks.append(cur)
        pos += 1
    assert got == toks, (got, toks)
