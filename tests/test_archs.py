"""Per-architecture smoke tests: reduced config, one forward/train step on
CPU, shape + finiteness assertions; prefill/decode consistency."""

import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import SHAPES_BY_NAME, TrainConfig, get_config, list_archs, shape_applicable
from repro.models import (
    forward_decode,
    forward_prefill,
    forward_train,
    init_caches,
    init_params,
)
from repro.train.steps import init_train_state, make_train_step

ARCHS = list_archs()
B, S = 2, 12


def _batch(cfg, key, s=S, with_labels=True):
    toks = jax.random.randint(key, (B, s), 0, cfg.vocab_size)
    batch = {"tokens": toks}
    if with_labels:
        batch["labels"] = toks
    if cfg.num_prefix_tokens:
        batch["prefix_emb"] = jax.random.normal(key, (B, cfg.num_prefix_tokens, cfg.d_model))
    if cfg.is_encoder_decoder:
        batch["frames"] = jax.random.normal(key, (B, cfg.encoder_seq, cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward(arch):
    cfg = get_config(arch).reduced()
    params = init_params(cfg, jax.random.key(0))
    logits = forward_train(cfg, params, _batch(cfg, jax.random.key(1), with_labels=False))
    assert logits.shape == (B, S, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all()


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step(arch):
    cfg = get_config(arch).reduced()
    tcfg = TrainConfig(learning_rate=1e-3, warmup_steps=1, total_steps=4)
    state = init_train_state(cfg, tcfg, jax.random.key(0))
    step = jax.jit(make_train_step(cfg, tcfg))
    batch = _batch(cfg, jax.random.key(1))
    state, m = step(state, batch)
    state, m2 = step(state, batch)
    assert np.isfinite(float(m["loss"])) and np.isfinite(float(m2["loss"]))
    assert float(m2["loss"]) <= float(m["loss"]) + 0.5
    assert int(state["step"]) == 2


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_matches_full_forward(arch):
    cfg = get_config(arch).reduced()
    if cfg.num_experts:
        cfg = dataclasses.replace(cfg, capacity_factor=8.0)  # no drops
    params = init_params(cfg, jax.random.key(0))
    key = jax.random.key(42)
    toks = jax.random.randint(key, (B, S + 1), 0, cfg.vocab_size)
    batch_full = _batch(cfg, key, with_labels=False)
    batch_full["tokens"] = toks
    batch_pre = dict(batch_full)
    batch_pre["tokens"] = toks[:, :S]
    full_logits = forward_train(cfg, params, batch_full)
    pre_pos = cfg.num_prefix_tokens  # paligemma offsets positions by the prefix
    cache_len = pre_pos + S + 1
    last, caches, enc_kv = forward_prefill(cfg, params, batch_pre, cache_len=cache_len)
    np.testing.assert_allclose(
        np.asarray(last), np.asarray(full_logits[:, S - 1]), rtol=3e-4, atol=3e-4
    )
    dec, _ = forward_decode(
        cfg, params, caches, toks[:, S : S + 1],
        jnp.full((B,), pre_pos + S, jnp.int32), enc_kv=enc_kv,
    )
    np.testing.assert_allclose(
        np.asarray(dec), np.asarray(full_logits[:, S]), rtol=5e-4, atol=5e-4
    )


@pytest.mark.parametrize("arch", ARCHS)
def test_shape_cell_applicability(arch):
    """Every (arch x shape) cell is either applicable or has a recorded reason."""
    cfg = get_config(arch)
    for name in ("train_4k", "prefill_32k", "decode_32k", "long_500k"):
        ok, why = shape_applicable(cfg, SHAPES_BY_NAME[name])
        if not ok:
            assert name == "long_500k" and not cfg.subquadratic
            assert why


def test_sliding_window_decode_ring_buffer():
    """Decode with a ring-buffer cache matches full attention restricted to
    the window (hymba reduced config)."""
    cfg = dataclasses.replace(get_config("hymba-1.5b").reduced(), sliding_window=4)
    params = init_params(cfg, jax.random.key(0))
    n = 2 * cfg.sliding_window + 1  # the ring buffer wraps twice
    toks = jax.random.randint(jax.random.key(5), (B, n), 0, cfg.vocab_size)
    caches = init_caches(cfg, B, n)
    assert caches["sub0"]["k"].shape[2] == cfg.sliding_window
    decode = jax.jit(lambda caches, tok, pos: forward_decode(cfg, params, caches, tok, pos))
    logits = None
    for t in range(n):
        logits, caches = decode(caches, toks[:, t : t + 1], jnp.full((B,), t, jnp.int32))
    assert np.isfinite(np.asarray(logits)).all()


def test_param_counts_match_analytic():
    for arch in ARCHS:
        cfg = get_config(arch)
        from repro.models import abstract_params

        tree = abstract_params(cfg)
        total = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(tree))
        analytic = cfg.n_params()
        assert abs(total - analytic) / analytic < 0.02, (arch, total, analytic)
