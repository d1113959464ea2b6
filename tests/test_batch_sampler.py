"""The serving engine's batched sampler (``sampler.sample_batch``) against
the per-row samplers and the merge-path top-k, and the engine's sample
counters.  Small vocabularies, every route jitted."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import topk_batched
from repro.models import init_params
from repro.serving.engine import Request, ServingEngine
from repro.serving.sampler import greedy, sample_batch
from repro.telemetry import get_telemetry

_sample = jax.jit(sample_batch, static_argnames=("k",))
_greedy = jax.jit(greedy)
_core_topk = jax.jit(topk_batched, static_argnums=1)
_lax_topk = jax.jit(jax.lax.top_k, static_argnums=1)


def _tied(dtype, b=6, v=48, seed=0):
    """Logits from a few levels, so that most rows tie at the max and
    across the top-k boundary."""
    levels = np.random.default_rng(seed).integers(0, 5, size=(b, v))
    return jnp.asarray(levels * 0.75, dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_greedy_rows_equal_greedy_bit_for_bit(dtype):
    logits = _tied(dtype)
    want = np.asarray(_greedy(logits))
    assert (np.asarray(logits) == np.asarray(logits).max(1, keepdims=True)).sum(1).min() > 1  # tied maxima
    all_greedy, _ = _sample(logits, jax.random.key(0), jnp.zeros(6), k=4)
    np.testing.assert_array_equal(np.asarray(all_greedy), want)
    # greedy rows of a mixed batch (temperature 0 or below) still take the first max
    temp = jnp.asarray([0.0, 0.8, -1.0, 1.0, 0.0, 0.5], jnp.float32)
    mixed, _ = _sample(logits, jax.random.key(1), temp, k=4)
    greedy_rows = np.asarray(temp) <= 0
    np.testing.assert_array_equal(np.asarray(mixed)[greedy_rows], want[greedy_rows])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_topk_candidates_and_draws_follow_the_merge_path_topk(dtype):
    """``lax.top_k``'s candidates are the core merge-path top-k's, in order,
    on tie-heavy rows; every draw lies in its row's own top ``row_k``."""
    k = 8
    logits = _tied(dtype, seed=1)
    core_vals, core_idx = _core_topk(logits, k)
    vals, idx = _lax_topk(logits, k)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(core_idx))
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(core_vals))
    row_k = jnp.asarray([8, 1, 3, 5, 2, 8], jnp.int32)
    temp = jnp.full((6,), 0.8, jnp.float32)
    allowed = [set(np.asarray(core_idx[r, : int(row_k[r])]).tolist()) for r in range(6)]
    keys = jax.random.split(jax.random.key(2), 64)
    toks = np.asarray(jax.jit(jax.vmap(lambda kk: _sample(logits, kk, temp, k=k, row_k=row_k)[0]))(keys))
    for r in range(6):
        assert set(toks[:, r].tolist()) <= allowed[r]
    assert set(toks[:, 1].tolist()) == {int(core_idx[1, 0])}  # k = 1 is the first max


def test_draw_frequencies_match_the_topk_softmax():
    """Over 6,000 keys each row's draws follow the softmax of its top-k
    logits at temperature 0.8 (within 5 standard errors a token)."""
    k, t, n = 5, 0.8, 6000
    logits = jax.random.normal(jax.random.key(3), (3, 32), jnp.float32) * 2.0
    temp = jnp.full((3,), t, jnp.float32)
    keys = jax.random.split(jax.random.key(4), n)
    toks = np.asarray(jax.jit(jax.vmap(lambda kk: _sample(logits, kk, temp, k=k)[0]))(keys))
    vals, idx = jax.lax.top_k(logits, k)
    p = np.asarray(jax.nn.softmax(vals / t, axis=-1), np.float64)
    for r in range(3):
        freq = np.array([(toks[:, r] == i).mean() for i in np.asarray(idx[r])])
        assert np.isclose(freq.sum(), 1.0)  # nothing outside the top k
        assert np.all(np.abs(freq - p[r]) <= 5 * np.sqrt(p[r] * (1 - p[r]) / n) + 1e-3), (freq, p[r])


def test_mixed_batch_with_dead_rows_and_per_row_k():
    """Dead rows (temperature 0) and greedy rows take the argmax, top-k rows
    stay inside their own ``row_k``; the key advances once a call, whether
    or not any row samples."""
    logits = jax.random.normal(jax.random.key(5), (5, 40), jnp.float32)
    temp = jnp.asarray([0.0, 0.8, 0.0, 1.3, 0.0], jnp.float32)  # rows 0, 4 dead; row 2 greedy
    row_k = jnp.asarray([1, 2, 40, 6, 1], jnp.int32)
    key = jax.random.key(6)
    best = np.asarray(_greedy(logits))
    _, idx = jax.lax.top_k(logits, 6)
    for i in range(20):
        toks, nxt = _sample(logits, key, temp, k=6, row_k=row_k)
        toks = np.asarray(toks)
        np.testing.assert_array_equal(toks[[0, 2, 4]], best[[0, 2, 4]])
        assert toks[1] in np.asarray(idx[1, :2]) and toks[3] in np.asarray(idx[3, :6])
        _, greedy_next = _sample(logits, key, jnp.zeros(5), k=6, row_k=row_k)
        assert jax.random.key_data(nxt).tolist() == jax.random.key_data(greedy_next).tolist()
        assert jax.random.key_data(nxt).tolist() != jax.random.key_data(key).tolist()
        key = nxt


COUNTERS = ("serving.sample_calls", "serving.topk_calls", "serving.decode_calls")


def _counts():
    return {n: get_telemetry().counter(n).value for n in COUNTERS}


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("tinyllama-1.1b").reduced()
    return cfg, init_params(cfg, jax.random.key(0))


def test_engine_all_greedy_runs_no_topk(tiny):
    cfg, params = tiny
    eng = ServingEngine(cfg, params, batch=2, max_seq=24)
    before = _counts()
    for uid in range(3):
        eng.submit(Request(uid=uid, prompt=np.array([3, 4, 5], np.int32), max_new_tokens=3, temperature=0.0))
    assert eng.run_until_done().ok()
    got = {n: v - before[n] for n, v in _counts().items()}
    assert got["serving.topk_calls"] == 0
    # one call a refill (3) and one a lockstep tick (2 ticks for each of the two waves)
    assert got["serving.sample_calls"] == 3 + 4


def test_engine_lockstep_tick_is_one_sample_call(tiny):
    """A lockstep tick of 4 live slots, mixed greedy and top-k: 1 decode
    call, 1 sample call, 1 top-k call and 4 tokens."""
    cfg, params = tiny
    eng = ServingEngine(cfg, params, batch=4, max_seq=24, seed=3)
    for uid in range(4):
        eng.submit(Request(uid=uid, prompt=np.array([7, 8], np.int32), max_new_tokens=5,
                           temperature=0.8 if uid % 2 else 0.0, topk=3 + uid))
    eng.step()  # refills the 4 slots (2 prompt calls and 1 sample call each), then one lockstep tick
    assert [len(r.generated) for r in eng.active] == [2] * 4
    before = _counts()
    eng.step()
    got = {n: v - before[n] for n, v in _counts().items()}
    assert got == {"serving.sample_calls": 1, "serving.topk_calls": 1, "serving.decode_calls": 1}
    assert [len(r.generated) for r in eng.active] == [3] * 4
    for r in eng.active:
        assert all(0 <= t < cfg.vocab_size for t in r.generated)
