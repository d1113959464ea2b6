"""Ragged-length hardening suite (PR 2).

Covers the length-aware Merge Path layers end to end:

* fuzzed ragged batched merges (>= 200 random ``(B, a_lens, b_lens)``
  row configurations, zero-length rows and sentinel-valued payloads
  included) against the per-row NumPy stable-merge oracle, on both the
  pure-JAX path and the 2-D-grid ragged Pallas kernel;
* residue-free ``partitioned_merge`` / ``segmented_merge{,_kv}``
  (non-divisible sizes, mid-segment input exhaustion, real ``+inf`` /
  ``iinfo.max`` keys, empty inputs);
* the int-overflow top-k fix (``iinfo.min`` payloads);
* pad handling in the distributed combine helpers;
* the ragged consumers: MoE padded-token dispatch and masked-vocab
  sampling.

Pure pytest (no hypothesis) so the whole file is tier-1 in offline
containers.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import (
    flip_desc,
    merge_batched_ragged,
    merge_kv_batched_ragged,
    merge_k,
    merge_k_kv,
    merge_sort_batched_ragged,
    merge_sort_kv_batched_ragged,
    partitioned_merge,
    segmented_merge,
    segmented_merge_kv,
    stable_argsort_batched_ragged,
    topk_batched,
    topk_batched_ragged,
    topk_desc,
)
from repro.core.distributed import _pairwise_tree_merge
from repro.kernels import merge_batched_ragged_pallas, merge_kv_batched_ragged_pallas
from repro.kernels import ops

I32MAX = np.iinfo(np.int32).max
I32MIN = np.iinfo(np.int32).min


def ragged_rows(rng, b, n, dtype=np.int32, sentinel_rate=0.15):
    """Sorted (B, n) rows + random valid lengths; garbage beyond lengths.

    A slice of rows gets payloads *equal* to the padding sentinel
    (``iinfo.max`` / ``+inf``) inside the valid prefix, the classic
    collision case.
    """
    if np.issubdtype(dtype, np.integer):
        x = rng.integers(-1000, 1000, (b, n)).astype(dtype)
        sent = np.iinfo(dtype).max
    else:
        x = rng.standard_normal((b, n)).astype(dtype)
        sent = np.inf
    x = np.sort(x, axis=1)
    lens = rng.integers(0, n + 1, b).astype(np.int32)
    lens[rng.integers(0, b)] = 0  # always include an empty row
    for r in range(b):
        if rng.random() < sentinel_rate and lens[r] > 0:
            x[r, max(0, lens[r] - 2) : lens[r]] = sent  # real sentinel payloads
        # scribble on the padding region: the API must ignore it
        x[r, lens[r] :] = rng.permutation(x[r, lens[r] :])
    return x, lens


def np_merge_oracle(a_valid, b_valid):
    """Stable A-priority merge == stable sort of [A then B]."""
    return np.sort(np.concatenate([a_valid, b_valid]), kind="stable")


def np_merge_kv_oracle(ak, av, bk, bv):
    keys = np.concatenate([ak, bk])
    vals = np.concatenate([av, bv])
    order = np.argsort(keys, kind="stable")
    return keys[order], vals[order]


# --- fuzzed ragged batched merges (acceptance: >= 200 row configs) ----------


@pytest.mark.parametrize("dtype", [np.int32, np.float32])
def test_fuzz_merge_batched_ragged_vs_np_oracle(dtype):
    """13 batches x 8 rows x 2 dtypes = 208 random (lens_a, lens_b) row
    configurations, bit-identical to the per-row NumPy oracle."""
    rng = np.random.default_rng(0 if dtype is np.int32 else 1)
    B, na, nb = 8, 48, 64
    fn = jax.jit(merge_batched_ragged)
    sent = np.iinfo(dtype).max if np.issubdtype(dtype, np.integer) else np.inf
    for it in range(13):
        a, al = ragged_rows(rng, B, na, dtype)
        b, bl = ragged_rows(rng, B, nb, dtype)
        out = np.asarray(fn(jnp.array(a), jnp.array(b), jnp.array(al), jnp.array(bl)))
        for r in range(B):
            m = al[r] + bl[r]
            ref = np_merge_oracle(a[r, : al[r]], b[r, : bl[r]])
            np.testing.assert_array_equal(out[r, :m], ref)
            assert (out[r, m:] == sent).all()


def test_fuzz_merge_kv_batched_ragged_vs_np_oracle():
    """Ragged kv merges carry values exactly — incl. sentinel-equal keys."""
    rng = np.random.default_rng(2)
    B, na, nb = 8, 31, 17
    fn = jax.jit(merge_kv_batched_ragged)
    for it in range(8):
        ak, al = ragged_rows(rng, B, na, np.int32, sentinel_rate=0.5)
        bk, bl = ragged_rows(rng, B, nb, np.int32, sentinel_rate=0.5)
        av = rng.integers(0, 10**6, (B, na)).astype(np.int32)
        bv = rng.integers(0, 10**6, (B, nb)).astype(np.int32)
        ko, vo = fn(
            jnp.array(ak), jnp.array(av), jnp.array(bk), jnp.array(bv),
            jnp.array(al), jnp.array(bl),
        )
        ko, vo = np.asarray(ko), np.asarray(vo)
        for r in range(B):
            m = al[r] + bl[r]
            rk, rv = np_merge_kv_oracle(
                ak[r, : al[r]], av[r, : al[r]], bk[r, : bl[r]], bv[r, : bl[r]]
            )
            np.testing.assert_array_equal(ko[r, :m], rk)
            np.testing.assert_array_equal(vo[r, :m], rv)
            assert (ko[r, m:] == I32MAX).all() and (vo[r, m:] == 0).all()


def test_fuzz_ragged_pallas_kernel_vs_np_oracle():
    """The 2-D-grid ragged kernel (lengths via scalar prefetch) matches the
    oracle bit-exactly across random lengths and non-divisible tiles."""
    rng = np.random.default_rng(3)
    B, na, nb, tile = 8, 70, 45, 64  # (na+nb) % tile != 0
    fn = jax.jit(
        lambda a, b, al, bl: merge_batched_ragged_pallas(a, b, al, bl, tile=tile)
    )
    for it in range(3):
        a, al = ragged_rows(rng, B, na, np.float32)
        b, bl = ragged_rows(rng, B, nb, np.float32)
        out = np.asarray(fn(jnp.array(a), jnp.array(b), jnp.array(al), jnp.array(bl)))
        for r in range(B):
            m = al[r] + bl[r]
            np.testing.assert_array_equal(
                out[r, :m], np_merge_oracle(a[r, : al[r]], b[r, : bl[r]])
            )
            assert (out[r, m:] == np.inf).all()


def test_ragged_pallas_kv_kernel_sentinel_keys():
    rng = np.random.default_rng(4)
    B, na, nb, tile = 4, 80, 50, 64
    ak, al = ragged_rows(rng, B, na, np.int32, sentinel_rate=1.0)
    bk, bl = ragged_rows(rng, B, nb, np.int32, sentinel_rate=1.0)
    av = rng.integers(0, 10**6, (B, na)).astype(np.int32)
    bv = rng.integers(0, 10**6, (B, nb)).astype(np.int32)
    ko, vo = merge_kv_batched_ragged_pallas(
        jnp.array(ak), jnp.array(av), jnp.array(bk), jnp.array(bv),
        jnp.array(al), jnp.array(bl), tile=tile,
    )
    rk, rv = merge_kv_batched_ragged(
        jnp.array(ak), jnp.array(av), jnp.array(bk), jnp.array(bv),
        jnp.array(al), jnp.array(bl),
    )
    np.testing.assert_array_equal(np.asarray(ko), np.asarray(rk))
    np.testing.assert_array_equal(np.asarray(vo), np.asarray(rv))


def test_ops_ragged_dispatch_both_paths():
    rng = np.random.default_rng(5)
    a, al = ragged_rows(rng, 4, 100, np.float32)
    b, bl = ragged_rows(rng, 4, 80, np.float32)
    args = (jnp.array(a), jnp.array(b), jnp.array(al), jnp.array(bl))
    ref = np.asarray(merge_batched_ragged(*args))
    np.testing.assert_array_equal(np.asarray(ops.merge_batched_ragged(*args, tile=512)), ref)
    np.testing.assert_array_equal(np.asarray(ops.merge_batched_ragged(*args, tile=64)), ref)


# --- ragged sorts / argsort / top-k -----------------------------------------


def test_merge_sort_batched_ragged_matches_np():
    rng = np.random.default_rng(6)
    B, n = 6, 90
    x = rng.standard_normal((B, n)).astype(np.float32)
    x[0, :4] = np.inf  # real sentinel payloads inside the valid prefix
    lens = rng.integers(0, n + 1, B).astype(np.int32)
    lens[0] = 10
    out = np.asarray(merge_sort_batched_ragged(jnp.array(x), jnp.array(lens)))
    for r in range(B):
        np.testing.assert_array_equal(out[r, : lens[r]], np.sort(x[r, : lens[r]]))
        assert (out[r, lens[r] :] == np.inf).all()


def test_stable_argsort_batched_ragged_is_permutation():
    rng = np.random.default_rng(7)
    B, n = 5, 40
    keys = rng.integers(0, 6, (B, n)).astype(np.int32)
    lens = np.array([40, 17, 0, 1, 33], np.int32)
    perm = np.asarray(stable_argsort_batched_ragged(jnp.array(keys), jnp.array(lens)))
    for r in range(B):
        np.testing.assert_array_equal(
            perm[r, : lens[r]], np.argsort(keys[r, : lens[r]], kind="stable")
        )
        np.testing.assert_array_equal(np.sort(perm[r]), np.arange(n))  # full permutation


def test_topk_batched_ragged_matches_lax_topk_per_row():
    rng = np.random.default_rng(8)
    B, n, k = 6, 64, 9
    x = rng.standard_normal((B, n)).astype(np.float32)
    x[1, :3] = -np.inf  # banned-token logits
    lens = np.array([64, 64, 20, 9, 4, 0], np.int32)
    v, i = topk_batched_ragged(jnp.array(x), k, jnp.array(lens))
    v, i = np.asarray(v), np.asarray(i)
    for r in range(B):
        kk = min(k, lens[r])
        if kk:
            rv, ri = jax.lax.top_k(jnp.array(x[r, : lens[r]]), kk)
            np.testing.assert_array_equal(v[r, :kk], np.asarray(rv))
            np.testing.assert_array_equal(i[r, :kk], np.asarray(ri))
        assert (i[r, kk:] == -1).all() and (v[r, kk:] == -np.inf).all()


# --- int-overflow top-k fix (satellite) -------------------------------------


def test_topk_desc_iinfo_min_regression():
    """``keys = -x`` wraps at iinfo.min; flip_desc must not."""
    x = np.array([5, I32MIN, 7, I32MIN, I32MAX, 0, I32MAX], np.int32)
    v, i = topk_desc(jnp.array(x), x.size)
    rv, ri = jax.lax.top_k(jnp.array(x), x.size)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(rv))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))


def test_topk_batched_int_extremes():
    x = np.array(
        [[I32MIN, 3, I32MAX, I32MIN], [I32MAX, I32MAX, I32MIN, 0]], np.int32
    )
    v, i = topk_batched(jnp.array(x), 4)
    rv, ri = jax.lax.top_k(jnp.array(x), 4)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(rv))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))


def test_flip_desc_is_exact_order_reversal():
    x = np.array([I32MIN, I32MIN + 1, -1, 0, 1, I32MAX - 1, I32MAX], np.int32)
    f = np.asarray(flip_desc(jnp.array(x)))
    assert (np.diff(f) < 0).all()  # strictly decreasing image of increasing input
    assert f[0] == I32MAX and f[-1] == I32MIN


# --- residue-free partitioned / segmented merges (satellite) ----------------


@pytest.mark.parametrize("p", [1, 3, 5, 7, 13])
def test_partitioned_merge_non_divisible(p):
    rng = np.random.default_rng(100 + p)
    a = np.sort(rng.integers(-100, 100, 23)).astype(np.int32)
    b = np.sort(rng.integers(-100, 100, 18)).astype(np.int32)
    out = np.asarray(partitioned_merge(jnp.array(a), jnp.array(b), p))
    np.testing.assert_array_equal(out, np_merge_oracle(a, b))


@pytest.mark.parametrize("seg", [3, 7, 16])
def test_segmented_merge_non_divisible_and_exhaustion(seg):
    """One input exhausted mid-segment: tiny A against long B, and
    duplicate keys equal to the int sentinel."""
    rng = np.random.default_rng(200 + seg)
    a = np.sort(rng.integers(-10, 10, 3)).astype(np.int32)
    b = np.sort(rng.integers(-10, 10, 41)).astype(np.int32)
    b[-3:] = I32MAX  # duplicate sentinel-equal keys
    out = np.asarray(segmented_merge(jnp.array(a), jnp.array(b), seg))
    np.testing.assert_array_equal(out, np_merge_oracle(a, b))


def test_segmented_merge_empty_sides():
    e = jnp.array([], jnp.int32)
    a = jnp.array([1, 5, 9], jnp.int32)
    np.testing.assert_array_equal(np.asarray(segmented_merge(a, e, 2)), [1, 5, 9])
    np.testing.assert_array_equal(np.asarray(segmented_merge(e, a, 4)), [1, 5, 9])
    assert np.asarray(segmented_merge(e, e, 4)).shape == (0,)
    with pytest.raises(ValueError):
        segmented_merge(a, e, 0)


def test_partitioned_merge_empty_sides_and_inf():
    e = jnp.array([], jnp.float32)
    a = jnp.array([-np.inf, 0.0, np.inf], jnp.float32)
    np.testing.assert_array_equal(np.asarray(partitioned_merge(a, e, 4)), np.asarray(a))
    np.testing.assert_array_equal(np.asarray(partitioned_merge(e, a, 4)), np.asarray(a))
    b = jnp.array([np.inf, np.inf], jnp.float32)
    out = np.asarray(partitioned_merge(a, b, 2))
    np.testing.assert_array_equal(out, [-np.inf, 0.0, np.inf, np.inf, np.inf])


def test_segmented_merge_kv_sentinel_keys_carry_values():
    """Real +inf keys mid-stream must keep their values: pre-fix, window
    pads shadowed them and surfaced zeros."""
    af = np.array([-2.0, 1.0, np.inf], np.float32)
    bf = np.array([-1.0, np.inf, np.inf, np.inf, np.inf, np.inf, np.inf, np.inf, np.inf], np.float32)
    av = np.array([10.0, 11.0, 12.0], np.float32)
    bv = 100.0 + np.arange(9, dtype=np.float32)
    ko, vo = segmented_merge_kv(
        jnp.array(af), jnp.array(av), jnp.array(bf), jnp.array(bv), 4
    )
    rk, rv = np_merge_kv_oracle(af, av, bf, bv)
    np.testing.assert_array_equal(np.asarray(ko), rk)
    np.testing.assert_array_equal(np.asarray(vo), rv)


def test_segmented_merge_kv_non_divisible():
    rng = np.random.default_rng(9)
    ak = np.sort(rng.integers(0, 50, 13)).astype(np.int32)
    bk = np.sort(rng.integers(0, 50, 29)).astype(np.int32)
    av = np.arange(13, dtype=np.float32)
    bv = 100 + np.arange(29, dtype=np.float32)
    ko, vo = segmented_merge_kv(
        jnp.array(ak), jnp.array(av), jnp.array(bk), jnp.array(bv), 16
    )
    rk, rv = np_merge_kv_oracle(ak, av, bk, bv)
    np.testing.assert_array_equal(np.asarray(ko), rk)
    np.testing.assert_array_equal(np.asarray(vo), rv)


# --- k-way and distributed combine helpers (satellites) ---------------------


def test_merge_k_ragged_lens_with_sentinel_payloads():
    rng = np.random.default_rng(10)
    runs = np.sort(rng.integers(-20, 20, (5, 8)), axis=1).astype(np.int32)
    runs[0, -2:] = I32MAX  # real iinfo.max data in a *short* run's prefix
    lens = np.array([8, 3, 0, 5, 8], np.int32)
    out = np.asarray(merge_k(jnp.array(runs), lens=jnp.array(lens)))
    ref = np.sort(
        np.concatenate([runs[j, : lens[j]] for j in range(5)]), kind="stable"
    )
    np.testing.assert_array_equal(out[: lens.sum()], ref)
    assert (out[lens.sum() :] == I32MAX).all()


def test_merge_k_kv_duplicate_max_keys():
    """The pre-ragged tournament interleaved pads ahead of later runs' real
    iinfo.max keys, leaking zero values into the trimmed result."""
    kk = np.array([[0, 1], [I32MAX, I32MAX], [2, I32MAX]], np.int32)
    vv = np.array([[10, 11], [20, 21], [30, 31]], np.int32)
    mk, mv = merge_k_kv(jnp.array(kk), jnp.array(vv))
    # run-major stable flatten == run-priority tie-break
    order = np.argsort(kk.reshape(-1), kind="stable")
    np.testing.assert_array_equal(np.asarray(mk), kk.reshape(-1)[order])
    np.testing.assert_array_equal(np.asarray(mv), vv.reshape(-1)[order])


def test_merge_k_identity_with_lens_normalizes_tail():
    """k == 1 runs no merge round; caller-lens tails must still come back
    sentinel-normalized (keys) / zeroed (values), per the contract."""
    x = np.array([[1, 2, 3, 7, 0]], np.int32)
    out = np.asarray(merge_k(jnp.array(x), lens=jnp.array([3])))
    np.testing.assert_array_equal(out, [1, 2, 3, I32MAX, I32MAX])
    v = np.array([[10, 20, 30, 40, 50]], np.int32)
    ko, vo = merge_k_kv(jnp.array(x), jnp.array(v), lens=jnp.array([3]))
    np.testing.assert_array_equal(np.asarray(ko), [1, 2, 3, I32MAX, I32MAX])
    np.testing.assert_array_equal(np.asarray(vo), [10, 20, 30, 0, 0])


def test_pairwise_tree_merge_duplicate_max():
    """Tie-break doc'd behavior: lower-indexed run first; int runs whose
    data contains iinfo.max merge exactly (satellite regression)."""
    runs = np.array(
        [[1, 5, I32MAX, I32MAX], [2, I32MAX, I32MAX, I32MAX], [0, 3, 4, I32MAX]],
        np.int32,
    )
    out = np.asarray(_pairwise_tree_merge(jnp.array(runs)))
    np.testing.assert_array_equal(out, np.sort(runs.reshape(-1), kind="stable"))
    # ragged form: only the valid prefixes participate
    lens = np.array([2, 4, 1], np.int32)
    out = np.asarray(_pairwise_tree_merge(jnp.array(runs), lens=jnp.array(lens)))
    ref = np.sort(np.concatenate([runs[j, : lens[j]] for j in range(3)]), kind="stable")
    np.testing.assert_array_equal(out[: lens.sum()], ref)
    assert (out[lens.sum() :] == I32MAX).all()


# --- ragged consumers: MoE padded tokens, masked-vocab sampling -------------


def test_moe_token_counts_padding_invariance():
    import dataclasses

    from repro.configs import get_config
    from repro.models import init_params
    from repro.models import moe as moe_mod

    base = get_config("phi3.5-moe-42b-a6.6b").reduced()
    cfg = dataclasses.replace(
        base, num_experts=8, experts_per_token=2, moe_dispatch="merge_path"
    )
    params = init_params(cfg, jax.random.key(0))
    layer0 = jax.tree.map(lambda t: t[0], params["layers"])
    x = jax.random.normal(jax.random.key(1), (3, 32, cfg.d_model))
    moe_apply = jax.jit(moe_mod.moe_apply, static_argnums=2)
    # full counts == no counts, bit-compatible
    y_full = moe_apply(layer0["moe"], x, cfg)
    y_cnt = moe_apply(layer0["moe"], x, cfg, token_counts=jnp.array([32, 32, 32]))
    np.testing.assert_allclose(np.asarray(y_full), np.asarray(y_cnt), rtol=1e-6)
    # padding tokens must not affect valid outputs (they can't steal capacity)
    counts = jnp.array([32, 20, 7])
    y1 = moe_apply(layer0["moe"], x, cfg, token_counts=counts)
    x2 = x.at[1, 20:].set(99.0).at[2, 7:].set(-3.0)
    y2 = moe_apply(layer0["moe"], x2, cfg, token_counts=counts)
    for r, c in enumerate(np.asarray(counts)):
        np.testing.assert_allclose(
            np.asarray(y1)[r, :c], np.asarray(y2)[r, :c], rtol=1e-5, atol=1e-5
        )


def test_topk_batched_ragged_k_exceeds_width():
    """k > n truncates to the row width like topk_batched / lax.top_k,
    instead of crashing on a broadcast mismatch."""
    rng = np.random.default_rng(12)
    x = rng.standard_normal((3, 10)).astype(np.float32)
    lens = np.array([10, 4, 0], np.int32)
    v, i = topk_batched_ragged(jnp.array(x), 128, jnp.array(lens))
    assert v.shape == (3, 10) and i.shape == (3, 10)
    rv, ri = jax.lax.top_k(jnp.array(x[0]), 10)
    np.testing.assert_array_equal(np.asarray(v)[0], np.asarray(rv))
    assert (np.asarray(i)[2] == -1).all()


def test_sampler_empty_vocab_row_returns_minus_one():
    """A vocab_lens == 0 row deterministically samples -1 (documented
    out-of-band marker); live rows are never contaminated."""
    from repro.serving.sampler import topk_sample

    topk_sample = jax.jit(topk_sample, static_argnames=("k",))
    rng = np.random.default_rng(13)
    logits = rng.standard_normal((3, 64)).astype(np.float32)
    for seed in range(3):
        s = np.asarray(
            topk_sample(jnp.array(logits), jax.random.key(seed), k=8,
                        vocab_lens=jnp.array([0, 5, 64]))
        )
        assert s[0] == -1 and 0 <= s[1] < 5 and 0 <= s[2] < 64


def test_sampler_masked_vocab():
    from repro.serving.sampler import topk_sample, topp_sample

    topk_sample = jax.jit(topk_sample, static_argnames=("k",))
    topp_sample = jax.jit(topp_sample, static_argnames=("k_max",))
    rng = np.random.default_rng(11)
    logits = rng.standard_normal((4, 64)).astype(np.float32)
    lens = np.array([64, 30, 10, 5])
    for seed in range(3):
        s = np.asarray(
            topk_sample(jnp.array(logits), jax.random.key(seed), k=8,
                        vocab_lens=jnp.array(lens))
        )
        assert (s >= 0).all() and (s < lens).all()
        # a padded row samples identically to its unpadded truncation
        s_trunc = np.asarray(
            topk_sample(jnp.array(logits[1:2, :30]), jax.random.key(seed), k=8)
        )
        s_rag = np.asarray(
            topk_sample(jnp.array(logits[1:2]), jax.random.key(seed), k=8,
                        vocab_lens=jnp.array([30]))
        )
        assert s_trunc[0] == s_rag[0]
        sp = np.asarray(
            topp_sample(jnp.array(logits), jax.random.key(seed), p=0.8, k_max=8,
                        vocab_lens=jnp.array(lens))
        )
        assert (sp >= 0).all() and (sp < lens).all()
