"""Batched & k-way Merge Path subsystem (`repro.core.batched` + the 2-D
grid Pallas kernels).  Pure pytest — no hypothesis, so this file is the
tier-1 coverage for the batched API in offline containers."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import (
    merge,
    merge_kv,
    merge_batched,
    merge_kv_batched,
    merge_k,
    merge_k_kv,
    merge_sort_batched,
    merge_sort_k,
    merge_sort_kv_batched,
    searchsorted_batched,
    stable_argsort_batched,
    topk_batched,
)
from repro.kernels import merge_batched_pallas, merge_kv_batched_pallas
from repro.kernels import ops


def sorted_rows(rng, b, n, lo=-1000, hi=1000, dtype=np.int32):
    return np.sort(rng.integers(lo, hi, (b, n)), axis=1).astype(dtype)


# --- fused batched primitives ------------------------------------------------

def test_searchsorted_batched_matches_numpy():
    rng = np.random.default_rng(0)
    s = sorted_rows(rng, 6, 50)
    q = rng.integers(-1100, 1100, (6, 33)).astype(np.int32)
    for side in ("left", "right"):
        got = np.asarray(searchsorted_batched(jnp.array(s), jnp.array(q), side))
        ref = np.stack([np.searchsorted(s[i], q[i], side=side) for i in range(6)])
        np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("na,nb", [(40, 40), (100, 7), (7, 100), (1, 1)])
def test_merge_batched_matches_vmapped_merge(na, nb):
    """Uneven |A| != |B| included: batched == vmapped pairwise, bit-exact."""
    rng = np.random.default_rng(na * 1000 + nb)
    a = sorted_rows(rng, 5, na)
    b = sorted_rows(rng, 5, nb)
    out = np.asarray(merge_batched(jnp.array(a), jnp.array(b)))
    ref = np.asarray(jax.vmap(merge)(jnp.array(a), jnp.array(b)))
    np.testing.assert_array_equal(out, ref)


def test_merge_batched_empty_rows():
    """Zero-width sides: (B, 0) merges are the identity on the other side."""
    rng = np.random.default_rng(1)
    a = sorted_rows(rng, 4, 9)
    e = jnp.zeros((4, 0), jnp.int32)
    np.testing.assert_array_equal(np.asarray(merge_batched(jnp.array(a), e)), a)
    np.testing.assert_array_equal(np.asarray(merge_batched(e, jnp.array(a))), a)
    both = merge_batched(e, e)
    assert both.shape == (4, 0)


def test_merge_kv_batched_stability_a_priority():
    """Duplicate keys: ties take A first and preserve in-array order, per row."""
    ak = jnp.array([[1, 1, 2], [5, 5, 5]], jnp.int32)
    av = jnp.array([[10, 11, 12], [10, 11, 12]], jnp.int32)
    bk = jnp.array([[1, 2, 2], [5, 5, 6]], jnp.int32)
    bv = jnp.array([[20, 21, 22], [20, 21, 22]], jnp.int32)
    ko, vo = merge_kv_batched(ak, av, bk, bv)
    np.testing.assert_array_equal(np.asarray(ko), [[1, 1, 1, 2, 2, 2], [5, 5, 5, 5, 5, 6]])
    np.testing.assert_array_equal(np.asarray(vo), [[10, 11, 20, 12, 21, 22], [10, 11, 12, 20, 21, 22]])


def test_merge_sort_batched_matches_jnp_sort():
    rng = np.random.default_rng(2)
    x = rng.standard_normal((8, 321)).astype(np.float32)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(merge_sort_batched)(jnp.array(x))),
        np.asarray(jnp.sort(jnp.array(x), axis=1)),
    )


def test_merge_sort_kv_batched_stable():
    rng = np.random.default_rng(3)
    k = rng.integers(0, 5, (4, 130)).astype(np.int32)
    v = np.broadcast_to(np.arange(130, dtype=np.int32), (4, 130)).copy()
    ks, vs = jax.jit(merge_sort_kv_batched)(jnp.array(k), jnp.array(v))
    for r in range(4):
        order = np.argsort(k[r], kind="stable")
        np.testing.assert_array_equal(np.asarray(ks)[r], k[r][order])
        np.testing.assert_array_equal(np.asarray(vs)[r], v[r][order])


def test_stable_argsort_and_topk_batched():
    rng = np.random.default_rng(4)
    x = rng.standard_normal((6, 200)).astype(np.float32)
    perm = np.asarray(jax.jit(stable_argsort_batched)(jnp.array(x)))
    for r in range(6):
        np.testing.assert_array_equal(perm[r], np.argsort(x[r], kind="stable"))
    v, i = jax.jit(topk_batched, static_argnums=1)(jnp.array(x), 17)
    rv, ri = jax.lax.top_k(jnp.array(x), 17)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(rv))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))


# --- k-way tournament merges -------------------------------------------------

def test_merge_k_identity_k1():
    x = np.sort(np.random.default_rng(5).integers(-50, 50, 13)).astype(np.int32)
    np.testing.assert_array_equal(np.asarray(merge_k([jnp.array(x)])), x)
    np.testing.assert_array_equal(np.asarray(merge_k(jnp.array(x)[None, :])), x)


@pytest.mark.parametrize("k", [2, 3, 5, 8])
def test_merge_k_stacked_matches_sort(k):
    """k > 2 tournaments (incl. non-power-of-two k) agree with the oracle."""
    rng = np.random.default_rng(k)
    runs = np.sort(rng.integers(-100, 100, (k, 16)), axis=1).astype(np.int32)
    out = np.asarray(merge_k(jnp.array(runs)))
    np.testing.assert_array_equal(out, np.sort(runs.reshape(-1), kind="stable"))


def test_merge_k_ragged_runs():
    rng = np.random.default_rng(6)
    runs = [np.sort(rng.integers(-40, 40, n)).astype(np.int32) for n in (5, 0, 12, 3, 9)]
    out = np.asarray(merge_k([jnp.array(r) for r in runs]))
    np.testing.assert_array_equal(out, np.sort(np.concatenate(runs)))


def test_merge_k_kv_stable_across_runs():
    """Ties resolve toward the lower-indexed run, preserving in-run order."""
    rng = np.random.default_rng(7)
    kk = np.sort(rng.integers(0, 6, (4, 8)), axis=1).astype(np.int32)
    vv = np.arange(32, dtype=np.int32).reshape(4, 8)
    mk, mv = merge_k_kv(jnp.array(kk), jnp.array(vv))
    order = np.argsort(kk.reshape(-1), kind="stable")  # run-major flatten == run priority
    np.testing.assert_array_equal(np.asarray(mk), kk.reshape(-1)[order])
    np.testing.assert_array_equal(np.asarray(mv), vv.reshape(-1)[order])


@pytest.mark.parametrize("k", [1, 2, 4, 8])
def test_merge_sort_k_matches_jnp_sort(k):
    """n = 203 pads to 256: for k = 8 the last round merges only 4 runs."""
    rng = np.random.default_rng(10 + k)
    x = rng.standard_normal(203).astype(np.float32)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(merge_sort_k, static_argnums=1)(jnp.array(x), k)),
        np.asarray(jnp.sort(jnp.array(x))),
    )


def test_merge_sort_k_rejects_non_power_of_two():
    with pytest.raises(ValueError):
        merge_sort_k(jnp.arange(8, dtype=jnp.int32), 3)


# --- 2-D grid Pallas kernels -------------------------------------------------

@pytest.mark.parametrize("na,nb,tile", [(300, 212, 128), (128, 128, 128), (100, 30, 64)])
def test_merge_batched_pallas_matches_vmapped_merge(na, nb, tile):
    """Non-divisible tile sizes included: (na+nb) % tile != 0 cases."""
    rng = np.random.default_rng(na + nb + tile)
    a = np.sort(rng.standard_normal((3, na)), axis=1).astype(np.float32)
    b = np.sort(rng.standard_normal((3, nb)), axis=1).astype(np.float32)
    out = np.asarray(merge_batched_pallas(jnp.array(a), jnp.array(b), tile=tile))
    ref = np.asarray(jax.vmap(merge)(jnp.array(a), jnp.array(b)))
    np.testing.assert_array_equal(out, ref)


def test_merge_kv_batched_pallas_matches_vmapped_merge_kv():
    rng = np.random.default_rng(8)
    ak = sorted_rows(rng, 3, 260)
    bk = sorted_rows(rng, 3, 190)
    av = rng.integers(0, 10**6, (3, 260)).astype(np.int32)
    bv = rng.integers(0, 10**6, (3, 190)).astype(np.int32)
    ko, vo = merge_kv_batched_pallas(
        jnp.array(ak), jnp.array(av), jnp.array(bk), jnp.array(bv), tile=128
    )
    rk, rv = jax.vmap(merge_kv)(jnp.array(ak), jnp.array(av), jnp.array(bk), jnp.array(bv))
    np.testing.assert_array_equal(np.asarray(ko), np.asarray(rk))
    np.testing.assert_array_equal(np.asarray(vo), np.asarray(rv))


def test_ops_merge_batched_both_dispatch_paths():
    rng = np.random.default_rng(9)
    a = np.sort(rng.standard_normal((4, 100)), axis=1).astype(np.float32)
    b = np.sort(rng.standard_normal((4, 80)), axis=1).astype(np.float32)
    ref = np.asarray(jax.vmap(merge)(jnp.array(a), jnp.array(b)))
    # small path (fused pure-JAX) and kernel path must agree bit-exactly
    np.testing.assert_array_equal(
        np.asarray(ops.merge_batched(jnp.array(a), jnp.array(b), tile=512)), ref
    )
    np.testing.assert_array_equal(
        np.asarray(ops.merge_batched(jnp.array(a), jnp.array(b), tile=64)), ref
    )


def test_ops_sort_wide_rounds_on_batched_kernel():
    rng = np.random.default_rng(11)
    x = rng.standard_normal(2048).astype(np.float32)
    np.testing.assert_array_equal(
        np.asarray(ops.sort(jnp.array(x), tile=256)), np.asarray(jnp.sort(jnp.array(x)))
    )
    k = rng.integers(0, 7, 2048).astype(np.int32)
    v = np.arange(2048, dtype=np.int32)
    ks, vs = ops.sort_kv(jnp.array(k), jnp.array(v), tile=256)
    order = np.argsort(k, kind="stable")
    np.testing.assert_array_equal(np.asarray(ks), k[order])
    np.testing.assert_array_equal(np.asarray(vs), v[order])


# --- acceptance: the issue's (64, 4096) case --------------------------------

def test_acceptance_64x4096_bit_exact():
    """merge_batched on a (64, 4096)+(64, 4096) batch == vmapped core.merge,
    bit-exact (stable, A-priority), on both the fused core path and the
    2-D-grid Pallas kernel."""
    rng = np.random.default_rng(64)
    a = np.sort(rng.standard_normal((64, 4096)), axis=1).astype(np.float32)
    b = np.sort(rng.standard_normal((64, 4096)), axis=1).astype(np.float32)
    ref = np.asarray(jax.vmap(merge)(jnp.array(a), jnp.array(b)))
    np.testing.assert_array_equal(np.asarray(merge_batched(jnp.array(a), jnp.array(b))), ref)
    out = np.asarray(merge_batched_pallas(jnp.array(a), jnp.array(b), tile=1024))
    np.testing.assert_array_equal(out, ref)
