"""Property + unit tests for the core Merge Path algorithms."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import (
    diagonal_intersections,
    merge,
    merge_kv,
    merge_sort,
    merge_sort_kv,
    partitioned_merge,
    segmented_merge,
    segmented_merge_kv,
    stable_argsort,
    topk_desc,
)

# bounded so int sentinels never collide with payloads
ints = st.integers(min_value=-10_000, max_value=10_000)

# The properties call jitted routes and draw lengths from a few fixed
# sizes (empty, one, non-pow2, pow2, long), so examples share compiles
# while the values are drawn per example.  Eager calls would re-trace the
# merge-path loops on every example.
_merge = jax.jit(merge)
_diagonal_intersections = jax.jit(diagonal_intersections)
_merge_sort = jax.jit(merge_sort)
_stable_argsort = jax.jit(stable_argsort)
_topk_desc = jax.jit(topk_desc, static_argnums=1)


def fixed_len_lists(elems, sizes):
    return st.sampled_from(sizes).flatmap(lambda n: st.lists(elems, min_size=n, max_size=n))


def sorted_arr(draw, n, dtype=np.int32):
    xs = draw(st.lists(ints, min_size=n, max_size=n))
    return np.sort(np.array(xs, dtype=dtype))


@st.composite
def two_sorted(draw, sizes=(0, 1, 7, 64, 200)):
    na = draw(st.sampled_from(sizes))
    nb = draw(st.sampled_from(sizes))
    return sorted_arr(draw, na), sorted_arr(draw, nb)


@settings(max_examples=60, deadline=None)
@given(two_sorted())
def test_merge_is_stable_sorted_permutation(ab):
    a, b = ab
    out = np.asarray(_merge(jnp.array(a), jnp.array(b)))
    ref = np.sort(np.concatenate([a, b]), kind="stable")
    assert out.shape == (len(a) + len(b),)
    np.testing.assert_array_equal(out, ref)


@settings(max_examples=40, deadline=None)
@given(two_sorted(sizes=(0, 1, 7, 64, 120)), st.integers(0, 500))
def test_diagonal_intersection_invariants(ab, dseed):
    a, b = ab
    n = len(a) + len(b)
    d = np.array([dseed % (n + 1)]) if n else np.array([0])
    ai = int(np.asarray(_diagonal_intersections(jnp.array(a), jnp.array(b), jnp.array(d)))[0])
    bi = int(d[0]) - ai
    assert 0 <= ai <= len(a) and 0 <= bi <= len(b)
    # the partition is a valid merge-path point: everything consumed is <=
    # everything not yet consumed (ties broken toward A)
    if ai > 0 and bi < len(b):
        assert a[ai - 1] <= b[bi]
    if bi > 0 and ai < len(a):
        assert b[bi - 1] < a[ai]


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 6), st.data())
def test_partitioned_merge_matches_merge(logp, data):
    p = 1 << logp
    # sizes chosen so |A|+|B| divisible by p
    total = p * data.draw(st.integers(1, 16))
    na = data.draw(st.integers(0, total))
    a = np.sort(np.array(data.draw(st.lists(ints, min_size=na, max_size=na)), np.int32))
    nb = total - na
    b = np.sort(np.array(data.draw(st.lists(ints, min_size=nb, max_size=nb)), np.int32))
    out = np.asarray(partitioned_merge(jnp.array(a), jnp.array(b), p))
    np.testing.assert_array_equal(out, np.sort(np.concatenate([a, b]), kind="stable"))


@settings(max_examples=30, deadline=None)
@given(st.data())
def test_segmented_merge_matches(data):
    seg = data.draw(st.sampled_from([4, 8, 16, 32]))
    nseg = data.draw(st.integers(1, 8))
    total = seg * nseg
    na = data.draw(st.integers(0, total))
    a = np.sort(np.array(data.draw(st.lists(ints, min_size=na, max_size=na)), np.int32))
    b = np.sort(np.array(data.draw(st.lists(ints, min_size=total - na, max_size=total - na)), np.int32))
    out = np.asarray(segmented_merge(jnp.array(a), jnp.array(b), seg))
    np.testing.assert_array_equal(out, np.sort(np.concatenate([a, b]), kind="stable"))


@settings(max_examples=40, deadline=None)
@given(fixed_len_lists(ints, (0, 1, 7, 64, 257, 500)))
def test_merge_sort(xs):
    x = np.array(xs, np.int32)
    out = np.asarray(_merge_sort(jnp.array(x)))
    np.testing.assert_array_equal(out, np.sort(x))


@settings(max_examples=40, deadline=None)
@given(fixed_len_lists(st.integers(0, 7), (1, 7, 64, 300)))
def test_stable_argsort_matches_numpy(keys):
    k = np.array(keys, np.int32)
    perm = np.asarray(_stable_argsort(jnp.array(k)))
    np.testing.assert_array_equal(perm, np.argsort(k, kind="stable"))


@settings(max_examples=40, deadline=None)
@given(fixed_len_lists(st.floats(-1e6, 1e6, allow_nan=False, allow_subnormal=False, width=32),
                       (1, 7, 64, 200)),
       st.sampled_from([1, 5, 20]))
def test_topk_matches_lax(xs, k):
    # normalize -0.0 -> 0.0: lax.top_k uses IEEE total order (0.0 > -0.0)
    # while merge-path compares them equal and breaks ties by index.
    x = np.array(xs, np.float32) + 0.0
    k = min(k, len(x))
    v, i = _topk_desc(jnp.array(x), k)
    rv, ri = jax.lax.top_k(jnp.array(x), k)
    np.testing.assert_allclose(np.asarray(v), np.asarray(rv))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))


def test_merge_kv_stability_a_priority():
    ak = jnp.array([1, 1, 2], jnp.int32)
    av = jnp.array([10, 11, 12], jnp.int32)
    bk = jnp.array([1, 2, 2], jnp.int32)
    bv = jnp.array([20, 21, 22], jnp.int32)
    ko, vo = merge_kv(ak, av, bk, bv)
    np.testing.assert_array_equal(np.asarray(ko), [1, 1, 1, 2, 2, 2])
    np.testing.assert_array_equal(np.asarray(vo), [10, 11, 20, 12, 21, 22])


def test_merge_sort_kv_stable():
    rng = np.random.default_rng(0)
    k = rng.integers(0, 5, 257).astype(np.int32)
    v = np.arange(257, dtype=np.int32)
    ks, vs = merge_sort_kv(jnp.array(k), jnp.array(v))
    order = np.argsort(k, kind="stable")
    np.testing.assert_array_equal(np.asarray(ks), k[order])
    np.testing.assert_array_equal(np.asarray(vs), v[order])


def test_segmented_merge_kv():
    rng = np.random.default_rng(1)
    ak = np.sort(rng.integers(0, 50, 48)).astype(np.int32)
    bk = np.sort(rng.integers(0, 50, 16)).astype(np.int32)
    av = np.arange(48, dtype=np.float32)
    bv = 100 + np.arange(16, dtype=np.float32)
    ko, vo = segmented_merge_kv(jnp.array(ak), jnp.array(av), jnp.array(bk), jnp.array(bv), 16)
    rk, rv = jax.lax.sort(
        (jnp.concatenate([jnp.array(ak), jnp.array(bk)]),
         jnp.concatenate([jnp.array(av), jnp.array(bv)])),
        is_stable=True, num_keys=1)
    np.testing.assert_array_equal(np.asarray(ko), np.asarray(rk))
    np.testing.assert_array_equal(np.asarray(vo), np.asarray(rv))


def test_empty_and_degenerate():
    e = jnp.array([], jnp.int32)
    a = jnp.array([1, 2, 3], jnp.int32)
    np.testing.assert_array_equal(np.asarray(merge(a, e)), [1, 2, 3])
    np.testing.assert_array_equal(np.asarray(merge(e, a)), [1, 2, 3])
    np.testing.assert_array_equal(np.asarray(merge_sort(e)), [])
    np.testing.assert_array_equal(np.asarray(merge_sort(jnp.array([5], jnp.int32))), [5])
