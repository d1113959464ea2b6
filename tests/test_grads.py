"""Gradient tests for the kernel-path permutation VJPs.

The merge-path sort is a *stable permutation* (Siebert & Träff's co-rank
partition makes it well-defined even under duplicate keys), so the
kernel route's ``custom_vjp`` — forward saves the stable argsort,
backward is one inverse-gather scatter — must be **bit-identical** to
``jax.grad`` through the pure-JAX oracle route for any input, including
duplicate keys, ragged ``lens=``, sentinel-tied keys, and non-pow2
(padding-path) sizes.

Fuzzing comes in two tiers, mirroring ``test_merge_path.py``'s optional
hypothesis: property tests run where ``hypothesis`` is importable, and a
seeded deterministic sweep over the same regimes (duplicate-heavy value
pool including the f32 max-sentinel, ragged lens with empty rows,
non-pow2 n) always runs.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import repro.core as core
import repro.kernels.ops as kops
from grad_utils import fd_check, vjp_compare

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # offline container: the seeded sweep below still runs
    st = None

F32_MAX = float(np.finfo(np.float32).max)

# small value pool => heavy duplication; includes the f32 max-sentinel
# value so sentinel-tied keys are fuzzed too
VAL_POOL = np.array([-2.5, -1.0, 0.0, 0.5, 1.0, 1.5, F32_MAX], np.float32)


# The oracle routes run jitted, like the kernel routes: eager dispatch
# re-traces every loop of the merge-path sort on each call, while a jitted
# route compiles once per shape and the fuzz below reuses a few shapes.
_merge_sort = jax.jit(core.merge_sort)
_merge_sort_kv = jax.jit(core.merge_sort_kv)
_merge_sort_batched = jax.jit(core.merge_sort_batched)
_merge_sort_kv_batched = jax.jit(core.merge_sort_kv_batched)
_topk_batched = jax.jit(core.topk_batched, static_argnums=1)
_topk_batched_ragged = jax.jit(core.topk_batched_ragged, static_argnums=1)


def _pool_draw(rng, shape):
    return jnp.asarray(rng.choice(VAL_POOL, size=shape).astype(np.float32))


# ---------------------------------------------------------------------------
# deterministic seeded sweep (always runs)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed,n", [(0, 2), (1, 7), (4, 33)])
def test_sort_grad_bit_identical(seed, n):
    x = _pool_draw(np.random.default_rng(seed), (n,))
    vjp_compare(lambda v: kops.sort(v), lambda v: _merge_sort(v), [x], seed=seed)


@pytest.mark.parametrize("seed,n", [(0, 5), (2, 29)])
def test_sort_kv_grads_bit_identical(seed, n):
    rng = np.random.default_rng(seed)
    keys = _pool_draw(rng, (n,))
    vals = jnp.asarray(rng.standard_normal(n), jnp.float32)
    vjp_compare(
        lambda k, v: kops.sort_kv(k, v),
        lambda k, v: _merge_sort_kv(k, v),
        [keys, vals],
        seed=seed,
    )


@pytest.mark.parametrize("seed,n", [(0, 13)])
def test_sort_kv_int_payload_key_grads(seed, n):
    """Int payloads take the float0 branch; key grads still bit-match,
    and the tied-key permutation matches the oracle (stability)."""
    keys = _pool_draw(np.random.default_rng(seed), (n,))
    vals = jnp.arange(n, dtype=jnp.int32)[::-1]

    vjp_compare(
        lambda k: kops.sort_kv(k, vals)[0],
        lambda k: _merge_sort_kv(k, vals)[0],
        [keys],
        seed=seed,
    )
    np.testing.assert_array_equal(
        np.asarray(kops.sort_kv(keys, vals)[1]),
        np.asarray(_merge_sort_kv(keys, vals)[1]),
    )


@pytest.mark.parametrize("seed,b,n", [(0, 1, 9), (2, 2, 24)])
def test_sort_batched_grad_bit_identical(seed, b, n):
    x = _pool_draw(np.random.default_rng(seed), (b, n))
    vjp_compare(
        lambda v: kops.sort_batched(v), lambda v: _merge_sort_batched(v), [x],
        seed=seed,
    )


@pytest.mark.parametrize("seed,b,n", [(0, 2, 11), (1, 3, 24)])
def test_sort_kv_batched_grads_bit_identical(seed, b, n):
    rng = np.random.default_rng(seed)
    keys = _pool_draw(rng, (b, n))
    vals = jnp.asarray(rng.standard_normal((b, n)), jnp.float32)
    vjp_compare(
        lambda k, v: kops.sort_kv_batched(k, v),
        lambda k, v: _merge_sort_kv_batched(k, v),
        [keys, vals],
        seed=seed,
    )


@pytest.mark.parametrize("seed,b,n,k", [(0, 2, 12, 3), (2, 1, 9, 20)])
def test_topk_batched_grad_bit_identical(seed, b, n, k):
    x = _pool_draw(np.random.default_rng(seed), (b, n))
    vjp_compare(
        lambda v: kops.topk_batched(v, k)[0],
        lambda v: _topk_batched(v, k)[0],
        [x],
        seed=seed,
    )


@pytest.mark.parametrize(
    "seed,b,n,k,lens",
    [
        (0, 3, 12, 4, (0, 5, 12)),   # empty row + partial + full
        (1, 2, 9, 3, (1, 9)),        # non-pow2 n
    ],
)
def test_topk_batched_ragged_grads(seed, b, n, k, lens):
    """Ragged grads bit-match the oracle AND masked columns are zero."""
    x = _pool_draw(np.random.default_rng(seed), (b, n))
    lens = jnp.asarray(lens, jnp.int32)
    g = vjp_compare(
        lambda v: kops.topk_batched_ragged(v, k, lens)[0],
        lambda v: _topk_batched_ragged(v, k, lens)[0],
        [x],
        seed=seed,
    )
    dx = np.asarray(g[0])
    cols = np.arange(n)[None, :]
    masked = cols >= np.asarray(lens)[:, None]
    assert np.all(dx[masked] == 0.0), "cotangent leaked into masked (ragged) slots"


def test_sort_nonpow2_kernel_round_grad():
    """n=192 with tile=128: pow2-pad path + a wide Pallas round under AD."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.choice([-1.0, 0.0, 0.25, 1.0], size=192), jnp.float32)
    vjp_compare(
        lambda v: kops.sort(v, tile=128, leaf=32),
        lambda v: _merge_sort(v),
        [x],
    )


def test_sort_kv_batched_kernel_round_grad():
    """Wide flat-round kernel engaged for a batched kv sort under AD."""
    rng = np.random.default_rng(1)
    keys = jnp.asarray(rng.choice([0.0, 1.0, 2.0], size=(2, 192)), jnp.float32)
    vals = jnp.asarray(rng.standard_normal((2, 192)), jnp.float32)
    vjp_compare(
        lambda k, v: kops.sort_kv_batched(k, v, tile=128, leaf=64),
        lambda k, v: _merge_sort_kv_batched(k, v),
        [keys, vals],
    )


def test_sort_oracle_fd_check():
    """f64 central differences validate the oracle route the kernel is
    compared against (away from ties, where sort is differentiable)."""
    x = jnp.asarray([3.0, -1.5, 0.25, 7.0, -4.0, 2.0, 0.75, -0.5], jnp.float32)
    fd_check(lambda v: _merge_sort(v), [x], rtol=1e-6, atol=1e-9)


def test_topk_oracle_fd_check():
    x = jnp.asarray([[3.0, -1.5, 0.25, 7.0, -4.0, 2.0]], jnp.float32)
    fd_check(lambda v: core.topk_batched(v, 3)[0], [x], rtol=1e-6, atol=1e-9)


def test_moe_dispatch_pallas_grads_match_oracle_route():
    """moe_apply grads on merge_path_pallas == merge_path, bit-identical.

    seq*k = 512 slots exceeds the min int tile, so the flat Pallas round
    actually runs inside the differentiated forward.
    """
    import dataclasses
    from repro.configs.registry import get_config
    from repro.models import moe as moe_mod

    cfg = get_config("phi35-moe").reduced()
    cfg_k = dataclasses.replace(cfg, moe_dispatch="merge_path_pallas")
    params = moe_mod.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 256, cfg.d_model), jnp.float32)

    def loss(p, xx, c):
        return jnp.sum(moe_mod.moe_apply(p, xx, c) ** 2)

    grad = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)), static_argnums=2)
    (l_o, g_o) = grad(params, x, cfg)
    (l_k, g_k) = grad(params, x, cfg_k)
    assert float(l_o) == float(l_k)
    for lo, lk in zip(jax.tree.leaves(g_o), jax.tree.leaves(g_k)):
        np.testing.assert_array_equal(np.asarray(lo), np.asarray(lk))
    assert all(bool(jnp.any(l != 0)) for l in jax.tree.leaves(g_k))


# ---------------------------------------------------------------------------
# hypothesis property tests (run where hypothesis is available)
# ---------------------------------------------------------------------------

if st is not None:
    key_vals = st.sampled_from([float(v) for v in VAL_POOL])

    def _farr(vals):
        return jnp.asarray(np.array(vals, np.float32))

    # Lengths come from a few fixed sizes (tiny, non-pow2, past one tile)
    # so examples share compiles; the values, the sentinel ties and the
    # ragged lens are still drawn per example.
    @st.composite
    def dup_keys(draw, sizes=(2, 7, 33, 48)):
        n = draw(st.sampled_from(sizes))
        return _farr(draw(st.lists(key_vals, min_size=n, max_size=n)))

    @st.composite
    def dup_keys_batched(draw, rows=(1, 3), sizes=(2, 9, 24)):
        b = draw(st.sampled_from(rows))
        n = draw(st.sampled_from(sizes))
        rows = [draw(st.lists(key_vals, min_size=n, max_size=n)) for _ in range(b)]
        return _farr(rows)

    @settings(max_examples=40)
    @given(dup_keys())
    def test_sort_grad_bit_identical_prop(x):
        vjp_compare(lambda v: kops.sort(v), lambda v: _merge_sort(v), [x])

    @settings(max_examples=30)
    @given(dup_keys())
    def test_sort_kv_grads_bit_identical_prop(keys):
        rng = np.random.default_rng(keys.shape[0])
        vals = jnp.asarray(rng.standard_normal(keys.shape), jnp.float32)
        vjp_compare(
            lambda k, v: kops.sort_kv(k, v),
            lambda k, v: _merge_sort_kv(k, v),
            [keys, vals],
        )

    @settings(max_examples=30)
    @given(dup_keys_batched())
    def test_sort_kv_batched_grads_bit_identical_prop(keys):
        rng = np.random.default_rng(keys.shape[1])
        vals = jnp.asarray(rng.standard_normal(keys.shape), jnp.float32)
        vjp_compare(
            lambda k, v: kops.sort_kv_batched(k, v),
            lambda k, v: _merge_sort_kv_batched(k, v),
            [keys, vals],
        )

    @settings(max_examples=30)
    @given(dup_keys_batched(), st.sampled_from([1, 3, 8]), st.data())
    def test_topk_batched_ragged_grads_prop(x, k, data):
        bsz, n = x.shape
        lens = jnp.asarray(
            [data.draw(st.integers(0, n), label=f"len{i}") for i in range(bsz)],
            jnp.int32,
        )
        g = vjp_compare(
            lambda v: kops.topk_batched_ragged(v, k, lens)[0],
            lambda v: _topk_batched_ragged(v, k, lens)[0],
            [x],
        )
        dx = np.asarray(g[0])
        masked = np.arange(n)[None, :] >= np.asarray(lens)[:, None]
        assert np.all(dx[masked] == 0.0)
