"""Hierarchical two-level tile engine: bit-exactness + satellite coverage.

The property the whole PR rests on: for every kernel variant, the
hierarchical engine (level-2 sub-diagonal bisection + (S, S) leaf merge
matrices + O(T) gather apply) produces output **bit-identical** to the
single-level (T, T) merge-matrix engine — over fuzzed windows with
duplicates, payload keys tied with the sentinel (``+inf`` /
``iinfo.max``), ragged valid lengths, and non-divisible T/S combos.

Also covered: the flat sort rounds (padding hoisted out of the loop),
the (tile, leaf) autotune table, the env-overridable interpret default,
and the consumer routes (MoE dispatch, sampler, distributed sort).
"""

import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import batched as bat
from repro.core import merge_path as mp
from repro.kernels import ops, ref, tune
from repro.kernels.merge_path import (
    merge_batched_pallas,
    merge_batched_ragged_pallas,
    merge_kv_batched_ragged_pallas,
    merge_kv_pallas,
    merge_pallas,
)
from subproc import SUBPROCESS_TIMEOUT_S, jit_shard_map, src_env

I32MAX = np.iinfo(np.int32).max

# The core oracles run jitted: called eagerly, their loops are re-traced on
# every call, which costs more than the interpreted kernels under test.
_core_merge_batched = jax.jit(bat.merge_batched)
_core_merge_batched_ragged = jax.jit(bat.merge_batched_ragged)
_core_merge_kv_batched_ragged = jax.jit(bat.merge_kv_batched_ragged)
_core_topk_batched = jax.jit(bat.topk_batched, static_argnums=1)
_core_topk_batched_ragged = jax.jit(bat.topk_batched_ragged, static_argnums=1)


def _eq(got, exp):
    np.testing.assert_array_equal(
        np.asarray(got).astype(np.float64), np.asarray(exp).astype(np.float64)
    )


def _fuzz_sorted(rng, n, dtype, sentinel_ties: bool):
    """Sorted 1-D data with heavy duplicates; optionally sentinel-valued
    payload tail (+inf / iinfo.max) — the classic pad-shadowing trap."""
    if np.dtype(dtype) == np.int32:
        x = np.sort(rng.integers(-8, 8, n)).astype(np.int32)
        if sentinel_ties and n >= 2:
            x[-(n // 4 or 1):] = I32MAX
    else:
        x = np.sort(rng.standard_normal(n)).astype(np.float32)
        if sentinel_ties and n >= 2:
            x[-(n // 4 or 1):] = np.inf
    return x


# ---------------------------------------------------------------------------
# Fuzzed bit-exactness: hier == matrix == oracle
# ---------------------------------------------------------------------------

# (seed, tile, leaf) — leaves chosen to hit S | T, S ∤ T, S == T, S > T
FUZZ_1D = [
    (s, t, l)
    for s, (t, l) in enumerate(
        [
            (64, 8), (64, 24), (128, 32), (128, 100), (128, 128),
            (192, 32), (192, 56), (256, 16), (256, 192), (96, 32),
            (128, 8), (256, 256), (160, 48), (64, 64), (256, 11),
        ]
    )
]


@pytest.mark.parametrize("seed,tile,leaf", FUZZ_1D)
@pytest.mark.parametrize("dtype", [np.int32, np.float32], ids=["i32", "f32"])
def test_fuzz_1d_hier_matrix_oracle(seed, tile, leaf, dtype):
    rng = np.random.default_rng(seed)
    na, nb = int(rng.integers(0, 1500)), int(rng.integers(0, 1500))
    ties = bool(rng.integers(0, 2))
    a = jnp.asarray(_fuzz_sorted(rng, na, dtype, ties))
    b = jnp.asarray(_fuzz_sorted(rng, nb, dtype, ties))
    h = merge_pallas(a, b, tile=tile, leaf=leaf, engine="hier")
    m = merge_pallas(a, b, tile=tile, leaf=leaf, engine="matrix")
    _eq(h, m)
    _eq(h, ref.merge_ref(a, b))


@pytest.mark.parametrize("seed,tile,leaf", [(0, 128, 32), (1, 128, 48), (2, 256, 17), (3, 64, 64)])
def test_fuzz_kv_sentinel_tied_keys(seed, tile, leaf):
    """Payload keys equal to the sentinel must keep their values through
    both engines (pads are excluded by index, never by comparison)."""
    rng = np.random.default_rng(100 + seed)
    na, nb = int(rng.integers(1, 1200)), int(rng.integers(1, 1200))
    ak = _fuzz_sorted(rng, na, np.int32, True)
    bk = _fuzz_sorted(rng, nb, np.int32, True)
    av = np.arange(na, dtype=np.float32)
    bv = 10_000 + np.arange(nb, dtype=np.float32)
    args = tuple(map(jnp.asarray, (ak, av, bk, bv)))
    kh, vh = merge_kv_pallas(*args, tile=tile, leaf=leaf, engine="hier")
    km, vm = merge_kv_pallas(*args, tile=tile, leaf=leaf, engine="matrix")
    _eq(kh, km)
    _eq(vh, vm)
    rk, rv = ref.merge_kv_ref(*args)
    _eq(kh, rk)
    _eq(vh, rv)


@pytest.mark.parametrize("seed,tile,leaf", [(0, 64, 16), (1, 128, 40), (2, 128, 128), (3, 96, 32)])
def test_fuzz_batched_hier_vs_matrix(seed, tile, leaf):
    rng = np.random.default_rng(200 + seed)
    bsz, n = int(rng.integers(1, 5)), int(rng.integers(2, 600))
    a = jnp.asarray(np.sort(rng.standard_normal((bsz, n)), axis=1).astype(np.float32))
    b = jnp.asarray(np.sort(rng.standard_normal((bsz, n)), axis=1).astype(np.float32))
    h = merge_batched_pallas(a, b, tile=tile, leaf=leaf, engine="hier")
    m = merge_batched_pallas(a, b, tile=tile, leaf=leaf, engine="matrix")
    _eq(h, m)
    _eq(h, _core_merge_batched(a, b))


@pytest.mark.parametrize("seed,tile,leaf", [(0, 64, 16), (1, 128, 24), (2, 128, 100), (3, 256, 32)])
def test_fuzz_ragged_hier_vs_matrix(seed, tile, leaf):
    """Ragged rows: full outputs (incl. the visible sentinel tails) must be
    bit-identical across engines AND to the fused core path."""
    rng = np.random.default_rng(300 + seed)
    bsz, n = int(rng.integers(1, 5)), int(rng.integers(2, 500))
    a = jnp.asarray(np.sort(rng.integers(-6, 6, (bsz, n)), axis=1).astype(np.int32))
    b = jnp.asarray(np.sort(rng.integers(-6, 6, (bsz, n)), axis=1).astype(np.int32))
    al = jnp.asarray(rng.integers(0, n + 1, bsz), jnp.int32)
    bl = jnp.asarray(rng.integers(0, n + 1, bsz), jnp.int32)
    h = merge_batched_ragged_pallas(a, b, al, bl, tile=tile, leaf=leaf, engine="hier")
    m = merge_batched_ragged_pallas(a, b, al, bl, tile=tile, leaf=leaf, engine="matrix")
    _eq(h, m)
    _eq(h, _core_merge_batched_ragged(a, b, al, bl))


@pytest.mark.parametrize("seed,tile,leaf", [(0, 64, 24), (1, 128, 32), (2, 128, 56)])
def test_fuzz_ragged_kv_sentinel_ties(seed, tile, leaf):
    """Ragged kv with sentinel-tied payload keys: valid +inf/iinfo.max keys
    keep their values; sentinel-pad tails carry zero values, identically
    across engines and vs the core ragged kv merge."""
    rng = np.random.default_rng(400 + seed)
    bsz, n = int(rng.integers(1, 4)), int(rng.integers(4, 400))
    ak = np.sort(rng.integers(-5, 5, (bsz, n)), axis=1).astype(np.int32)
    bk = np.sort(rng.integers(-5, 5, (bsz, n)), axis=1).astype(np.int32)
    ak[:, -max(1, n // 5):] = I32MAX  # real payloads tied with the pad sentinel
    bk[:, -max(1, n // 5):] = I32MAX
    av = rng.standard_normal((bsz, n)).astype(np.float32)
    bv = rng.standard_normal((bsz, n)).astype(np.float32)
    al = jnp.asarray(rng.integers(0, n + 1, bsz), jnp.int32)
    bl = jnp.asarray(rng.integers(0, n + 1, bsz), jnp.int32)
    args = tuple(map(jnp.asarray, (ak, av, bk, bv))) + (al, bl)
    kh, vh = merge_kv_batched_ragged_pallas(*args, tile=tile, leaf=leaf, engine="hier")
    km, vm = merge_kv_batched_ragged_pallas(*args, tile=tile, leaf=leaf, engine="matrix")
    _eq(kh, km)
    _eq(vh, vm)
    rk, rv = _core_merge_kv_batched_ragged(*args)
    _eq(kh, rk)
    _eq(vh, rv)


# ---------------------------------------------------------------------------
# Flat sort rounds (hoisted padding)
# ---------------------------------------------------------------------------


def test_sort_flat_rounds_vs_numpy():
    rng = np.random.default_rng(7)
    for n in (1, 2, 777, 3000):
        x = jnp.asarray(rng.standard_normal(n).astype(np.float32))
        _eq(ops.sort(x, tile=64), np.sort(np.asarray(x)))


def test_sort_rejects_non_pow2_tile():
    """Flat sort rounds need tile | 2*width — an explicit non-pow2 tile is
    an error, not a silent rewrite (merge wrappers still honor any tile)."""
    x = jnp.arange(512, dtype=jnp.float32)
    with pytest.raises(ValueError, match="power of two"):
        ops.sort(x, tile=200)
    with pytest.raises(ValueError, match="power of two"):
        ops.sort_kv_batched(x[None, :], x[None, :].astype(jnp.int32), tile=96)


def test_sort_matrix_engine_equivalence():
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.integers(-50, 50, 600).astype(np.int32))
    _eq(ops.sort(x, tile=64, engine="matrix"), ops.sort(x, tile=64, engine="hier"))


def test_sort_kv_flat_rounds_stable():
    rng = np.random.default_rng(9)
    k = jnp.asarray(rng.integers(0, 6, 2048).astype(np.int32))
    v = jnp.arange(2048, dtype=jnp.int32)
    ks, vs = ops.sort_kv(k, v, tile=128)
    rk, rv = ref.sort_kv_ref(k, v)
    _eq(ks, rk)
    _eq(vs, rv)


def test_sort_batched_rows_never_mix():
    rng = np.random.default_rng(10)
    x = jnp.asarray(rng.standard_normal((6, 700)).astype(np.float32))
    _eq(ops.sort_batched(x, tile=128), np.sort(np.asarray(x), axis=1))


def test_sort_kv_batched_is_stable_argsort():
    rng = np.random.default_rng(11)
    k = jnp.asarray(rng.integers(0, 9, (4, 900)).astype(np.int32))
    v = jnp.broadcast_to(jnp.arange(900, dtype=jnp.int32)[None, :], (4, 900))
    ks, vs = ops.sort_kv_batched(k, v, tile=128)
    _eq(ks, np.sort(np.asarray(k), axis=1))
    _eq(vs, np.argsort(np.asarray(k), axis=1, kind="stable"))


def test_ops_topk_matches_core_and_lax():
    rng = np.random.default_rng(12)
    x = jnp.asarray(rng.standard_normal((3, 1500)).astype(np.float32))
    vals, idx = ops.topk_batched(x, 25, tile=128)
    lv, li = jax.lax.top_k(x, 25)
    _eq(vals, lv)
    _eq(idx, li)
    # int rows containing iinfo.min (flip_desc exactness)
    xi = jnp.asarray(rng.integers(-100, 100, (2, 640)).astype(np.int32))
    xi = xi.at[0, 0].set(np.iinfo(np.int32).min)
    vi, ii = ops.topk_batched(xi, 10, tile=64)
    cv, ci = _core_topk_batched(xi, 10)
    _eq(vi, cv)
    _eq(ii, ci)


def test_ops_topk_ragged_matches_core():
    rng = np.random.default_rng(13)
    x = jnp.asarray(rng.standard_normal((4, 800)).astype(np.float32))
    lens = jnp.asarray([800, 500, 3, 0], jnp.int32)
    vals, idx = ops.topk_batched_ragged(x, 20, lens, tile=128)
    cv, ci = _core_topk_batched_ragged(x, 20, lens)
    _eq(vals, cv)
    _eq(idx, ci)


# ---------------------------------------------------------------------------
# Autotune table
# ---------------------------------------------------------------------------


def test_tune_pick_sane():
    for n in (16, 1000, 1 << 12, 1 << 15, 1 << 20):
        for dt in (jnp.float32, jnp.int32, jnp.bfloat16):
            tile, leaf = tune.pick(n, dt)
            assert tile & (tile - 1) == 0, (n, dt, tile)
            assert 1 <= leaf <= tile
    # tiny problems never get a tile wider than the (pow2-rounded) problem
    tile, _ = tune.pick(16, jnp.float32)
    assert tile <= 128


def test_tune_autotune_updates_table():
    best = tune.autotune(512, jnp.float32, tiles=(128, 256), leaves=(16, 32), iters=1)
    assert best[0] in (128, 256) and best[1] in (16, 32)
    assert tune._TABLE[("f", tune._bucket(512))] == best
    # restore the shipped entry so other tests see the defaults
    tune._TABLE.clear()
    tune._TABLE.update(tune.DEFAULT_TABLE)


# ---------------------------------------------------------------------------
# Interpret default (backend-derived, env override for tests)
# ---------------------------------------------------------------------------


INTERPRET_ENVS = [("0", "False"), ("false", "False"), ("1", "True"), (None, "True"), ("", "True")]


@pytest.fixture(scope="module")
def interpret_defaults():
    """``default_interpret()`` under each env value, from one fresh CPU child
    (the env is read per call, so one import serves every case)."""
    code = (
        "import os\n"
        "from repro.kernels import ops\n"
        f"for env in {[env for env, _ in INTERPRET_ENVS]!r}:\n"
        "    os.environ.pop('REPRO_PALLAS_INTERPRET', None)\n"
        "    if env is not None:\n"
        "        os.environ['REPRO_PALLAS_INTERPRET'] = env\n"
        "    print(repr(env), ops.default_interpret())\n"
    )
    e = src_env(JAX_PLATFORMS="cpu")
    e.pop("REPRO_PALLAS_INTERPRET", None)
    out = subprocess.run(
        [sys.executable, "-c", code], env=e, capture_output=True, text=True, check=True,
        timeout=SUBPROCESS_TIMEOUT_S,
    )
    return dict(line.rsplit(" ", 1) for line in out.stdout.strip().splitlines())


@pytest.mark.parametrize("env,expected", INTERPRET_ENVS)
def test_interpret_env_default(interpret_defaults, env, expected):
    """Unset (or empty) the backend decides — the CPU here interprets; a
    non-empty REPRO_PALLAS_INTERPRET overrides it either way."""
    assert interpret_defaults[repr(env)] == expected


def test_interpret_default_follows_backend(monkeypatch):
    """Decided per call, not at import: the same process compiles once the
    backend is a TPU."""
    from repro.kernels import merge_path as mpk

    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(mpk.jax, "default_backend", lambda: "tpu")
    assert mpk.default_interpret() is False
    assert mpk._interp(True) is True
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert mpk.default_interpret() is True


# ---------------------------------------------------------------------------
# Consumer routes
# ---------------------------------------------------------------------------


def test_moe_positions_pallas_backend_parity():
    from repro.models.moe import _positions_merge_path_batched

    core = jax.jit(_positions_merge_path_batched, static_argnums=1)
    rng = np.random.default_rng(14)
    fe = jnp.asarray(rng.integers(0, 8, (3, 640)).astype(np.int32))
    _eq(
        _positions_merge_path_batched(fe, 8, None, "pallas"),
        core(fe, 8),
    )
    sl = jnp.asarray([640, 200, 0], jnp.int32)
    _eq(
        _positions_merge_path_batched(fe, 8, sl, "pallas"),
        core(fe, 8, sl),
    )


def test_sampler_pallas_backend_parity():
    from repro.serving.sampler import topk_sample, topp_sample

    static = ("backend", "tile")
    topk_sample = jax.jit(topk_sample, static_argnames=static)
    topp_sample = jax.jit(topp_sample, static_argnames=static)
    rng = np.random.default_rng(15)
    logits = jnp.asarray(rng.standard_normal((3, 1024)).astype(np.float32))
    key = jax.random.key(21)
    _eq(topk_sample(logits, key, backend="pallas", tile=128), topk_sample(logits, key))
    vl = jnp.asarray([1024, 700, 40], jnp.int32)
    _eq(
        topk_sample(logits, key, vocab_lens=vl, backend="pallas", tile=128),
        topk_sample(logits, key, vocab_lens=vl),
    )
    _eq(topp_sample(logits, key, backend="pallas", tile=128), topp_sample(logits, key))


def test_distributed_sort_pallas_local(monkeypatch):
    from repro.core import distributed as dist
    from repro.core import distributed_sort

    # compile the mapped body once instead of dispatching it per primitive;
    # the guard around it still runs eagerly
    monkeypatch.setattr(dist, "shard_map", jit_shard_map(dist.shard_map))

    rng = np.random.default_rng(16)
    x = jnp.asarray(rng.standard_normal(512).astype(np.float32))
    out_c, cnt_c, ovf_c = distributed_sort(x)
    out_p, cnt_p, ovf_p = distributed_sort(x, local_sort="pallas")
    _eq(out_p, out_c)
    _eq(cnt_p, cnt_c)
    assert not bool(ovf_p)
