"""The bitonic tile engine against the merge-matrix oracle, bit for bit.

``engine="hier"`` merges each tile with a stable bitonic network whose
order is (key, source index), with pads keyed to the sentinel and indexed
past every real element.  Each case here aims at one way that order could
go wrong, and compares the raw bits of every output with
``engine="matrix"`` (so ``-0.0`` against ``0.0`` counts) and with a
stable-argsort oracle:

* every key equal, across A and B (only the index decides);
* real keys equal to ``max_sentinel`` beside pads;
* a flat sort round whose windows overrun into the neighbour run;
* a ragged fill (sentinel keys, zero values past each row's length);
* keys-only (unmasked) and key-value (masked) tiles;
* tiles 1024, 512 and 128 (whole vregs), 96 (padded to 128 inside the
  network) and 64 (one short row).

Each case makes two interpreted kernel calls (~1-2 s each).
"""

import numpy as np
import jax.numpy as jnp

from repro.kernels.merge_path import (
    merge_kv_batched_ragged_pallas,
    merge_kv_pallas,
    merge_pallas,
    sort_round_kv_pallas,
    sort_tail,
)

I32MAX = np.iinfo(np.int32).max


def _bits(x) -> np.ndarray:
    x = np.asarray(x)
    return x.view(f"u{x.dtype.itemsize}")


def _same(got, exp):
    np.testing.assert_array_equal(_bits(got), _bits(exp))


def _stable_merge(ak, av, bk, bv):
    keys, vals = np.concatenate([ak, bk]), np.concatenate([av, bv])
    perm = np.argsort(keys, kind="stable")
    return keys[perm], vals[perm]


def _engines(fn, *args, **kw):
    return fn(*args, engine="hier", **kw), fn(*args, engine="matrix", **kw)


def test_all_keys_equal_kv_tile1024():
    """Every key equal on both sides: the output is A's values in order,
    then B's, decided by the source index alone."""
    na, nb = 1500, 900
    ak, bk = np.full(na, 7, np.int32), np.full(nb, 7, np.int32)
    av = np.arange(na, dtype=np.int32)
    bv = np.arange(nb, dtype=np.int32) | np.int32(1 << 30)
    args = tuple(map(jnp.asarray, (ak, av, bk, bv)))
    (kh, vh), (km, vm) = _engines(merge_kv_pallas, *args, tile=1024)
    _same(kh, km)
    _same(vh, vm)
    rk, rv = _stable_merge(ak, av, bk, bv)
    _same(kh, rk)
    _same(vh, rv)


def test_sentinel_keys_beside_pads_kv_tile512():
    """Real ``iinfo.max`` keys on both sides, with window pads (also
    ``iinfo.max``) right behind them: each real key keeps its value."""
    rng = np.random.default_rng(21)
    na, nb = 1337, 611
    ak = np.sort(rng.integers(-4, 4, na)).astype(np.int32)
    bk = np.sort(rng.integers(-4, 4, nb)).astype(np.int32)
    ak[-400:] = I32MAX
    bk[-200:] = I32MAX
    av = rng.integers(1, 1 << 20, na).astype(np.int32)
    bv = -rng.integers(1, 1 << 20, nb).astype(np.int32)
    args = tuple(map(jnp.asarray, (ak, av, bk, bv)))
    (kh, vh), (km, vm) = _engines(merge_kv_pallas, *args, tile=512)
    _same(kh, km)
    _same(vh, vm)
    rk, rv = _stable_merge(ak, av, bk, bv)
    _same(kh, rk)
    _same(vh, rv)


def test_sort_round_overrun_into_neighbour_kv_tile128():
    """A flat sort round of runs of 128 at tile 128: windows near a run's
    end read on into the next run, whose keys are often smaller; those
    slots must sort as pads, last."""
    rng = np.random.default_rng(22)
    tile, width, m = 128, 128, 1024
    runs = np.sort(rng.integers(0, 40, (m // width, width)), axis=1).astype(np.int32)
    runs[1::2] -= 30  # B runs hold smaller keys than the A runs on either side
    kf = np.concatenate([runs.reshape(-1), np.full(sort_tail(tile), I32MAX, np.int32)])
    vf = np.concatenate([np.arange(m, dtype=np.int32), np.zeros(sort_tail(tile), np.int32)])
    (kh, vh), (km, vm) = _engines(sort_round_kv_pallas, jnp.asarray(kf), jnp.asarray(vf), width, tile=tile)
    _same(kh, km)
    _same(vh, vm)
    for p in range(m // (2 * width)):
        lo, mid, hi = 2 * p * width, (2 * p + 1) * width, (2 * p + 2) * width
        rk, rv = _stable_merge(kf[lo:mid], vf[lo:mid], kf[mid:hi], vf[mid:hi])
        _same(np.asarray(kh)[lo:hi], rk)
        _same(np.asarray(vh)[lo:hi], rv)
    _same(np.asarray(kh)[m:], kf[m:])
    _same(np.asarray(vh)[m:], vf[m:])


def test_ragged_fill_kv_tile96():
    """Ragged rows at a tile that is not a power of two (the network pads
    96 to 128): merged valid pairs, then sentinel keys with zero values;
    real ``iinfo.max`` keys among them."""
    rng = np.random.default_rng(23)
    bsz, n = 4, 300
    ak = np.sort(rng.integers(-5, 5, (bsz, n)), axis=1).astype(np.int32)
    bk = np.sort(rng.integers(-5, 5, (bsz, n)), axis=1).astype(np.int32)
    ak[:, -60:] = I32MAX
    bk[:, -60:] = I32MAX
    av = rng.integers(1, 1 << 20, (bsz, n)).astype(np.int32)
    bv = -rng.integers(1, 1 << 20, (bsz, n)).astype(np.int32)
    al = np.array([n, 0, 257, 95], np.int32)
    bl = np.array([n - 1, 130, 0, 97], np.int32)
    args = tuple(map(jnp.asarray, (ak, av, bk, bv, al, bl)))
    (kh, vh), (km, vm) = _engines(merge_kv_batched_ragged_pallas, *args, tile=96)
    _same(kh, km)
    _same(vh, vm)
    for r in range(bsz):
        rk, rv = _stable_merge(ak[r, : al[r]], av[r, : al[r]], bk[r, : bl[r]], bv[r, : bl[r]])
        k = len(rk)
        _same(np.asarray(kh)[r, :k], rk)
        _same(np.asarray(vh)[r, :k], rv)
        _same(np.asarray(kh)[r, k:], np.full(2 * n - k, I32MAX, np.int32))
        _same(np.asarray(vh)[r, k:], np.zeros(2 * n - k, np.int32))


def test_keys_only_signed_zeros_and_inf_tile64():
    """Keys-only float merge (unmasked tiles): ``-0.0`` and ``0.0`` compare
    equal but differ in bits, so only a stable merge puts every A zero,
    signs in order, before every B zero; real ``+inf`` keys tie with the
    window pads."""
    rng = np.random.default_rng(24)
    na, nb = 700, 433
    vals = np.array([-1.0, 0.0, 1.0, np.inf], np.float32)
    ak = np.sort(vals[rng.integers(0, 4, na)])
    bk = np.sort(vals[rng.integers(0, 4, nb)])
    ak[ak == 0] *= rng.choice([-1.0, 1.0], int((ak == 0).sum())).astype(np.float32)
    bk[bk == 0] *= rng.choice([-1.0, 1.0], int((bk == 0).sum())).astype(np.float32)
    h, m = _engines(merge_pallas, jnp.asarray(ak), jnp.asarray(bk), tile=64)
    _same(h, m)
    rk, _ = _stable_merge(ak, ak, bk, bk)
    _same(h, rk)
