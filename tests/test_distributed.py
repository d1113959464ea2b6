"""Multi-device tests for core.distributed — run in a subprocess with 8
fake CPU devices so the main pytest process keeps 1 device (per spec).

Each child compiles the shard_map bodies (``subproc.JIT_SHARD_MAP``) and
turns a guard fallback into an error: a broken exchange must fail here,
not hide behind the gather or re-sort fallback that serves the call."""

from subproc import JIT_SHARD_MAP, run_with_devices

STRICT = JIT_SHARD_MAP + """
import warnings
from repro.runtime.resilience import FallbackWarning
warnings.simplefilter("error", FallbackWarning)
"""


def run_strict(code: str, n: int = 8) -> None:
    run_with_devices(code, n, prelude=STRICT)


def test_distributed_merge():
    run_strict("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import distributed_merge
        rng = np.random.default_rng(1)
        for na, nb in [(64, 64), (96, 32), (32, 96)]:
            a = np.sort(rng.standard_normal(na)).astype(np.float32)
            b = np.sort(rng.standard_normal(nb)).astype(np.float32)
            out = np.asarray(distributed_merge(jnp.array(a), jnp.array(b)))
            assert np.allclose(out, np.sort(np.concatenate([a, b])))
        print("ok")
    """)


def test_distributed_sort():
    run_strict("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import distributed_sort
        rng = np.random.default_rng(2)
        x = rng.standard_normal(256).astype(np.float32)
        s, cnt, ovf = distributed_sort(jnp.array(x))
        s, cnt, ovf = np.asarray(s), np.asarray(cnt), np.asarray(ovf)
        assert not ovf
        P = 8; percap = s.shape[0] // P
        got = np.concatenate([s[i*percap:i*percap+cnt[i]] for i in range(P)])
        assert np.allclose(got, np.sort(x))
        print("ok")
    """)


def test_distributed_topk():
    run_strict("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import distributed_topk
        rng = np.random.default_rng(3)
        x = rng.standard_normal(512).astype(np.float32)
        v, i = distributed_topk(jnp.array(x), 16)
        rv, ri = jax.lax.top_k(jnp.array(x), 16)
        assert np.allclose(np.asarray(v), np.asarray(rv))
        assert (np.asarray(i) == np.asarray(ri)).all()
        print("ok")
    """)


def test_exchange_window_vs_gather_fuzz():
    """The bandwidth-optimal window exchange is bit-identical to the
    all-gather oracle: duplicates, sentinel-tied kv keys, ragged /
    non-divisible shards, P in {2, 4, 8}, keys-only / kv / batched — and
    the max-window/max-piece bounds of window_bounds() really bound the
    true windows (so the fixed-size exchange buffers can never silently
    truncate)."""
    run_strict("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.core import (distributed_merge, distributed_merge_kv,
                                distributed_merge_kv_batched, window_bounds)

        def np_cuts(a, b, diags):
            # numpy oracle for the A-priority diagonal intersections
            out = []
            for d in diags:
                lo, hi = max(0, d - len(b)), min(d, len(a))
                while lo < hi:
                    mid = (lo + hi) // 2
                    if a[min(mid, len(a)-1)] <= b[min(max(d-1-mid, 0), len(b)-1)]:
                        lo = mid + 1
                    else:
                        hi = mid
                out.append(lo)
            return np.array(out)

        def np_merge_kv(ak, av, bk, bv):
            # stable A-priority kv merge oracle
            keys = np.concatenate([ak, bk])
            vals = np.concatenate([av, bv])
            perm = np.argsort(keys, kind="stable")
            return keys[perm], vals[perm]

        rng = np.random.default_rng(11)
        devs = jax.devices()
        M = np.iinfo(np.int32).max
        cases = [  # (P, na, nb, flavor)
            (8, 67, 61, "dup"),       # duplicates, non-divisible
            (8, 9, 130, "float"),     # skewed sizes
            (4, 37, 100, "dup"),      # ragged small prime
            (4, 24, 24, "sentinel"),  # kv keys tied with the pad sentinel
            (2, 7, 50, "sentinel"),
            (8, 33, 63, "batched"),   # batched kv rows
        ]
        for p, na, nb, flavor in cases:
            mesh = Mesh(np.array(devs[:p]), ("x",))
            if flavor == "float":
                a = np.sort(rng.standard_normal(na)).astype(np.float32)
                b = np.sort(rng.standard_normal(nb)).astype(np.float32)
                w = np.asarray(distributed_merge(jnp.array(a), jnp.array(b), mesh, exchange="window"))
                g = np.asarray(distributed_merge(jnp.array(a), jnp.array(b), mesh, exchange="gather"))
                assert np.array_equal(w, np.sort(np.concatenate([a, b]))), (p, na, nb)
                assert np.array_equal(w, g), (p, na, nb)
            elif flavor in ("dup", "sentinel"):
                ak = np.sort(rng.integers(-4, 4, na)).astype(np.int32)
                bk = np.sort(rng.integers(-4, 4, nb)).astype(np.int32)
                if flavor == "sentinel":  # real payload keys == pad sentinel
                    ak[-3:] = M; bk[-2:] = M
                av = np.arange(na, dtype=np.int32)
                bv = 10_000 + np.arange(nb, dtype=np.int32)
                args = (jnp.array(ak), jnp.array(av), jnp.array(bk), jnp.array(bv))
                kw, vw = distributed_merge_kv(*args, mesh=mesh, exchange="window")
                kg, vg = distributed_merge_kv(*args, mesh=mesh, exchange="gather")
                kr, vr = np_merge_kv(ak, av, bk, bv)
                assert np.array_equal(np.asarray(kw), kr), (p, na, nb, flavor)
                assert np.array_equal(np.asarray(vw), vr), (p, na, nb, flavor)
                assert np.array_equal(np.asarray(kw), np.asarray(kg)), (p, na, nb)
                assert np.array_equal(np.asarray(vw), np.asarray(vg)), (p, na, nb)
            else:  # batched kv
                R = 3
                ak = np.sort(rng.integers(-9, 9, (R, na)), axis=1).astype(np.int32)
                bk = np.sort(rng.integers(-9, 9, (R, nb)), axis=1).astype(np.int32)
                av = np.tile(np.arange(na, dtype=np.int32), (R, 1))
                bv = 10_000 + np.tile(np.arange(nb, dtype=np.int32), (R, 1))
                args = (jnp.array(ak), jnp.array(av), jnp.array(bk), jnp.array(bv))
                kw, vw = distributed_merge_kv_batched(*args, mesh=mesh, exchange="window")
                kg, vg = distributed_merge_kv_batched(*args, mesh=mesh, exchange="gather")
                assert np.array_equal(np.asarray(kw), np.asarray(kg)), (p, na, nb)
                assert np.array_equal(np.asarray(vw), np.asarray(vg)), (p, na, nb)
                for r in range(R):
                    kr, vr = np_merge_kv(ak[r], av[r], bk[r], bv[r])
                    assert np.array_equal(np.asarray(kw)[r], kr), (p, r)
                    assert np.array_equal(np.asarray(vw)[r], vr), (p, r)
            # max-window / max-piece bound assertion: the true cut table
            # must respect the static buffer bounds for every device
            a1 = (a if flavor == "float" else ak)
            b1 = (b if flavor == "float" else bk)
            if a1.ndim == 2:
                a_rows, b_rows = list(a1), list(b1)
            else:
                a_rows, b_rows = [a1], [b1]
            seg, W_a, W_b, w_a, w_b = window_bounds(na, nb, p)
            m_a, m_b = -(-na // p), -(-nb // p)
            for ar, br in zip(a_rows, b_rows):
                diags = np.minimum(np.arange(p + 1) * seg, na + nb)
                acut = np_cuts(ar, br, diags)
                bcut = diags - acut
                alen, blen = np.diff(acut), np.diff(bcut)
                assert (alen <= W_a).all() and (blen <= W_b).all(), (p, na, nb)
                # pieces: overlap of each sender shard with each window
                for cuts, m, w in ((acut, m_a, w_a), (bcut, m_b, w_b)):
                    for j in range(p):
                        piece = np.minimum(cuts[1:], (j + 1) * m) - np.maximum(cuts[:-1], j * m)
                        assert (piece <= w).all(), (p, na, nb, j)
        print("ok")
    """)


def test_distributed_sort_combines_and_topk_exchanges():
    """combine="tournament" (incl. the Pallas-kernel rounds of
    local_sort="pallas") matches combine="onepass"; the butterfly top-k
    combine matches the gather tree bit-for-bit; the batched top-k and the
    sampler's backend="distributed" agree with lax.top_k."""
    run_strict("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import distributed_sort, distributed_topk, distributed_topk_batched
        from repro.serving.sampler import topk_sample
        rng = np.random.default_rng(5)
        x = rng.integers(-1000, 1000, 256).astype(np.int32)
        x[:4] = np.iinfo(np.int32).max  # sentinel-valued payloads
        ref = np.sort(x)
        P = 8
        outs = {}
        for combine, local_sort in [("onepass", "core"), ("tournament", "core"),
                                    ("tournament", "pallas")]:
            s, cnt, ovf = distributed_sort(jnp.array(x), combine=combine, local_sort=local_sort)
            s, cnt = np.asarray(s), np.asarray(cnt)
            assert not np.asarray(ovf), (combine, local_sort)
            percap = s.shape[0] // P
            got = np.concatenate([s[i*percap:i*percap+cnt[i]] for i in range(P)])
            assert np.array_equal(got, ref), (combine, local_sort)
        # top-k: butterfly == gather == lax.top_k (incl. duplicate values)
        y = rng.integers(-20, 20, 256).astype(np.int32)
        vb, ib = distributed_topk(jnp.array(y), 16, exchange="butterfly")
        vg, ig = distributed_topk(jnp.array(y), 16, exchange="gather")
        rv, ri = jax.lax.top_k(jnp.array(y), 16)
        assert np.array_equal(np.asarray(vb), np.asarray(rv)) and np.array_equal(np.asarray(ib), np.asarray(ri))
        assert np.array_equal(np.asarray(vb), np.asarray(vg)) and np.array_equal(np.asarray(ib), np.asarray(ig))
        # batched top-k over a vocab-sharded batch + the sampler route
        X = rng.standard_normal((4, 64)).astype(np.float32)
        vb, ib = distributed_topk_batched(jnp.array(X), 8)
        rv, ri = jax.lax.top_k(jnp.array(X), 8)
        assert np.array_equal(np.asarray(vb), np.asarray(rv)) and np.array_equal(np.asarray(ib), np.asarray(ri))
        tok_d = topk_sample(jnp.array(X), jax.random.key(0), k=8, backend="distributed")
        tok_c = topk_sample(jnp.array(X), jax.random.key(0), k=8, backend="core")
        assert np.array_equal(np.asarray(tok_d), np.asarray(tok_c))
        print("ok")
    """)


def test_sharded_train_step_on_debug_mesh():
    """2x2 mesh: jitted train step with FSDP+TP shardings runs and matches
    the unsharded step's loss."""
    run_strict("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config, TrainConfig
        from repro.train.steps import make_train_step, init_train_state
        from repro.launch.mesh import make_debug_mesh
        from repro.launch.specs import state_shardings, batch_shardings
        from repro.parallel.sharding import make_rules, sharding_env
        from repro.configs.base import ShapeConfig

        cfg = get_config("tinyllama-1.1b").reduced()
        tcfg = TrainConfig(learning_rate=1e-3, warmup_steps=2, total_steps=10)
        mesh = make_debug_mesh(2, 2)
        rules = make_rules(mesh)
        state = init_train_state(cfg, tcfg, jax.random.key(0))
        _, st_sh = state_shardings(cfg, tcfg, mesh, rules)
        shape = ShapeConfig("t", 32, 4, "train")
        b_sh = batch_shardings(cfg, shape, "train", mesh, rules)
        toks = jax.random.randint(jax.random.key(1), (4, 32), 0, cfg.vocab_size)
        batch = {"tokens": toks, "labels": toks}
        with sharding_env(mesh, rules):
            step = jax.jit(make_train_step(cfg, tcfg), in_shardings=(st_sh, b_sh),
                           out_shardings=(st_sh, None))
            state_sh = jax.device_put(state, st_sh)
            batch_sh = jax.device_put(batch, b_sh)
            new_state, metrics = step(state_sh, batch_sh)
        sharded_loss = float(metrics["loss"])
        # unsharded reference
        step1 = jax.jit(make_train_step(cfg, tcfg))
        _, m1 = step1(state, batch)
        assert abs(sharded_loss - float(m1["loss"])) < 1e-2, (sharded_loss, float(m1["loss"]))
        print("ok", sharded_loss)
    """, n=4)


def test_ragged_hardening_distributed():
    """PR 2 regressions in one subprocess: non-divisible distributed_merge,
    ragged bucket counts with iinfo.max payloads in distributed_sort, and
    no pad-index leakage from distributed_topk under all--inf shards."""
    run_strict("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import distributed_merge, distributed_sort, distributed_topk
        rng = np.random.default_rng(7)
        # merge: |A|, |B| not divisible by P=8
        for na, nb in [(67, 61), (90, 5), (5, 130)]:
            a = np.sort(rng.standard_normal(na)).astype(np.float32)
            b = np.sort(rng.standard_normal(nb)).astype(np.float32)
            out = np.asarray(distributed_merge(jnp.array(a), jnp.array(b)))
            assert out.shape == (na + nb,)
            assert np.allclose(out, np.sort(np.concatenate([a, b]))), (na, nb)
        # sample sort: int payloads equal to the sentinel ride the ragged
        # bucket combine exactly
        M = np.iinfo(np.int32).max
        x = rng.integers(-1000, 1000, 256).astype(np.int32)
        x[:5] = M
        s, cnt, ovf = distributed_sort(jnp.array(x))
        s, cnt = np.asarray(s), np.asarray(cnt)
        assert not np.asarray(ovf)
        P = 8; percap = s.shape[0] // P
        got = np.concatenate([s[i*percap:i*percap+cnt[i]] for i in range(P)])
        assert (got == np.sort(x)).all()
        # top-k: shards full of -inf logits (keys tie with the pad
        # sentinel) must never surface a pad index
        x = np.full(512, -np.inf, np.float32)
        x[100] = 1.0; x[300] = 2.0
        v, i = distributed_topk(jnp.array(x), 16)
        v, i = np.asarray(v), np.asarray(i)
        assert (i >= 0).all(), i
        rv, ri = jax.lax.top_k(jnp.array(x), 16)
        assert np.array_equal(v, np.asarray(rv)) and (i == np.asarray(ri)).all()
        # int shards containing iinfo.min: the flip_desc combine must not
        # wrap them into spurious global maxima
        m = np.iinfo(np.int32).min
        xi = np.full(64, m, np.int32)
        xi[10] = 5; xi[40] = -3
        v, i = distributed_topk(jnp.array(xi), 4)
        rv, ri = jax.lax.top_k(jnp.array(xi), 4)
        assert (np.asarray(v) == np.asarray(rv)).all()
        assert (np.asarray(i) == np.asarray(ri)).all()
        print("ok")
    """)
