"""Context-parallel decode attention: shard_map combine == dense reference."""

from subproc import run_with_devices


def test_context_parallel_matches_dense():
    run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp, math, functools
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.core.distributed import shard_map  # jax-version compat wrapper
        from repro.serving.context_parallel import context_parallel_decode_attention

        B, S, K, G, hd = 2, 64, 2, 3, 16
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.standard_normal((B, K, G, hd)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, S, K, hd)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, S, K, hd)), jnp.float32)
        valid = jnp.asarray(np.arange(S)[None, :] <= 40).repeat(B, 0)

        mesh = Mesh(np.array(jax.devices()).reshape(8), ("ctx",))
        fn = shard_map(
            functools.partial(context_parallel_decode_attention, axis_name="ctx"),
            mesh=mesh,
            in_specs=(P(), P(None, "ctx"), P(None, "ctx"), P(None, "ctx")),
            out_specs=P(),
            check_vma=False,
        )
        out = fn(q, k, v, valid)

        # dense reference
        s = jnp.einsum("bkgh,bskh->bkgs", q, k) / math.sqrt(hd)
        s = jnp.where(valid[:, None, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        ref = jnp.einsum("bkgs,bskh->bkgh", p, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)
        print("ok")
    """)
