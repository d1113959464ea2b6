#!/usr/bin/env python
"""Bring-up check on a TPU: the merge-path kernels and phi3.5-moe serving.

    python chip_smoke.py [--seed N]      # one chip: kernels, MoE prefill, serving
    python chip_smoke.py --chips 4       # the distributed layer on a 4-chip mesh

Every input and weight is generated from ``--seed``.  Each phase checks
its result against an oracle — exact for merges, sorts and top-k,
``allclose`` for the SSM scan and the model's logits — and any failure,
guard fallback or ``FallbackWarning`` ends the run with a non-zero exit.
The script runs in one process and refuses to run without a TPU (there is
no CPU fallback) or while ``REPRO_PALLAS_INTERPRET`` forces the Pallas
interpreter.  Lines before the last report each phase and its set-up
seconds (compilation included); the last line is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.abspath(__file__))
# sizes users run (a CPU rehearsal may shrink them, never the chip run)
SIZES = {
    "merge": 1 << 23,  # keys per side of the 1-D merges
    "batched": (64, 1 << 16),  # rows, keys per side per row
    "sort": 1 << 24,
    "topk": (64, 32064, 40),  # rows, phi3.5-moe vocab, k
    "ssm": (1, 2048, 8192, 16),  # falcon-mamba-7b: (B, L, d_inner, state)
    "prefill": (4, 2048),  # rows x prompt tokens: 4096 routing slots per row
    "dist": 1 << 24,
}
# a phase that has not finished by then is hung (the whole run must end
# within 1200 s, compilation included)
PHASE_LIMIT_S = 300
# phi3.5-moe at published widths, depth cut 32 -> 2 layers
MODEL = {"arch": "phi3.5-moe", "layers": 2, "widths": "published"}


def _fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def _setup() -> None:
    """Refuse to start outside a checkout or under a forced interpreter."""
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        _fail(f"no repro package under {ROOT}/src: run from a checkout of the repository")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    env = os.environ.get("REPRO_PALLAS_INTERPRET", "").strip().lower()
    if env and env not in ("0", "false", "no", "off"):
        _fail(f"REPRO_PALLAS_INTERPRET={env!r} forces the Pallas interpreter; unset it")


# ---------------------------------------------------------------------------
# checks
# ---------------------------------------------------------------------------


def _bits(x):
    import jax
    import jax.numpy as jnp

    x = jnp.asarray(x)
    if jnp.issubdtype(x.dtype, jnp.floating):
        return jax.lax.bitcast_convert_type(x, jnp.int32)
    return x


def _exact(name, got, want) -> None:
    import jax.numpy as jnp

    if got.shape != want.shape or got.dtype != want.dtype:
        _fail(f"{name}: got {got.dtype}{got.shape}, oracle {want.dtype}{want.shape}")
    bad = int(jnp.sum(_bits(got) != _bits(want)))
    if bad:
        _fail(f"{name}: {bad} of {got.size} elements differ from the oracle")


def _kernel_in_program(name, fn, *args) -> None:
    """The program ``jit(fn)`` hands the TPU compiler for ``args`` must hold
    a Mosaic kernel.  (The lowered text is read, not a second compile: the
    eager call before it already compiled and ran the same kernel, and
    ``tests/test_tpu_compile.py`` checks the compiled text.)"""
    import jax

    if "tpu_custom_call" not in jax.jit(fn).lower(*args).as_text():
        _fail(f"{name}: no tpu_custom_call in the program")


def _phase(name, fn) -> None:
    """Run one phase under a watchdog: a phase that hangs the device dumps
    its stack and ends the process (exit 1) instead of running on."""
    import faulthandler

    from repro.telemetry import wall_seconds

    t0 = wall_seconds()
    faulthandler.dump_traceback_later(PHASE_LIMIT_S, exit=True)
    try:
        detail = fn()
    finally:
        faulthandler.cancel_dump_traceback_later()
    print(f"phase {name}: passed ({detail}); set-up s incl. compile {wall_seconds() - t0:.1f}",
          flush=True)


def _sorted_keys(key, shape, dtype):
    import jax
    import jax.numpy as jnp

    if jnp.issubdtype(dtype, jnp.floating):
        x = jax.random.normal(key, shape, dtype)
    else:  # a narrow range, so runs share many duplicate keys
        x = jax.random.randint(key, shape, -(1 << 20), 1 << 20, dtype)
    return jnp.sort(x, axis=-1)


# ---------------------------------------------------------------------------
# one chip: kernels
# ---------------------------------------------------------------------------


def _merge_phase(key, dtype):
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops

    def run():
        ka, kb = jax.random.split(key)
        n = SIZES["merge"]
        a = _sorted_keys(ka, (n,), dtype)
        b = _sorted_keys(kb, (n,), dtype)
        cat = jnp.concatenate([a, b])
        perm = jnp.argsort(cat, stable=True)
        _exact("merge", ops.merge(a, b), cat[perm])
        av = jnp.arange(n, dtype=jnp.int32)
        bv = av + n
        k, v = ops.merge_kv(a, av, b, bv)
        _exact("merge_kv keys", k, cat[perm])
        _exact("merge_kv values", v, perm.astype(jnp.int32))
        _kernel_in_program("merge", ops.merge, a, b)
        _kernel_in_program("merge_kv", ops.merge_kv, a, av, b, bv)
        return f"2x{n} {jnp.dtype(dtype).name} keys == stable argsort"

    return run


def _merge_batched_phase(key):
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops

    def run():
        ka, kb = jax.random.split(key)
        shape = SIZES["batched"]
        a = _sorted_keys(ka, shape, jnp.float32)
        b = _sorted_keys(kb, shape, jnp.float32)
        _exact("merge_batched", ops.merge_batched(a, b), jnp.sort(jnp.concatenate([a, b], 1), 1))
        _kernel_in_program("merge_batched", ops.merge_batched, a, b)
        return f"{shape} + {shape} f32 == jnp.sort"

    return run


def _sort_phase(key):
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops

    def run():
        n = SIZES["sort"]
        keys = jax.random.normal(key, (n,), jnp.float32)
        vals = jnp.arange(n, dtype=jnp.int32)
        perm = jnp.argsort(keys, stable=True)
        k, v = ops.sort_kv(keys, vals)
        _exact("sort_kv keys", k, keys[perm])
        _exact("sort_kv values", v, perm.astype(jnp.int32))
        _kernel_in_program("sort_kv", ops.sort_kv, keys, vals)
        return f"{n} f32 keys + int32 payload == stable argsort"

    return run


def _topk_phase(key):
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops

    def run():
        rows, vocab, k = SIZES["topk"]
        x = jax.random.normal(key, (rows, vocab), jnp.float32)
        vals, idx = ops.topk_batched(x, k)
        rv, ri = jax.lax.top_k(x, k)
        _exact("topk values", vals, rv)
        _exact("topk indices", idx.astype(jnp.int32), ri.astype(jnp.int32))
        _kernel_in_program("topk_batched", lambda y: ops.topk_batched(y, k), x)
        return f"({rows}, {vocab}) k={k} == lax.top_k"

    return run


def _ssm_phase(key):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.ssm_scan import ssm_scan_pallas, ssm_scan_ref

    def run():
        b, s, d, st = SIZES["ssm"]
        k = jax.random.split(key, 5)
        dt = jax.nn.softplus(jax.random.normal(k[0], (b, s, d)) - 4.0)
        x = jax.random.normal(k[1], (b, s, d))
        bm = jax.random.normal(k[2], (b, s, st))
        cm = jax.random.normal(k[3], (b, s, st))
        a = -jnp.exp(jax.random.normal(k[4], (d, st)))
        y, h = ssm_scan_pallas(dt, x, bm, cm, a)
        yr, hr = jax.jit(ssm_scan_ref)(dt, x, bm, cm, a)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(np.asarray(h), np.asarray(hr), rtol=1e-3, atol=1e-3)
        _kernel_in_program("ssm_scan", ssm_scan_pallas, dt, x, bm, cm, a)
        return f"(B, L, D, N) = {(b, s, d, st)} f32 allclose ssm_scan_ref"

    return run


def _health_phase():
    from repro.runtime import resilience

    def run():
        hs = resilience.health_summary()
        totals = hs.pop("totals")
        if totals["fallbacks"] or totals["exhausted"] or totals["launch_failures"]:
            _fail(f"guarded dispatch fell back: {totals}")
        for op, rec in hs.items():
            want = "pallas-scan" if op == "ssm_scan_pallas" else "pallas-hier"
            if set(rec["served_by"]) != {want}:
                _fail(f"{op} served by {rec['served_by']}, expected only {want}")
        return f"0 fallbacks; served_by {({op: rec['served_by'] for op, rec in hs.items()})}"

    return run


# ---------------------------------------------------------------------------
# one chip: phi3.5-moe at published widths
# ---------------------------------------------------------------------------


def _moe_config(layers: int, dispatch: str):
    import dataclasses

    from repro.configs import get_config

    cfg = get_config(MODEL["arch"])
    if MODEL["widths"] == "reduced":
        cfg = cfg.reduced()
    return dataclasses.replace(cfg, num_layers=layers, moe_dispatch=dispatch)


def _moe_phase(key, seed):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import forward_prefill, init_params
    from repro.models.layers import rms_norm
    from repro.models.moe import _positions_merge_path_batched

    def run():
        cfg = _moe_config(MODEL["layers"], "merge_path_pallas")
        core_cfg = _moe_config(MODEL["layers"], "merge_path")
        params = jax.jit(init_params, static_argnums=0)(cfg, jax.random.key(seed))
        n_params = sum(int(p.size) for p in jax.tree.leaves(params))
        tokens = jax.random.randint(key, SIZES["prefill"], 1, cfg.vocab_size, jnp.int32)
        batch = {"tokens": tokens}
        prefill = jax.jit(lambda p, b: forward_prefill(cfg, p, b)[0])
        _kernel_in_program("moe prefill", prefill, params, batch)
        logits = prefill(params, batch)
        ref = jax.jit(lambda p, b: forward_prefill(core_cfg, p, b)[0])(params, batch)
        if logits.shape != (tokens.shape[0], cfg.vocab_size) or not bool(jnp.all(jnp.isfinite(logits))):
            _fail(f"moe prefill: logits {logits.shape} not finite")
        np.testing.assert_allclose(
            np.asarray(logits, np.float32), np.asarray(ref, np.float32), rtol=2e-2, atol=2e-2
        )

        # routing of layer 0's router over the embedded prompt: the kernel
        # route must give every slot the same position-in-expert
        @jax.jit
        def routing(p, toks):
            lp = jax.tree.map(lambda t: t[0], p["layers"])
            x = p["embed"]["table"][toks]
            h = rms_norm(x, lp["ffn_norm"], cfg.rms_eps)
            logit = h.astype(jnp.float32) @ lp["moe"]["router"]
            _, top_e = jax.lax.top_k(logit, cfg.experts_per_token)
            flat = top_e.reshape(toks.shape[0], -1).astype(jnp.int32)  # (rows, 2 * tokens) slots
            return (
                _positions_merge_path_batched(flat, cfg.num_experts, None, "pallas"),
                _positions_merge_path_batched(flat, cfg.num_experts, None, "core"),
            )

        pos_k, pos_c = routing(params, tokens)
        _exact("moe routing positions", pos_k, pos_c)
        return (f"{cfg.name} {cfg.num_layers} layers d_model {cfg.d_model}, "
                f"{n_params / 1e9:.2f}e9 params, prompt {tokens.shape}; "
                f"routing positions equal, logits allclose merge_path")

    return run


def _serve_phase(seed):
    from repro.launch import serve

    def run():
        report = serve.main([
            "--arch", MODEL["arch"], "--widths", MODEL["widths"], "--layers", str(MODEL["layers"]),
            "--requests", "4", "--batch", "4",
            "--prompt-len", "128", "--max-new", "16", "--max-seq", "160",
            "--seed", str(seed),
        ])
        if not report.ok() or report.retries or report.completed != 4:
            _fail(f"serving: {report}")
        return f"4 requests completed, report.ok() with {report.ticks} ticks, 0 retries"

    return run


# ---------------------------------------------------------------------------
# four chips: the distributed layer
# ---------------------------------------------------------------------------


def _check_sharded(name, out, mesh) -> None:
    devs = {s.device for s in out.addressable_shards}
    sizes = {s.data.shape for s in out.addressable_shards}
    if devs != set(mesh.devices.flat) or len(sizes) != 1:
        _fail(f"{name}: shards on {sorted(d.id for d in devs)} with shapes {sizes}")


def _distributed_phases(key):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core.distributed import distributed_merge, distributed_sort, distributed_topk

    mesh = Mesh(np.asarray(jax.devices()[:4]), ("x",))
    shard = NamedSharding(mesh, P("x"))
    one = jax.devices()[0]
    ka, kb, kx = jax.random.split(key, 3)

    def merge():
        n = SIZES["dist"]
        a = _sorted_keys(ka, (n // 2,), jnp.int32)
        b = _sorted_keys(kb, (n // 2,), jnp.int32)
        ref = jnp.sort(jax.device_put(jnp.concatenate([a, b]), one))
        a, b = jax.device_put(a, shard), jax.device_put(b, shard)
        win = distributed_merge(a, b, mesh=mesh, exchange="window")
        gat = distributed_merge(a, b, mesh=mesh, exchange="gather")
        _check_sharded("distributed_merge", win, mesh)
        _exact("distributed_merge window vs gather", win, gat)
        _exact("distributed_merge vs one device", jax.device_put(win, one), ref)
        return f"2x{n // 2} int32, window == gather == jnp.sort, 4 shards"

    def sort():
        n = SIZES["dist"]
        x = jax.device_put(jax.random.normal(kx, (n,), jnp.float32), shard)
        out, counts, overflowed = distributed_sort(x, mesh=mesh, local_sort="pallas")
        _check_sharded("distributed_sort", out, mesh)
        if bool(overflowed) or int(counts.sum()) != n:
            _fail(f"distributed_sort: counts {counts}, overflowed {overflowed}")
        cap = out.shape[0] // 4
        parts = [np.asarray(s.data)[: int(c)] for s, c in
                 zip(sorted(out.addressable_shards, key=lambda s: s.index[0].start or 0),
                     np.asarray(counts))]
        ref = np.asarray(jnp.sort(jax.device_put(x, one)))
        _exact("distributed_sort vs one device", jnp.asarray(np.concatenate(parts)), jnp.asarray(ref))
        return f"{n} f32, local_sort=pallas, bucket counts {np.asarray(counts).tolist()} (cap {cap})"

    def topk():
        n = SIZES["dist"]
        x = jax.device_put(jax.random.normal(kx, (n,), jnp.float32), shard)
        vals, idx = distributed_topk(x, 40, mesh=mesh)
        rv, ri = jax.lax.top_k(jax.device_put(x, one), 40)
        _exact("distributed_topk values", jax.device_put(vals, one), rv)
        _exact("distributed_topk indices", jax.device_put(idx, one).astype(jnp.int32), ri.astype(jnp.int32))
        return f"{n} f32, k=40 == lax.top_k on one device"

    return [("distributed_merge", merge), ("distributed_sort", sort), ("distributed_topk", topk)]


# ---------------------------------------------------------------------------


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = run only the distributed layer over a 4-chip mesh")
    args = ap.parse_args(argv)
    _setup()

    import warnings

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        _fail(f"no TPU found: JAX's devices are {devices[0].platform} ({len(devices)})")
    if len(devices) < args.chips:
        _fail(f"--chips {args.chips} needs {args.chips} TPU devices, found {len(devices)}")

    from repro.kernels.merge_path import default_interpret
    from repro.runtime.resilience import FallbackWarning
    from repro.utils.compile_cache import enable_compile_cache

    if default_interpret():
        _fail("Pallas would run interpreted on this TPU")
    warnings.simplefilter("error", FallbackWarning)
    cache = enable_compile_cache()
    print(f"device {devices[0].device_kind} x{len(devices)}; compile cache {cache}", flush=True)

    keys = jax.random.split(jax.random.key(args.seed), 8)
    if args.chips == 4:
        phases = _distributed_phases(keys[0])
    else:
        phases = [
            ("merge int32", _merge_phase(keys[0], "int32")),
            ("merge f32", _merge_phase(keys[1], "float32")),
            ("merge_batched", _merge_batched_phase(keys[2])),
            ("sort_kv", _sort_phase(keys[3])),
            ("topk_batched", _topk_phase(keys[4])),
            ("ssm_scan", _ssm_phase(keys[5])),
            ("guard health", _health_phase()),
            ("phi3.5-moe prefill", _moe_phase(keys[6], args.seed)),
            ("phi3.5-moe serving", _serve_phase(args.seed)),
        ]
    for name, fn in phases:
        _phase(name, fn)
    stats = devices[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"peak_bytes_in_use {stats['peak_bytes_in_use']}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices),
    }}), flush=True)


if __name__ == "__main__":
    main()
