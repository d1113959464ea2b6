"""Benchmarks mirroring the paper's tables/figures, adapted to this
environment (single-CPU host; TPU numbers come from the dry-run roofline).

Paper artifact -> benchmark:
* Fig 4/5 (speedup vs threads)      -> bench_load_balance (Corollary 7: the
  partition gives *exactly* equal per-lane work, the paper's precondition
  for linear speedup; we measure per-lane work spread directly) and
  bench_partition_cost (the O(p log N) partition stage, Table 1 col 1).
* Table 1 (cache misses)            -> bench_segmented_vs_regular (SPM vs
  flat merge wall time on CPU, where the host cache plays the role the
  paper's L2/L3 plays).
* merging throughput                -> bench_merge_throughput (Pallas SPM
  kernel vs XLA sort oracle vs flat rank-merge).
* batched merging (§6 "building
  block for other functions")       -> bench_batched_merge (one 2-D-grid
  kernel launch for B merges vs a loop of pairwise 1-D launches, plus the
  fused pure-JAX batched pass vs vmapped pairwise).
* merge-sort                        -> bench_sort.
* framework integration (DESIGN §3) -> bench_moe_dispatch (merge-path vs
  cumsum dispatch inside the MoE layer).

Every bench takes ``smoke=True`` to shrink problem sizes so the whole
suite finishes in well under a minute (``benchmarks/run.py --smoke``,
wired to ``make bench-smoke``).
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks._timing import timeit


def _sorted_pair(n: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    a = np.sort(rng.standard_normal(n)).astype(np.float32)
    b = np.sort(rng.standard_normal(n)).astype(np.float32)
    return jnp.asarray(a), jnp.asarray(b)


def _sorted_rows(b: int, n: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    x = np.sort(rng.standard_normal((b, n)), axis=1).astype(np.float32)
    y = np.sort(rng.standard_normal((b, n)), axis=1).astype(np.float32)
    return jnp.asarray(x), jnp.asarray(y)


def bench_merge_throughput(rows: List[Dict], smoke: bool = False) -> None:
    from repro.core import merge as core_merge
    from repro.kernels.merge_path import merge_pallas
    from repro.kernels.ref import merge_ref

    sizes = (1 << 14,) if smoke else (1 << 16, 1 << 20)
    for n in sizes:
        a, b = _sorted_pair(n)
        variants = {
            "flat_rank_merge": jax.jit(core_merge),
            "xla_sort_oracle": jax.jit(merge_ref),
            "pallas_spm_tile512": jax.jit(lambda x, y: merge_pallas(x, y, tile=512)),
        }
        for name, fn in variants.items():
            us = timeit(
                fn, a, b,
                iters=3 if smoke else 5, warmup=1 if smoke else 2,
                label=f"merge_throughput/{name}/n={2*n}",
            )
            rows.append({
                "name": f"merge_throughput/{name}/n={2*n}",
                "us_per_call": us,
                "derived": f"{2*n/us:.1f} Melem/s",
            })


def bench_batched_merge(rows: List[Dict], smoke: bool = False) -> None:
    """Batched Merge Path: one 2-D (batch, tile) grid launch for the whole
    batch vs the pairwise alternatives.

    Baselines:
    * ``pairwise_pallas_loop`` — the pre-batched-API strategy: one 1-D
      kernel launch per row pair (what a vmapped consumer effectively
      paid per row).
    * ``vmapped_core_merge`` — pure-JAX pairwise merge under ``vmap``.
    * ``fused_core_batched`` — the fused single-pass Algorithm 2 batched
      merge (no kernel), the small-row dispatch target of ``kernels.ops``.

    Sizes sit in the many-small-rows regime the batched API exists for
    (MoE dispatch rounds, top-k candidate runs): there the per-launch
    overhead of the pairwise loop dominates and the single 2-D-grid
    launch wins.  (In interpret mode, very long rows instead penalize the
    batched kernel — the interpreter carries the whole batch output
    through its grid loop — which on real hardware is pipelined away.)
    """
    from repro.core import merge as core_merge
    from repro.core.batched import merge_batched as core_merge_batched
    from repro.kernels.merge_path import merge_batched_pallas, merge_pallas

    bsz, n, tile = (32, 256, 64) if smoke else (64, 512, 128)
    a, b = _sorted_rows(bsz, n, seed=7)
    iters, warmup = (3, 1) if smoke else (5, 2)

    def pairwise_loop(x, y):
        return jnp.stack([merge_pallas(x[i], y[i], tile=tile) for i in range(bsz)])

    variants = {
        "batched_pallas_2d_grid": jax.jit(lambda x, y: merge_batched_pallas(x, y, tile=tile)),
        "pairwise_pallas_loop": jax.jit(pairwise_loop),
        "fused_core_batched": jax.jit(core_merge_batched),
        "vmapped_core_merge": jax.jit(jax.vmap(core_merge)),
    }
    us_by_name = {}
    for name, fn in variants.items():
        us = timeit(
            fn, a, b, iters=iters, warmup=warmup,
            label=f"batched_merge/{name}/B={bsz}/n={2*n}",
        )
        us_by_name[name] = us
        rows.append({
            "name": f"batched_merge/{name}/B={bsz}/n={2*n}",
            "us_per_call": us,
            "derived": f"{bsz*2*n/us:.1f} Melem/s",
        })
    ratio = us_by_name["pairwise_pallas_loop"] / us_by_name["batched_pallas_2d_grid"]
    rows.append({
        "name": f"batched_merge/speedup_batched_vs_pairwise/B={bsz}/n={2*n}",
        "us_per_call": 0.0,
        "derived": f"{ratio:.2f}x (2-D grid launch vs per-pair launches)",
    })


def bench_ragged_merge(rows: List[Dict], smoke: bool = False) -> None:
    """Ragged batched Merge Path (PR 2): per-row valid lengths.

    Two claims measured:
    * ``uniform_fused_batched`` — the regular (non-ragged) fused batched
      merge at the acceptance size (64, 4096).  This path is untouched by
      the ragged API (raggedness must not tax it); its timing is the
      regression anchor recorded in BENCH_*.json.
    * ``ragged_fused_batched`` — the same batch with random per-row valid
      lengths through ``merge_batched_ragged``: the price of length
      masking + capped ranks relative to the uniform pass.
    * ``ragged_relative_cost`` — the ratio (derived).
    """
    from repro.core.batched import merge_batched as core_merge_batched
    from repro.core.batched import merge_batched_ragged

    # the acceptance size (64, 4096) is kept in smoke mode too — it is the
    # regression anchor the acceptance criteria compare against
    bsz, n = 64, 4096
    a, b = _sorted_rows(bsz, n, seed=13)
    rng = np.random.default_rng(13)
    al = jnp.asarray(rng.integers(0, n + 1, bsz), jnp.int32)
    bl = jnp.asarray(rng.integers(0, n + 1, bsz), jnp.int32)
    iters, warmup = (3, 1) if smoke else (5, 2)
    us_uniform = timeit(
        jax.jit(core_merge_batched), a, b, iters=iters, warmup=warmup,
        label=f"ragged_merge/uniform_fused_batched/B={bsz}/n={2*n}",
    )
    us_ragged = timeit(
        jax.jit(merge_batched_ragged), a, b, al, bl, iters=iters, warmup=warmup,
        label=f"ragged_merge/ragged_fused_batched/B={bsz}/n={2*n}",
    )
    rows.append({
        "name": f"ragged_merge/uniform_fused_batched/B={bsz}/n={2*n}",
        "us_per_call": us_uniform,
        "derived": f"{bsz*2*n/us_uniform:.1f} Melem/s",
    })
    rows.append({
        "name": f"ragged_merge/ragged_fused_batched/B={bsz}/n={2*n}",
        "us_per_call": us_ragged,
        "derived": f"{bsz*2*n/us_ragged:.1f} Melem/s (storage elems)",
    })
    rows.append({
        "name": f"ragged_merge/ragged_relative_cost/B={bsz}/n={2*n}",
        "us_per_call": 0.0,
        "derived": f"{us_ragged/us_uniform:.2f}x uniform-path time",
    })


def bench_partition_cost(rows: List[Dict], smoke: bool = False) -> None:
    """Partition stage cost vs p on 10M elements — the paper's O(p log N)."""
    from repro.core import diagonal_intersections

    n = 250_000 if smoke else 5_000_000
    ps = (16, 256) if smoke else (16, 256, 4096)
    a, b = _sorted_pair(n)
    for p in ps:
        diags = jnp.arange(p, dtype=jnp.int32) * (2 * n // p)
        fn = jax.jit(diagonal_intersections)
        us = timeit(
            fn, a, b, diags,
            iters=3 if smoke else 5, warmup=1 if smoke else 2,
            label=f"partition_cost/p={p}/n={2*n}",
        )
        rows.append({
            "name": f"partition_cost/p={p}/n={2*n}",
            "us_per_call": us,
            "derived": f"{us/p:.3f} us/partition-point",
        })


def bench_load_balance(rows: List[Dict], smoke: bool = False) -> None:
    """Corollary 7: per-segment work is exactly N/p for every lane —
    measured from the diagonal partition, vs the naive equal-|A|-split."""
    from repro.core import diagonal_intersections

    n = 1 << 16 if smoke else 1 << 20
    rng = np.random.default_rng(3)
    # skewed inputs: all of A greater than most of B (the paper's
    # counterexample to naive partitioning, §1)
    a = jnp.asarray(np.sort(rng.standard_normal(n) + 3.0).astype(np.float32))
    b = jnp.asarray(np.sort(rng.standard_normal(n)).astype(np.float32))
    p = 64
    seg = 2 * n // p
    diags = jnp.arange(p + 1, dtype=jnp.int32) * seg
    ai = np.asarray(diagonal_intersections(a, b, diags))
    work_mp = np.diff(ai) + np.diff(np.asarray(diags) - ai)  # per-lane outputs
    # naive: give lane i an equal slice of A and of B; its work is whatever
    # the merge of those turns out to be (bounded only by 2N/p, cf. [9])
    na_per = n // p
    naive_hi = 2 * seg  # worst-case bound
    rows.append({
        "name": f"load_balance/merge_path/p={p}",
        "us_per_call": 0.0,
        "derived": f"max/min work {work_mp.max()}/{work_mp.min()} (ratio {work_mp.max()/max(1,work_mp.min()):.3f})",
    })
    rows.append({
        "name": f"load_balance/naive_bound/p={p}",
        "us_per_call": 0.0,
        "derived": f"worst-case lane work {naive_hi} = 2x mean (Shiloach-Vishkin bound)",
    })


def bench_segmented_vs_regular(rows: List[Dict], smoke: bool = False) -> None:
    from repro.core import merge as core_merge
    from repro.core import segmented_merge

    n = 1 << 17 if smoke else 1 << 21  # full: 8 MiB per array f32, beyond host L2
    segs = (1 << 12, 1 << 13) if smoke else (1 << 14, 1 << 16)
    a, b = _sorted_pair(n, seed=5)
    iters, warmup = (3, 1) if smoke else (5, 2)
    us_flat = timeit(
        jax.jit(core_merge), a, b, iters=iters, warmup=warmup,
        label=f"segmented_merge/flat_baseline/n={2*n}",
    )
    for seg in segs:
        fn = jax.jit(lambda x, y, s=seg: segmented_merge(x, y, s))
        us = timeit(
            fn, a, b, iters=iters, warmup=warmup,
            label=f"segmented_merge/seg={seg}/n={2*n}",
        )
        rows.append({
            "name": f"segmented_merge/seg={seg}/n={2*n}",
            "us_per_call": us,
            "derived": f"{us/us_flat:.2f}x flat-merge time",
        })
    rows.append({
        "name": f"segmented_merge/flat_baseline/n={2*n}",
        "us_per_call": us_flat,
        "derived": "1.00x",
    })


def bench_sort(rows: List[Dict], smoke: bool = False) -> None:
    from repro.core import merge_sort
    from repro.kernels import ops as kops

    sizes = (1 << 12,) if smoke else (1 << 14, 1 << 17)
    for n in sizes:
        rng = np.random.default_rng(n)
        x = jnp.asarray(rng.standard_normal(n).astype(np.float32))
        iters, warmup = (3, 1) if smoke else (5, 2)
        us_mp = timeit(
            jax.jit(merge_sort), x, iters=iters, warmup=warmup,
            label=f"sort/merge_path/n={n}",
        )
        us_xla = timeit(
            jax.jit(jnp.sort), x, iters=iters, warmup=warmup,
            label=f"sort/xla_baseline/n={n}",
        )
        # kernel-backed sort: wide rounds on the flat round kernel
        # (bitonic tile engine, autotuned tile, padding hoisted)
        us_ko = timeit(
            kops.sort, x, iters=iters, warmup=warmup,
            label=f"sort/pallas_flat_rounds/n={n}",
        )
        rows.append({
            "name": f"sort/merge_path/n={n}",
            "us_per_call": us_mp,
            "derived": f"{n/us_mp:.1f} Melem/s",
        })
        rows.append({
            "name": f"sort/pallas_flat_rounds/n={n}",
            "us_per_call": us_ko,
            "derived": f"{n/us_ko:.1f} Melem/s",
        })
        rows.append({
            "name": f"sort/xla_baseline/n={n}",
            "us_per_call": us_xla,
            "derived": f"{n/us_xla:.1f} Melem/s",
        })


def bench_moe_dispatch(rows: List[Dict], smoke: bool = False) -> None:
    import dataclasses

    from repro.configs import get_config
    from repro.models import init_params
    from repro.models.moe import moe_apply

    base = get_config("phi3.5-moe-42b-a6.6b").reduced()
    base = dataclasses.replace(base, num_experts=16, experts_per_token=2)
    bsz, seq = (2, 128) if smoke else (4, 512)
    x = jax.random.normal(jax.random.key(1), (bsz, seq, base.d_model))
    for mode in ("merge_path", "cumsum"):
        cfg = dataclasses.replace(base, moe_dispatch=mode)
        params = init_params(cfg, jax.random.key(0))
        layer0 = jax.tree.map(lambda t: t[0], params["layers"])
        fn = jax.jit(lambda p, xx, c=cfg: moe_apply(p, xx, c))
        us = timeit(
            fn, layer0["moe"], x,
            iters=3 if smoke else 5, warmup=1 if smoke else 2,
            label=f"moe_dispatch/{mode}/tokens={bsz*seq}",
        )
        rows.append({
            "name": f"moe_dispatch/{mode}/tokens={bsz*seq}",
            "us_per_call": us,
            "derived": f"{us/(bsz*seq):.3f} us/token",
        })
