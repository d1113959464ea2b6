import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and extract memory / cost / roofline terms.

The two lines above MUST stay first: jax locks the device count at first
initialization, and the production meshes need 512 placeholder devices.
Smoke tests and benchmarks do NOT import this module.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out experiments/dryrun]
"""

import argparse
import json
import math
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import (
    SHAPES,
    SHAPES_BY_NAME,
    TrainConfig,
    get_config,
    list_archs,
    shape_applicable,
)
from repro.launch import roofline as rl
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import (
    batch_shardings,
    batch_specs,
    decode_shardings,
    decode_specs,
    params_shardings,
    state_shardings,
)
from repro.parallel.sharding import make_rules, sharding_env
from repro.train.steps import make_decode_step, make_prefill_step, make_train_step


def _depth_variant(cfg, num_layers: int):
    """Same architecture with ``num_layers`` scanned layers, leading dense
    layers kept (used for cost extrapolation)."""
    import dataclasses

    kw = {"num_layers": num_layers + cfg.first_k_dense}
    if cfg.encoder_layers:
        kw["encoder_layers"] = num_layers
    return dataclasses.replace(cfg, **kw)


def _compile_one(cfg, shape, multi_pod: bool, tcfg: TrainConfig):
    """Lower + compile one concrete config; returns (compiled, t_lower, t_compile)."""
    import dataclasses

    mesh = make_production_mesh(multi_pod=multi_pod)
    context_parallel = shape.mode == "decode"
    rules = make_rules(mesh, fsdp_over_pod=cfg.fsdp_over_pod, context_parallel=context_parallel)
    # batch-1 (long-context) cells cannot shard the batch axis — replicate it
    batch_degree = math.prod(dict(zip(mesh.axis_names, mesh.devices.shape))[a] for a in rules.batch)
    if shape.global_batch % batch_degree != 0:
        rules = dataclasses.replace(rules, batch=())

    t0 = time.time()
    with sharding_env(mesh, rules):
        if shape.mode == "train":
            state, st_sh = state_shardings(cfg, tcfg, mesh, rules)
            batch = batch_specs(cfg, shape, "train")
            b_sh = batch_shardings(cfg, shape, "train", mesh, rules)
            step = make_train_step(cfg, tcfg)
            jitted = jax.jit(step, in_shardings=(st_sh, b_sh), out_shardings=(st_sh, None),
                             donate_argnums=(0,))
            lowered = jitted.lower(state, batch)
        elif shape.mode == "prefill":
            params, p_sh = params_shardings(cfg, mesh, rules)
            batch = batch_specs(cfg, shape, "prefill")
            b_sh = batch_shardings(cfg, shape, "prefill", mesh, rules)
            step = make_prefill_step(cfg)
            jitted = jax.jit(step, in_shardings=(p_sh, b_sh))
            lowered = jitted.lower(params, batch)
        else:  # decode
            params, p_sh = params_shardings(cfg, mesh, rules)
            dspec = decode_specs(cfg, shape)
            d_sh = decode_shardings(cfg, shape, mesh, rules, dspec["caches"])
            step = make_decode_step(cfg)
            args = (params, dspec["caches"], dspec["token"], dspec["pos"])
            shardings = (p_sh, d_sh["caches"], d_sh["token"], d_sh["pos"])
            if cfg.is_encoder_decoder:
                args = args + (dspec["enc_kv"],)
                shardings = shardings + (d_sh["enc_kv"],)
            jitted = jax.jit(step, in_shardings=shardings,
                             out_shardings=(None, d_sh["caches"]), donate_argnums=(1,))
            lowered = jitted.lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    return compiled, t_lower, t_compile


def _measure(compiled):
    """(flops, bytes, wire_bytes, collective_detail) of one compiled module.

    NOTE: XLA cost_analysis counts while-loop (scan) bodies ONCE, not
    times the trip count — which is exactly why lower_cell compiles two
    reduced-depth variants and extrapolates linearly in depth.
    """
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    hlo = compiled.as_text()
    colls = rl.collective_bytes(hlo)
    wire = sum(v for k, v in colls.items() if k in rl._COLL_KINDS)
    return (
        float(cost.get("flops", 0.0)),
        float(cost.get("bytes accessed", 0.0)),
        wire,
        colls,
    )


def lower_cell(arch: str, shape_name: str, multi_pod: bool, tcfg: Optional[TrainConfig] = None,
               cfg_override=None):
    """Compile one cell at full depth (the dry-run proof + memory analysis)
    and at depths 2g/4g for linear-in-depth cost extrapolation."""
    cfg = cfg_override or get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "status": "skipped", "reason": why}, None
    tcfg = tcfg or TrainConfig()
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = math.prod(mesh.devices.shape)

    # --- full-depth compile: the actual dry-run artifact ---
    compiled, t_lower, t_compile = _compile_one(cfg, shape, multi_pod, tcfg)
    try:
        mem = compiled.memory_analysis()
        mem_rec = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        }
    except Exception as e:  # backend-dependent
        mem_rec = {"error": str(e)}
    f_full, b_full, w_full, colls_full = _measure(compiled)

    # --- depth extrapolation: measure cost-exact (fully unrolled) variants
    # at 1 and 2 layer-groups, extrapolate linearly in group count ---
    g = cfg.layer_group
    l1, l2 = g, 2 * g
    from repro.utils.costmode import set_cost_exact

    try:
        set_cost_exact(True)  # fully unroll scans in the shallow variants
        c1, *_ = _compile_one(_depth_variant(cfg, l1), shape, multi_pod, tcfg)
        f1, b1, w1, colls1 = _measure(c1)
        del c1
        c2, *_ = _compile_one(_depth_variant(cfg, l2), shape, multi_pod, tcfg)
        f2, b2, w2, colls2 = _measure(c2)
        del c2
    finally:
        set_cost_exact(False)
    scale = (cfg.stack_layers - l1) / (l2 - l1)
    flops = f1 + (f2 - f1) * scale
    bbytes = b1 + (b2 - b1) * scale
    wire = w1 + (w2 - w1) * scale
    colls_ext = {}
    for k in rl._COLL_KINDS:
        colls_ext[k] = colls1[k] + (colls2[k] - colls1[k]) * scale
    colls_ext["counts_full_module"] = colls_full["counts"]
    extrapolated = True

    mflops = rl.model_flops(cfg, shape)
    roof = rl.Roofline(
        flops_per_device=flops,
        bytes_per_device=bbytes,
        wire_bytes_per_device=wire,
        collective_detail=colls_ext,
        chips=chips,
        model_flops=mflops,
    )

    record = {
        "arch": arch,
        "shape": shape_name,
        "mode": shape.mode,
        "multi_pod": multi_pod,
        "chips": chips,
        "status": "ok",
        "mesh": list(mesh.devices.shape),
        "n_params": cfg.n_params(),
        "n_active_params": cfg.n_active_params(),
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "depth_extrapolated": extrapolated,
        "memory_analysis": mem_rec,
        "cost_analysis_module_raw": {"flops": f_full, "bytes accessed": b_full,
                                     "wire_bytes": w_full},
        "roofline": roof.to_dict(),
    }
    return record, compiled


def cell_filename(arch: str, shape_name: str, multi_pod: bool) -> str:
    pod = "2pod" if multi_pod else "1pod"
    return f"{arch.replace('/', '_')}__{shape_name}__{pod}.json"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    archs = list_archs() if (args.all or not args.arch) else [args.arch]
    shapes = [s.name for s in SHAPES] if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    failures = 0
    for arch in archs:
        for shape_name in shapes:
            for mp in meshes:
                fname = os.path.join(args.out, cell_filename(arch, shape_name, mp))
                if args.skip_existing and os.path.exists(fname):
                    print(f"[skip existing] {fname}")
                    continue
                print(f"=== {arch} x {shape_name} ({'2pod' if mp else '1pod'}) ===", flush=True)
                try:
                    record, compiled = lower_cell(arch, shape_name, mp)
                except Exception as e:
                    record = {"arch": arch, "shape": shape_name, "multi_pod": mp,
                              "status": "error", "error": f"{type(e).__name__}: {e}",
                              "traceback": traceback.format_exc()[-4000:]}
                    failures += 1
                with open(fname, "w") as f:
                    json.dump(record, f, indent=1)
                if record["status"] == "ok":
                    r = record["roofline"]
                    print(f"  compile {record['compile_s']}s | "
                          f"flops/dev {r['flops_per_device']:.3e} | "
                          f"bytes/dev {r['bytes_per_device']:.3e} | "
                          f"wire/dev {r['wire_bytes_per_device']:.3e} | "
                          f"bottleneck {r['bottleneck']} | t_step {r['t_step_s']*1e3:.2f} ms",
                          flush=True)
                elif record["status"] == "skipped":
                    print(f"  SKIPPED: {record['reason']}")
                else:
                    print(f"  ERROR: {record['error']}")
                compiled = None  # release
    print(f"done; {failures} failures")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
