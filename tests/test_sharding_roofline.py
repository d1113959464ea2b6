"""Unit tests for sharding rules and the roofline HLO parser (no mesh,
no heavy compiles)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import TrainConfig, get_config
from repro.launch import roofline as rl
from repro.parallel.sharding import (
    MeshRules,
    make_rules,
    param_pspec_tree,
    sanitize_spec,
)


def _fake_mesh(shape=(2, 2), axes=("data", "model")):
    devs = np.array(jax.devices() * (int(np.prod(shape)) // len(jax.devices()) + 1))
    return Mesh(devs[: int(np.prod(shape))].reshape(shape), axes)


# --- param rules -------------------------------------------------------------

def test_param_pspec_rules_cover_all_leaves():
    rules = MeshRules()
    for arch in ("tinyllama-1.1b", "moonshot-v1-16b-a3b", "falcon-mamba-7b",
                 "whisper-large-v3", "paligemma-3b", "hymba-1.5b"):
        cfg = get_config(arch).reduced()
        from repro.models import abstract_params

        params = abstract_params(cfg)
        specs = param_pspec_tree(params, rules)
        leaves_p = jax.tree.leaves(params)
        leaves_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        assert len(leaves_p) == len(leaves_s)
        # matrices (ndim >= 2, non-norm) should be sharded on some dim
        flat = jax.tree_util.tree_flatten_with_path(params)[0]
        spec_flat = jax.tree_util.tree_flatten_with_path(specs)[0]
        n_sharded = sum(
            1 for (_, spec) in spec_flat
            if isinstance(spec, P) and any(e is not None for e in tuple(spec))
        )
        assert n_sharded > 0


def test_moe_expert_leading_dim_tensor_sharded():
    cfg = get_config("moonshot-v1-16b-a3b").reduced()
    from repro.models import abstract_params

    params = abstract_params(cfg)
    specs = param_pspec_tree(params, MeshRules())
    wi_spec = specs["layers"]["moe"]["wi"]
    # stacked layer dim, then (E, d, ff): experts on model, d on fsdp
    assert tuple(wi_spec) == (None, "model", "data", None)


def test_make_rules_multipod_and_fsdp_over_pod():
    mesh = _fake_mesh((1, 2, 2), ("pod", "data", "model"))
    r1 = make_rules(mesh)
    assert r1.batch == ("pod", "data") and r1.fsdp == ("data",)
    r2 = make_rules(mesh, fsdp_over_pod=True)
    assert r2.fsdp == ("pod", "data")
    r3 = make_rules(mesh, context_parallel=True)
    assert r3.context == ("model",)


def test_sanitize_spec_drops_indivisible():
    mesh = _fake_mesh((1, 4), ("data", "model"))
    spec = P("model", "data")
    out = sanitize_spec(spec, (32001, 1600), mesh)
    assert tuple(out) == (None, "data")  # 32001 % 4 != 0 -> replicated
    out2 = sanitize_spec(spec, (32000, 1600), mesh)
    assert tuple(out2) == ("model", "data")
    # tuple axes
    out3 = sanitize_spec(P(("data", "model")), (6,), mesh)
    assert tuple(out3) == (None,)


# --- roofline parser ---------------------------------------------------------

FAKE_HLO = """
  %ag = bf16[16,4096,2048]{2,1,0} all-gather(%x), channel_id=1, dimensions={2}
  %ar = f32[1024,512]{1,0} all-reduce(%y), to_apply=%add.1
  %arp = f32[1024,512]{1,0} all-reduce(%y2), to_apply=%add.2.clone_promoted
  %rs = (f32[64,64]{1,0}, f32[64,64]{1,0}) reduce-scatter(%a, %b), dimensions={0}
  %cp = bf16[8]{0} collective-permute(%z), source_target_pairs={{0,1}}
  %a2a = s32[256,4]{1,0} all-to-all(%w), dimensions={0}
  %ars = f32[2,2]{1,0} all-reduce-start(%q), to_apply=%add.3
  %ard = f32[2,2]{1,0} all-reduce-done(%ars)
"""


def test_collective_parser_kinds_and_bytes():
    c = rl.collective_bytes(FAKE_HLO)
    # all-gather: 16*4096*2048*2 bytes, multiplier 1
    assert c["all-gather"] == 16 * 4096 * 2048 * 2
    # plain f32 all-reduce: 1024*512*4 * 2 (ring multiplier)
    # promoted one: same bytes but halved (bf16 wire) then x2 ring
    plain = 1024 * 512 * 4 * 2
    promoted = plain / 2
    start = 2 * 2 * 4 * 2
    assert c["all-reduce"] == plain + promoted + start
    assert c["reduce-scatter"] == 2 * 64 * 64 * 4
    assert c["collective-permute"] == 8 * 2
    assert c["all-to-all"] == 256 * 4 * 4
    assert c["counts"]["all-reduce"] == 3  # start counted once, done skipped


def test_roofline_terms_and_bottleneck():
    r = rl.Roofline(
        flops_per_device=197e12,  # exactly 1 s of compute
        bytes_per_device=819e9 / 2,  # 0.5 s of memory
        wire_bytes_per_device=200e9 * 2,  # 2 s of collective
        collective_detail={},
        chips=256,
        model_flops=197e12 * 256 * 0.5,
    )
    assert r.bottleneck == "collective"
    assert abs(r.t_compute - 1.0) < 1e-9
    assert abs(r.t_step - 2.0) < 1e-9
    assert abs(r.mfu_bound - 0.25) < 1e-9
    assert abs(r.useful_flops_fraction - 0.5) < 1e-9


def test_model_flops_modes():
    from repro.configs import SHAPES_BY_NAME

    cfg = get_config("tinyllama-1.1b")
    n = cfg.n_params()
    t = rl.model_flops(cfg, SHAPES_BY_NAME["train_4k"])
    assert abs(t - 6 * n * 256 * 4096) / t < 1e-9
    d = rl.model_flops(cfg, SHAPES_BY_NAME["decode_32k"])
    assert abs(d - 2 * n * 128) / d < 1e-9
    # MoE uses active params
    moe = get_config("moonshot-v1-16b-a3b")
    assert moe.n_active_params() < moe.n_params()
    tm = rl.model_flops(moe, SHAPES_BY_NAME["train_4k"])
    assert abs(tm - 6 * moe.n_active_params() * 256 * 4096) / tm < 1e-9


def test_n_params_sane():
    # analytic param counts in the right ballpark for known models
    assert 1.0e9 < get_config("tinyllama-1.1b").n_params() < 1.2e9
    assert 5.5e9 < get_config("yi-6b").n_params() < 6.5e9
    assert 300e9 < get_config("nemotron-4-340b").n_params() < 380e9
    assert 6.5e9 < get_config("falcon-mamba-7b").n_params() < 8.5e9
    # Moonlight-16B-A3B as published: 15.96e9 parameters, 2.91e9 active
    m = get_config("moonshot-v1-16b-a3b")
    assert abs(m.n_params() - 15.96e9) / 15.96e9 < 0.01
    assert abs(m.n_active_params() - 2.91e9) / 2.91e9 < 0.01
    p = get_config("phi3.5-moe-42b-a6.6b")
    assert 39e9 < p.n_params() < 45e9
    assert 5.5e9 < p.n_active_params() < 8e9
