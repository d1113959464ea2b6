"""Ahead-of-time compiles of the main-path kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached: a described
``v5e:2x2`` topology compiles programs that are never run.  That catches
what interpret mode cannot — unaligned VMEM/HBM accesses, illegal block
shapes, kernels over the scoped-VMEM limit — at the widths users run.
Every compile must contain a Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never at import
time: only one process may load the TPU library, and every test worker
imports this module.  Code that asks the backend still sees the CPU here,
so the kernels are called with ``interpret=False`` (or the test-only
``REPRO_PALLAS_INTERPRET=0`` override for code that takes no argument).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import merge_path as mpk
from repro.kernels import ops
from repro.kernels.ssm_scan import ssm_scan_pallas

I32, F32 = jnp.int32, jnp.float32
# falcon-mamba-7b's scan: (B, L, d_inner, state)
SSM = (1, 2048, 8192, 16)
# TPC-H SF10: orders and lineitem rows
ORDERS, LINEITEM = 15_000_000, 59_986_052


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """``spec(shape, dtype)``: an argument placed on one described chip."""
    one = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)


@pytest.fixture
def no_cache():
    """A described chip's programs cannot be read back from the persistent
    cache: keep it off so no compile warns about an unreadable entry."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _ssm_args(spec):
    b, s, d, st = SSM
    return [spec((b, s, d), F32), spec((b, s, d), F32), spec((b, s, st), F32),
            spec((b, s, st), F32), spec((d, st), F32)]


CASES = {
    "merge_i32_2^23": lambda spec: _compile(
        lambda a, b: ops.merge(a, b, interpret=False), spec((1 << 23,), I32), spec((1 << 23,), I32)
    ),
    "merge_kv_f32_2^23": lambda spec: _compile(
        lambda a, av, b, bv: ops.merge_kv(a, av, b, bv, interpret=False),
        spec((1 << 23,), F32), spec((1 << 23,), I32), spec((1 << 23,), F32), spec((1 << 23,), I32),
    ),
    "merge_batched_64x2^16": lambda spec: _compile(
        lambda a, b: ops.merge_batched(a, b, interpret=False),
        spec((64, 1 << 16), F32), spec((64, 1 << 16), F32),
    ),
    "sort_round_kv_2^24": lambda spec: _compile(
        lambda k, v: mpk.sort_round_kv_pallas(k, v, 1 << 12, tile=512, leaf=32, interpret=False),
        spec(((1 << 24) + mpk.sort_tail(512),), F32), spec(((1 << 24) + mpk.sort_tail(512),), I32),
    ),
    # the TPC-H SF10 benchmark cells: ORDER BY l_orderkey, orders JOIN lineitem
    "sort_kv_i32_tpch_sf10": lambda spec: _compile(
        lambda k, v: ops.sort_kv(k, v, tile=1024, interpret=False),
        spec((LINEITEM,), I32), spec((LINEITEM,), I32),
    ),
    "merge_kv_i32_tpch_sf10": lambda spec: _compile(
        lambda a, av, b, bv: ops.merge_kv(a, av, b, bv, interpret=False),
        spec((ORDERS,), I32), spec((ORDERS,), I32), spec((LINEITEM,), I32), spec((LINEITEM,), I32),
    ),
    "topk_batched_64x32064": lambda spec: _compile(
        lambda x: ops.topk_batched(x, 40, interpret=False), spec((64, 32064), F32)
    ),
    "ssm_fwd_falcon_mamba": lambda spec: _compile(
        lambda *a: ssm_scan_pallas(*a, interpret=False), *_ssm_args(spec)
    ),
    "ssm_bwd_falcon_mamba": lambda spec: _compile(
        jax.grad(lambda *a: jnp.sum(ssm_scan_pallas(*a, interpret=False)[0]), argnums=(0, 1, 2, 3, 4)),
        *_ssm_args(spec),
    ),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, spec, no_cache):
    CASES[case](spec)


def test_moe_dispatch_sort_compiles_for_v5e(spec, no_cache, monkeypatch):
    """phi3.5-moe's routing sort at 4096 slots per row (4 x 2048 prompt
    tokens, top-2) takes the kernel path."""
    from repro.models.moe import _positions_merge_path_batched

    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    _compile(lambda e: _positions_merge_path_batched(e, 16, None, "pallas"), spec((4, 4096), I32))
