"""Fault tolerance, checkpointing, data pipeline, optimizer unit tests."""

import os
import shutil

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.checkpoint.manager import CheckpointManager
from repro.configs import TrainConfig, get_config
from repro.data.pipeline import PipelineConfig, SyntheticLMPipeline
from repro.optim.adamw import adamw_update, cosine_schedule, init_opt_state
from repro.parallel import compression
from repro.runtime.fault_tolerance import (
    HeartbeatMonitor,
    StragglerMonitor,
    TrainLoopSupervisor,
    plan_elastic_mesh,
)
from repro.train.steps import init_train_state, make_train_step


# --- checkpoint ------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    state = {"step": jnp.int32(7), "params": {"w": jnp.arange(6.0).reshape(2, 3)},
             "opt": {"m": {"w": jnp.ones((2, 3))}}}
    mgr.save(7, state, blocking=True)
    like = jax.eval_shape(lambda: state)
    restored = mgr.restore(like)
    assert int(restored["step"]) == 7
    np.testing.assert_array_equal(np.asarray(restored["params"]["w"]),
                                  np.arange(6.0).reshape(2, 3))


def test_checkpoint_gc_and_latest(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    state = {"x": jnp.zeros(3)}
    for s in (1, 2, 3, 4):
        mgr.save(s, state, blocking=True)
    assert mgr.all_steps() == [3, 4]
    assert mgr.latest_step() == 4


def test_checkpoint_async(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3)
    state = {"x": jnp.arange(10.0)}
    mgr.save(5, state, blocking=False)
    mgr.wait()
    assert mgr.latest_step() == 5


def test_checkpoint_atomicity_partial_write_ignored(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3)
    state = {"x": jnp.arange(4.0)}
    mgr.save(1, state, blocking=True)
    # simulate a crashed writer: stale .tmp directory with garbage
    os.makedirs(tmp_path / "step_9.tmp")
    (tmp_path / "step_9.tmp" / "arrays.npz").write_bytes(b"garbage")
    assert mgr.latest_step() == 1  # tmp dir not considered


def test_checkpoint_shape_mismatch_raises(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"x": jnp.zeros((2, 2))}, blocking=True)
    with pytest.raises(ValueError):
        mgr.restore(jax.eval_shape(lambda: {"x": jnp.zeros((3, 3))}))


# --- fault tolerance -------------------------------------------------------

def test_heartbeat_monitor():
    t = {"now": 0.0}
    mon = HeartbeatMonitor(num_hosts=3, timeout=10, clock=lambda: t["now"])
    t["now"] = 5.0
    mon.beat(0)
    mon.beat(1)
    t["now"] = 12.0
    assert mon.dead_hosts() == [2]
    assert not mon.healthy()


def test_straggler_monitor():
    mon = StragglerMonitor(window=20, factor=2.0, patience=3)
    for _ in range(10):
        assert not mon.record(1.0)
    assert mon.record(5.0)
    assert mon.record(5.0)
    assert not mon.should_remesh()
    assert mon.record(5.0)
    assert mon.should_remesh()


def test_plan_elastic_mesh():
    shape, axes = plan_elastic_mesh(512, 16)
    assert shape == (2, 16, 16) and axes == ("pod", "data", "model")
    # lost a pod's worth of chips -> single-pod mesh
    shape, axes = plan_elastic_mesh(300, 16)
    assert shape == (16, 16) and axes == ("data", "model")
    # heavy loss -> shrink data axis to a power of two
    shape, axes = plan_elastic_mesh(100, 16)
    assert shape == (4, 16)
    with pytest.raises(ValueError):
        plan_elastic_mesh(8, 16)


def test_supervisor_restart_resumes_from_checkpoint():
    calls = {"saves": [], "restores": 0}
    progressed = []

    def step_fn(step):
        if step == 7 and calls["restores"] == 0:
            raise RuntimeError("boom")
        progressed.append(step)

    def save_fn(step):
        calls["saves"].append(step)

    def restore_fn():
        calls["restores"] += 1
        return max([s for s in calls["saves"]], default=0)

    sup = TrainLoopSupervisor(checkpoint_every=5)
    final = sup.run(0, 10, step_fn, save_fn, restore_fn)
    assert final == 10
    assert calls["restores"] == 1
    assert 7 in progressed  # the failed step was replayed after restore


def test_train_restart_bitwise_reproducible(tmp_path):
    """Crash + restore + deterministic data => identical final state."""
    cfg = get_config("tinyllama-1.1b").reduced()
    tcfg = TrainConfig(learning_rate=1e-3, warmup_steps=2, total_steps=8)
    pipe = SyntheticLMPipeline(cfg, 2, 16, PipelineConfig(seed=0))
    step_fn = jax.jit(make_train_step(cfg, tcfg))

    def run(with_crash: bool):
        mgr = CheckpointManager(str(tmp_path / ("a" if with_crash else "b")))
        state = init_train_state(cfg, tcfg, jax.random.key(0))
        s = 0
        while s < 6:
            batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(s).items()}
            state, _ = step_fn(state, batch)
            s += 1
            if s == 3:
                mgr.save(s, state, blocking=True)
                if with_crash:
                    # lose the in-memory state, restore from disk
                    state = mgr.restore(jax.eval_shape(lambda: state))
        return state

    s1 = run(False)
    s2 = run(True)
    for l1, l2 in zip(jax.tree.leaves(s1), jax.tree.leaves(s2)):
        np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))


# --- data pipeline ----------------------------------------------------------

def test_pipeline_deterministic_and_restartable():
    cfg = get_config("tinyllama-1.1b").reduced()
    p1 = SyntheticLMPipeline(cfg, 4, 32, PipelineConfig(seed=1))
    p2 = SyntheticLMPipeline(cfg, 4, 32, PipelineConfig(seed=1))
    b1 = p1.batch_at(17)
    b2 = p2.batch_at(17)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b3 = p1.batch_at(18)
    assert not np.array_equal(b1["tokens"], b3["tokens"])


def test_pipeline_packing_reduces_padding():
    cfg = get_config("tinyllama-1.1b").reduced()
    pk = SyntheticLMPipeline(cfg, 4, 256, PipelineConfig(seed=2, pack=True, mean_doc_len=32))
    un = SyntheticLMPipeline(cfg, 4, 256, PipelineConfig(seed=2, pack=False))
    packed = pk.batch_at(0)
    frac_pad = float((packed["labels"] < 0).mean())
    assert frac_pad < 0.25, frac_pad
    assert (un.batch_at(0)["labels"] >= 0).all()


def test_pipeline_host_sharding_differs():
    cfg = get_config("tinyllama-1.1b").reduced()
    h0 = SyntheticLMPipeline(cfg, 2, 32, PipelineConfig(seed=1, host_id=0, num_hosts=2))
    h1 = SyntheticLMPipeline(cfg, 2, 32, PipelineConfig(seed=1, host_id=1, num_hosts=2))
    assert not np.array_equal(h0.batch_at(0)["tokens"], h1.batch_at(0)["tokens"])


# --- optimizer / compression -------------------------------------------------

def test_adamw_matches_closed_form_single_param():
    tcfg = TrainConfig(learning_rate=0.1, warmup_steps=0, total_steps=10,
                       weight_decay=0.0, grad_clip=1e9)
    params = {"w": jnp.array([1.0, -2.0])}
    grads = {"w": jnp.array([0.5, 0.5])}
    opt = init_opt_state(params)
    new_p, new_opt, _ = adamw_update(tcfg, params, grads, opt, jnp.int32(0))
    lr = float(cosine_schedule(tcfg, jnp.int32(0)))
    m = 0.1 * 0.5 / (1 - 0.9)
    v = 0.05 * 0.25 / (1 - 0.95)
    expected = 1.0 - lr * (m / (np.sqrt(v) + tcfg.eps))
    np.testing.assert_allclose(float(new_p["w"][0]), expected, rtol=1e-5)


def test_grad_clip_effective():
    tcfg = TrainConfig(grad_clip=1.0)
    params = {"w": jnp.zeros(4)}
    grads = {"w": jnp.full(4, 100.0)}
    opt = init_opt_state(params)
    _, _, metrics = adamw_update(tcfg, params, grads, opt, jnp.int32(0))
    assert float(metrics["grad_norm"]) > 100


def test_compression_error_feedback_unbiased():
    g = {"w": jnp.asarray(np.random.default_rng(0).standard_normal(1000), jnp.float32)}
    err = compression.init_error_state(g)
    total_sent = jnp.zeros(1000)
    cur_err = err["w"]
    for _ in range(50):
        comp, new_err = compression.compress_grads(g, {"w": cur_err}, "topk", 0.05)
        total_sent = total_sent + comp["w"]
        cur_err = new_err["w"]
    # cumulative transmitted + residual == cumulative gradient (exactness of EF)
    np.testing.assert_allclose(
        np.asarray(total_sent + cur_err), np.asarray(g["w"] * 50), rtol=1e-4, atol=1e-4
    )


def test_int8_compression_bounded_error():
    g = {"w": jnp.asarray(np.random.default_rng(1).standard_normal(512), jnp.float32)}
    err = compression.init_error_state(g)
    comp, new_err = compression.compress_grads(g, err, "int8", 0.0)
    scale = float(jnp.max(jnp.abs(g["w"]))) / 127
    assert float(jnp.max(jnp.abs(comp["w"] - g["w"]))) <= scale * 0.5 + 1e-6


# --- compile cache -----------------------------------------------------------

_CACHE_PROBE = """
import jax, jax.numpy as jnp
from repro.utils.compile_cache import enable_compile_cache
d = enable_compile_cache()
jax.jit(lambda x: jnp.sort(x) * 2)(jnp.arange(10.0)).block_until_ready()
print(d)
"""


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_location(tmp_path, from_env):
    """Compiled programs land in $JAX_COMPILATION_CACHE_DIR when it is set,
    else in the checkout's fixed .jax_cache."""
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"), JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = os.path.join(root, ".jax_cache")
    if from_env:
        want = str(tmp_path / "cache")
        env["JAX_COMPILATION_CACHE_DIR"] = want
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env, capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().splitlines()[-1] == want
    assert any(f.startswith("jit__lambda") for f in os.listdir(want))
