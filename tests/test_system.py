"""End-to-end system tests: train driver (with failure injection), loss
convergence, and launch-layer plumbing that doesn't need 512 devices."""

import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import SHAPES, TrainConfig, get_config, list_archs
from repro.data.pipeline import PipelineConfig, SyntheticLMPipeline
from repro.train.steps import init_train_state, make_train_step
from subproc import SUBPROCESS_TIMEOUT_S, src_env


def test_overfit_single_batch():
    cfg = get_config("tinyllama-1.1b").reduced()
    tcfg = TrainConfig(learning_rate=1e-2, warmup_steps=2, total_steps=40)
    state = init_train_state(cfg, tcfg, jax.random.key(0))
    step = jax.jit(make_train_step(cfg, tcfg))
    toks = jax.random.randint(jax.random.key(1), (4, 17), 0, cfg.vocab_size)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    losses = []
    for _ in range(30):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < 0.5 * losses[0]
    assert np.isfinite(losses).all()


def test_grad_compression_still_learns():
    cfg = get_config("tinyllama-1.1b").reduced()
    tcfg = TrainConfig(learning_rate=1e-2, warmup_steps=2, total_steps=40,
                       grad_compression="int8")
    state = init_train_state(cfg, tcfg, jax.random.key(0))
    step = jax.jit(make_train_step(cfg, tcfg))
    toks = jax.random.randint(jax.random.key(1), (4, 17), 0, cfg.vocab_size)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    losses = []
    for _ in range(30):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < 0.6 * losses[0]


def test_train_driver_with_failure_injection(tmp_path):
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "tinyllama-1.1b",
         "--steps", "25", "--batch", "2", "--seq", "16", "--ckpt-every", "10",
         "--inject-failure-at", "13", "--ckpt-dir", str(tmp_path)],
        env=src_env(), capture_output=True, text=True, timeout=SUBPROCESS_TIMEOUT_S,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "[recovery] restored step 10" in proc.stdout
    assert "done at step 25" in proc.stdout


def test_serve_driver(tmp_path):
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "tinyllama-1.1b",
         "--requests", "3", "--batch", "2", "--max-new", "3"],
        env=src_env(), capture_output=True, text=True, timeout=SUBPROCESS_TIMEOUT_S,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "served 3 requests" in proc.stdout


def test_all_archs_registered_with_shapes():
    archs = list_archs()
    assert len(archs) == 10
    assert len(SHAPES) == 4
    for a in archs:
        cfg = get_config(a)
        assert cfg.n_params() > 0
        r = cfg.reduced()
        assert r.d_model == 64


def test_input_specs_cover_all_cells():
    from repro.configs import SHAPES_BY_NAME, shape_applicable
    from repro.launch.specs import input_specs

    for a in list_archs():
        cfg = get_config(a)
        for s in SHAPES:
            ok, _ = shape_applicable(cfg, s)
            if not ok:
                continue
            specs = input_specs(cfg, s)
            leaves = jax.tree.leaves(specs)
            assert leaves, (a, s.name)
            for l in leaves:
                assert isinstance(l, jax.ShapeDtypeStruct)
