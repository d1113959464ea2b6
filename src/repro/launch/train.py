"""End-to-end training driver.

CPU-example scale by default (reduced config, tiny mesh or no mesh);
pass ``--production`` under a real TPU slice to use the full config and
the (data, model) production mesh.  Demonstrates the full production
loop: data pipeline -> jitted train step -> checkpoint cadence ->
failure recovery (supervisor) -> straggler monitoring.

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
        --steps 60 --batch 8 --seq 64 --reduced
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs import TrainConfig, get_config
from repro.data.pipeline import PipelineConfig, SyntheticLMPipeline
from repro.runtime.fault_tolerance import StragglerMonitor, TrainLoopSupervisor
from repro.train.steps import init_train_state, make_train_step
from repro.utils.compile_cache import enable_compile_cache


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--no-reduced", dest="reduced", action="store_false")
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--grad-compression", default="none", choices=["none", "topk", "int8"])
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt_train")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--inject-failure-at", type=int, default=-1,
                    help="simulate a crash at this step (tests restart path)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    tcfg = TrainConfig(
        learning_rate=args.lr,
        warmup_steps=10,
        total_steps=args.steps,
        microbatch=args.microbatch,
        grad_compression=args.grad_compression,
        checkpoint_every=args.ckpt_every,
        checkpoint_dir=args.ckpt_dir,
    )
    pipe = SyntheticLMPipeline(cfg, args.batch, args.seq, PipelineConfig(seed=tcfg.seed))
    mgr = CheckpointManager(tcfg.checkpoint_dir, keep=3)
    state = init_train_state(cfg, tcfg, jax.random.key(tcfg.seed))
    start_step = 0
    if args.resume and mgr.latest_step() is not None:
        state = mgr.restore(jax.eval_shape(lambda: state))
        start_step = int(state["step"])
        print(f"resumed from step {start_step}")

    step_fn = jax.jit(make_train_step(cfg, tcfg), donate_argnums=(0,))
    straggler = StragglerMonitor()
    stateholder = {"state": state}
    inject = {"armed": args.inject_failure_at >= 0}

    def one_step(step: int) -> None:
        if inject["armed"] and step == args.inject_failure_at:
            inject["armed"] = False
            raise RuntimeError("injected failure (simulated node loss)")
        batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(step).items()}
        t0 = time.time()
        stateholder["state"], metrics = step_fn(stateholder["state"], batch)
        dt = time.time() - t0
        if straggler.record(dt):
            print(f"[straggler] step {step} took {dt:.3f}s")
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step:5d} loss {float(metrics['loss']):.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} lr {float(metrics['lr']):.2e} "
                  f"({dt*1e3:.0f} ms)")

    def save(step: int) -> None:
        mgr.save(step, stateholder["state"], blocking=False)

    def restore() -> int:
        mgr.wait()
        latest = mgr.latest_step()
        if latest is None:
            stateholder["state"] = init_train_state(cfg, tcfg, jax.random.key(tcfg.seed))
            return 0
        stateholder["state"] = mgr.restore(jax.eval_shape(lambda: stateholder["state"]))
        print(f"[recovery] restored step {latest}")
        return latest

    sup = TrainLoopSupervisor(checkpoint_every=tcfg.checkpoint_every)
    final = sup.run(start_step, args.steps, one_step, save, restore)
    mgr.wait()
    mgr.save(final, stateholder["state"], blocking=True)
    print(f"done at step {final}; checkpoints in {tcfg.checkpoint_dir}")


if __name__ == "__main__":
    main()
