"""Kernel-path training smoke test (``make train-smoke``).

Runs one **real** :func:`repro.train.steps.make_train_step` step — loss,
backward, AdamW update — for two reduced-but-faithful configs that route
training through the Pallas kernels:

* falcon-mamba (SSM family) with ``ssm_backend="fused"`` — the forward
  AND backward go through ``repro.kernels.ssm_scan``'s chunk-recompute
  ``custom_vjp``;
* phi3.5-moe (MoE family) with ``moe_dispatch="merge_path_pallas"`` —
  dispatch positions come from the bitonic tile-engine kv-sort in
  ``repro.kernels.ops`` (seq is sized so the flat round actually exceeds
  the minimum Pallas tile and the kernel, not the XLA fallback, runs).

For each config it asserts:

1. the step's loss is finite;
2. ``jax.grad`` of the *same* loss function produces a finite, nonzero
   gradient on **every** parameter leaf (a dead leaf means a route
   silently detached — exactly the failure mode the custom VJPs close);
3. the optimizer update actually moved the parameters.

Interpret-mode Pallas (the default off-TPU) makes this CPU-runnable; on
real hardware the same script exercises the compiled kernels.
"""

from __future__ import annotations

import dataclasses
import sys

import jax
import jax.numpy as jnp

from repro.configs.registry import get_config
from repro.train.steps import init_train_state, make_train_step


def _fake_batch(key: jax.Array, batch: int, seq: int, vocab: int):
    tok = jax.random.randint(key, (batch, seq), 0, vocab, jnp.int32)
    labels = jnp.roll(tok, -1, axis=1).at[:, -1].set(-1)  # mask last position
    return {"tokens": tok, "labels": labels}


def _leaf_report(grads) -> list:
    """(path, finite, nonzero) per leaf."""
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(grads)[0]:
        name = jax.tree_util.keystr(path)
        finite = bool(jnp.all(jnp.isfinite(leaf)))
        nonzero = bool(jnp.any(leaf != 0))
        out.append((name, finite, nonzero))
    return out


def smoke_one(name: str, cfg, *, batch: int, seq: int, seed: int = 0) -> bool:
    from repro.configs.base import TrainConfig

    tcfg = TrainConfig(z_loss=1e-4, grad_clip=1.0)
    key = jax.random.PRNGKey(seed)
    kinit, kbatch = jax.random.split(key)
    state = init_train_state(cfg, tcfg, kinit)
    # the linear warmup is exactly 0 at step 0; start mid-warmup so a
    # zero-lr first step can't mask a dead backward
    state["step"] = jnp.ones((), jnp.int32)
    data = _fake_batch(kbatch, batch, seq, cfg.vocab_size)

    # per-leaf gradient audit against the identical loss the step uses
    step = make_train_step(cfg, tcfg)
    from repro.train.steps import _cast, cross_entropy_loss
    from repro.models import forward_train

    def loss_fn(params):
        logits = forward_train(cfg, _cast(params, jnp.dtype(cfg.dtype)), data)
        return cross_entropy_loss(logits, data["labels"], tcfg.z_loss)[0]

    loss0, grads = jax.value_and_grad(loss_fn)(state["params"])
    report = _leaf_report(grads)
    bad = [(n, f, z) for n, f, z in report if not (f and z)]

    new_state, metrics = jax.jit(step)(state, data)
    loss = float(metrics["loss"])
    moved = any(
        bool(jnp.any(a != b))
        for a, b in zip(jax.tree.leaves(state["params"]), jax.tree.leaves(new_state["params"]))
    )

    ok = jnp.isfinite(loss0) and jnp.isfinite(loss) and not bad and moved
    status = "ok" if ok else "FAIL"
    print(
        f"[train-smoke] {name}: loss={loss:.4f} leaves={len(report)} "
        f"all_finite_nonzero={not bad} params_moved={moved} -> {status}"
    )
    for n, f, z in bad:
        print(f"  BAD LEAF {n}: finite={f} nonzero={z}")
    return bool(ok)


def main() -> int:
    ok = True

    # SSM on the fused Pallas scan (falcon-mamba-shaped). Seq straddles
    # chunk boundaries (not a multiple of ssm_chunk=8) so the identity-pad
    # path of the kernel is part of the trained graph.
    ssm = dataclasses.replace(get_config("falcon-mamba").reduced(), ssm_backend="fused")
    ok &= smoke_one("falcon-mamba/fused-ssm-scan", ssm, batch=2, seq=36)

    # MoE on the tile-engine dispatch (phi3.5-moe-shaped). seq*k = 512
    # assignment slots > the minimum int sort tile (256), so the flat
    # merge round runs in the Pallas kernel, not the small-n fallback.
    moe = dataclasses.replace(
        get_config("phi35-moe").reduced(), moe_dispatch="merge_path_pallas"
    )
    ok &= smoke_one("phi3.5-moe/merge-path-pallas", moe, batch=1, seq=256)

    if not ok:
        print("[train-smoke] FAILED", file=sys.stderr)
        return 1
    print("[train-smoke] all kernel-path train steps passed")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
