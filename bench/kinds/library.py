"""Library traffic: back-to-back calls of one ``repro.kernels.ops``
operation, each awaited, cycling over inputs made from the seed.

Traffic keys: ``op`` (``bench/ops/<op>.py``), ``inputs`` (how many inputs
to cycle over), ``kept_outputs`` (a seeded reservoir sample of the window's
calls whose outputs are compared with the reference), ``check`` (the limit
of each number compared) and the op's own keys.  The configuration's module
makes the tables (``tables(cfg, key)``).

Every call's output rows count in ``keys_per_s``.  A call that the guarded
dispatch served with anything but the Pallas kernel (``pallas-hier``)
counts as failed, and ``failed_calls`` is compared beside the rows: a
fallback was not the kernel under test.
"""

from __future__ import annotations

import os
import time

from harness import device, registry, seeds
from harness.record import Record
from harness.trace import TRACE_DIR, Window

KERNEL_EDGE = "pallas-hier"


def _served_by(resilience, op: str) -> dict:
    return dict(resilience.health_summary().get(op, {}).get("served_by", {}))


def make_inputs(cell: dict, seed: int, op) -> list:
    import jax

    data = registry.reference(cell["config_name"])
    tr = cell["traffic"]
    inputs = []
    for v in range(tr["inputs"]):
        tables = data.tables(cell["config"], seeds.jax_key(seed, 1, v))
        inputs.append(op.operands(tables, tr, seeds.jax_key(seed, 2, v)))
    return jax.block_until_ready(inputs)


def compare(op, inputs: list, kept: list, fn=None) -> int:
    """Rows of the kept outputs that differ from ``fn`` (the reference)."""
    import jax.numpy as jnp

    fn = fn or op.reference
    want, bad = {}, 0
    for _, v, (keys, values) in kept:
        if v not in want:
            want[v] = fn(*inputs[v])
        rk, rv = want[v]
        if keys.shape != rk.shape or values.shape != rv.shape:
            bad += int(rk.shape[0])
            continue
        bad += int(jnp.sum((keys != rk) | (values != rv)))
    return bad


def run(cell: dict, seed: int, seconds: float, traced: bool, devices, t0: float, log) -> Record:
    import jax
    from repro.kernels import ops as program
    from repro.runtime import resilience

    tr = cell["traffic"]
    op = registry.op(tr["op"])
    inputs = make_inputs(cell, seed, op)
    n_out = op.n_out(inputs[0])
    jax.block_until_ready(op.call(program, inputs[0]))  # warm-up: the one shape the window uses
    before = _served_by(resilience, tr["op"])
    rng = seeds.rng(seed, 3)
    kept, calls = [], []
    window = Window(seconds, traced, os.path.join(registry.CHECKOUT, TRACE_DIR))
    start = window.open()
    try:
        i = 0
        while True:
            v = i % len(inputs)
            s = time.monotonic()
            with jax.profiler.TraceAnnotation("bench.call"):
                out = jax.block_until_ready(op.call(program, inputs[v]))
            e = time.monotonic()
            calls.append((s, e, n_out))
            # reservoir sample of the window's outputs, drawn from the seed
            if len(kept) < tr["kept_outputs"]:
                kept.append((i, v, out))
            else:
                j = int(rng.integers(0, i + 1))
                if j < len(kept):
                    kept[j] = (i, v, out)
            del out
            i += 1
            if window.over(e):
                break
    finally:
        window.close()
    after = _served_by(resilience, tr["op"])
    rec = Record(
        kind="library", cell=cell, peaks=device.peaks(devices[0].device_kind),
        work=registry.work(cell["config_name"]), setup_s=start - t0,
        window=(start, window.end), traced_window=window.traced_window, calls=calls,
    )
    rec.memory_peak_bytes = device.memory_peak_bytes(devices)
    served = {k: after.get(k, 0) - before.get(k, 0) for k in after}
    rec.attempted = len(calls)
    rec.failed = sum(n for edge, n in served.items() if edge != KERNEL_EDGE)
    log(f"{tr['op']}: {len(calls)} calls of {n_out} rows in {rec.window_s:.3f} s "
        f"(programs compiled, loaded from cache in the window: {window.compiled}), "
        f"served_by {served}; comparing {len(kept)} outputs (calls {sorted(k[0] for k in kept)})")
    rec.trace = window.read()
    rec.checks = {"mismatched_rows": {"value": compare(op, inputs, kept),
                                      "limit": tr["check"]["mismatched_rows"]},
                  "failed_calls": {"value": rec.failed, "limit": tr["check"]["failed_calls"]}}
    return rec


def control(cell: dict, seed: int, seconds: float, devices, log) -> dict:
    """The numbers compared, for the program and for the control (the op's
    ``control``: the reference with stability broken) on the same inputs."""
    import jax
    from repro.kernels import ops as program

    op = registry.op(cell["traffic"]["op"])
    inputs = make_inputs(cell, seed, op)
    kept = [(v, v, jax.block_until_ready(op.call(program, x))) for v, x in enumerate(inputs)]
    ctrl = [(v, v, op.control(*x)) for v, x in enumerate(inputs)]
    return {"program": {"mismatched_rows": compare(op, inputs, kept)},
            "control": {"mismatched_rows": compare(op, inputs, ctrl)}}
