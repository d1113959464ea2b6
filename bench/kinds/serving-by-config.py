"""Serving traffic for a configuration whose reference file maps its own
keys onto the program (``configs/<c>.py::program_config``).

The traffic, the closed loop, the window, the sample that is checked and
the gaps are the ``serving`` kind's (``kinds/serving.py``: ``plan``,
``drive``, ``_select``, ``gaps``, ``p90``), reused as they are.  Two
things differ:

* the program's configuration comes from the reference file, so a block
  that ``serving``'s mapping of Phi-style keys cannot express (latent
  attention, sigmoid routing, leading dense layers) runs through the same
  engine;
* each step records the deltas of the engine's counters
  (``serving.decode_calls``, ``moe.experts_reached``,
  ``mla.latent_positions`` of ``repro.telemetry``) under ``counts``, for
  the per-layer readers.

Warm-up serves a greedy request when the traffic has greedy clients and a
top-k one when it has others, so that no sampler the traffic never uses
is compiled.  The record is ``kind="serving"``: the serving readers apply.
"""

from __future__ import annotations

import gc
import os
from typing import Dict

import numpy as np

from harness import device, registry, seeds
from harness.record import Record
from harness.trace import TRACE_DIR, Window

serving = registry.kind("serving")

COUNTERS = ("serving.decode_calls", "moe.experts_reached", "mla.latent_positions")


def _counts() -> Dict[str, int]:
    from repro.telemetry import get_telemetry

    tel = get_telemetry()
    return {name: tel.counter(name).value for name in COUNTERS}


class CountingEngine:
    """The engine as ``drive`` sees it, recording the counters' deltas of each ``step()``."""

    def __init__(self, engine):
        self.engine = engine
        self.deltas = []

    def __getattr__(self, name):
        return getattr(self.engine, name)

    def step(self) -> None:
        before = _counts()
        self.engine.step()
        after = _counts()
        self.deltas.append({k: after[k] - before[k] for k in COUNTERS})


def setup(cell: dict, seed: int, log=print):
    """The program's configuration (first: a program that cannot take it
    fails here, at once), weights from the seed, and the engine, warmed on
    every shape and sampler the traffic uses."""
    import jax
    from repro.serving.engine import Request, ServingEngine

    cfg, tr = cell["config"], cell["traffic"]
    ref = registry.reference(cell["config_name"])
    pc = ref.program_config(cfg, log)
    w = jax.block_until_ready(ref.weights(cfg, seeds.jax_key(seed, 1)))
    engine = ServingEngine(pc, w, batch=tr["slots"], max_seq=tr["max_seq"], seed=seed % (1 << 32))
    temps = ([0.0] if tr["greedy_clients"] > 0 else []) + ([tr["temperature"]] if tr["greedy_clients"] < tr["clients"] else [])
    for i, temp in enumerate(temps):
        engine.submit(Request(uid=-1 - i, prompt=np.ones(2, np.int32), max_new_tokens=2,
                              temperature=temp, topk=tr["topk"]))
    report = engine.run_until_done()
    if not report.ok():
        device.fail(f"warm-up requests did not complete: {report.statuses}")
    jax.block_until_ready(engine.caches)
    return ref, pc, w, engine


def run(cell: dict, seed: int, seconds: float, traced: bool, devices, t0: float, log) -> Record:
    cfg, tr = cell["config"], cell["traffic"]
    ref, pc, w, engine = setup(cell, seed, log)
    counting = CountingEngine(engine)
    window = Window(seconds, traced, os.path.join(registry.CHECKOUT, TRACE_DIR))
    try:
        reqs, steps = serving.drive(counting, tr, seed, cfg["vocab_size"], window)
    finally:
        window.close()
    for step, delta in zip(steps, counting.deltas):
        step["counts"] = delta
    rec = Record(
        kind="serving", cell=cell, peaks=device.peaks(devices[0].device_kind),
        work=registry.work(cell["config_name"]), setup_s=window.start - t0,
        window=(window.start, window.end), traced_window=window.traced_window,
        requests=reqs, steps=steps,
    )
    rec.memory_peak_bytes = device.memory_peak_bytes(devices)
    del engine, counting  # the program's state (caches); the weights stay for the reference
    gc.collect()
    lo, hi = rec.window
    ended = [r for r in reqs if r["end"] is not None and lo < r["end"] <= hi]
    served = [r for r in reqs if r["generated"]]
    rec.attempted = len(ended)
    rec.failed = sum(r["status"] != "completed" for r in ended)
    inside = [s for s in steps if s["start"] >= lo and s["end"] <= hi]
    calls = {k: sum(s["counts"][k] for s in inside) for k in COUNTERS}
    log(f"window {rec.window_s:.3f} s (programs compiled, loaded from cache in it: {window.compiled}): "
        f"{len(ended)} requests ended ({rec.failed} not completed), "
        f"{sum(s['tokens'] for s in inside)} tokens, {len(inside)} steps, counts {calls}")
    rec.trace = window.read()
    sample = serving._select(served, tr, seed)
    g = serving.gaps(ref, cfg, w, sample, tr["max_seq"], tr)
    log(f"check: {len(sample)} requests; greedy gaps: {serving._summary(g['greedy'])}")
    rec.checks = {
        "failed_requests": {"value": rec.failed, "limit": 0},
        "greedy_gap_p90": {"value": serving.p90(g["greedy"]), "limit": tr["check"]["greedy_gap_p90"]},
    }
    return rec


def control(cell: dict, seed: int, seconds: float, devices, log) -> dict:
    """The numbers compared, for the program (a window of ``seconds`` at the
    cell's own load) and for the control (the reference at ``fp8``,
    serving the tokens it would serve) on the same prompts and served
    tokens; beside them two witnesses of what bfloat16 alone does: the
    reference's ``bfloat16`` pass serving its own tokens, and how many of
    the program's greedy tokens more than 0.1 and more than the limit below
    the reference's best sit where the router's choice flips when it reads
    bfloat16 (``kinds/serving.py::flips_at_wide_gaps``)."""
    cfg, tr = cell["config"], cell["traffic"]
    ref, pc, w, engine = setup(cell, seed, log)
    window = Window(seconds, False, "")
    try:
        reqs, steps = serving.drive(engine, tr, seed, cfg["vocab_size"], window)
    finally:
        window.close()
    del engine
    gc.collect()
    sample = serving._select([r for r in reqs if r["generated"]], tr, seed)
    prog = serving.gaps(ref, cfg, w, sample, tr["max_seq"], tr)
    ctrl = serving.gaps(ref, cfg, w, sample, tr["max_seq"], tr, precision="fp8", seed=seed)
    bf16 = serving.gaps(ref, cfg, w, sample, tr["max_seq"], tr, precision="bfloat16", seed=seed)
    out = {"program": {k + "_gap": serving._summary(v) for k, v in prog.items()},
           "control": {k + "_gap": serving._summary(v) for k, v in ctrl.items()},
           "bfloat16_reference": {k + "_gap": serving._summary(v) for k, v in bf16.items()},
           "router_flips": {str(wide): serving.flips_at_wide_gaps(ref, cfg, w, sample, tr["max_seq"], wide)
                            for wide in (0.1, tr["check"]["greedy_gap_p90"])},
           "requests": len(sample), "served_tokens": sum(len(r["generated"]) for r in sample),
           "not_completed": sum(r["status"] not in (None, "completed") for r in reqs)}
    del w
    gc.collect()
    return out
