"""Serving traffic: closed-loop clients driving ``ServingEngine.submit`` /
``step`` as a server loop does.

Traffic keys: ``clients`` and ``slots`` (the engine's batch), ``max_seq``,
``prompt`` and ``output`` lengths (``median``, ``sigma`` of a log-normal,
clipped to ``min``..``max``), ``topk``, ``temperature``,
``greedy_clients`` (the first clients decode greedily, so that the check
reads some hundreds of greedy tokens from several slots),
``pool`` and ``check``.

Every seed gets the same request sizes in the same blocks of 8: ``pool``
lengths at evenly spaced quantiles of each distribution, each block one
size from every eighth.  The seed orders the requests inside each block
and draws the prompt tokens.  Each client sends its next request as soon
as its last one completes; a greedy client's requests are greedy, so the
mix of greedy and top-k slots stays the same for every seed.

The window opens once every slot holds a request that has its first token
and closes with the first ``step()`` that returns ``seconds`` later.  A
token counts at the time the ``step()`` that delivered it returned.  After
the window a seeded sample of the requests served by then (completed or
still in flight, each with the tokens it was served), the longest among
them, is run through the configuration's reference: for a greedy token the
gap by which its reference logit lies below the reference's best, for a
top-k token the gap below the reference's k-th best, each in standard
deviations of the reference's logits at that position.  The number
compared is the 90th percentile of the greedy gaps: a few percent of the
tokens lie far below the reference's best in any precision, so the widest
gap is as wide for bfloat16 as for the fp8 control, and the 90th
percentile separates the two.  The top-k gaps are printed, not compared:
a token drawn from the top 40 at temperature 0.8 lies well inside the
reference's top 40 in either precision, so they do not separate the
control.
"""

from __future__ import annotations

import dataclasses
import gc
import os
import statistics
import time
from typing import Dict, Iterator, List

import numpy as np

from harness import device, registry, seeds
from harness.record import Record
from harness.stats import percentile
from harness.trace import TRACE_DIR, Window

NORMAL = statistics.NormalDist()


def lengths(spec: dict, n: int) -> List[int]:
    """``n`` lengths at the quantiles ``(i + 0.5) / n`` of the clipped log-normal."""
    out = []
    for i in range(n):
        z = NORMAL.inv_cdf((i + 0.5) / n)
        out.append(int(min(spec["max"], max(spec["min"], round(spec["median"] * np.exp(spec["sigma"] * z))))))
    return out


def plan(tr: dict, seed: int, vocab: int) -> Iterator[Dict]:
    """The request stream of one seed: prompt and output length.

    Block ``b`` of 8 requests always holds the same 8 (prompt, output)
    pairs, one from each eighth of both distributions, whatever the seed;
    the seed orders the pairs inside each block and draws the tokens."""
    n = tr["pool"]
    prompts, outputs = lengths(tr["prompt"], n), lengths(tr["output"], n)
    fixed = np.random.default_rng(0)  # the blocks' contents: the same for every seed
    strata = n // 8
    p_order = [fixed.permutation(strata) + s * strata for s in range(8)]
    o_order = [fixed.permutation(strata) + s * strata for s in range(8)]
    pairs = [[(prompts[p_order[s][b]], outputs[o_order[t][b]])
              for s, t in zip(range(8), fixed.permutation(8))] for b in range(strata)]
    rng = seeds.rng(seed, 4)
    uid = 0
    while True:
        for block in pairs:
            for j in rng.permutation(8):
                plen, olen = block[j]
                yield {"uid": uid, "prompt": rng.integers(1, vocab, size=plen).astype(np.int32), "max_new": olen}
                uid += 1


def program_config(cfg: dict, log=None):
    """The program's preset for ``cfg["program"]["arch"]`` with every size
    taken from the configuration file (the file holds what is run)."""
    from repro.configs import get_config

    prog = cfg["program"]
    base = get_config(prog["arch"])
    sizes = {
        "num_layers": cfg["num_hidden_layers"], "d_model": cfg["hidden_size"],
        "d_ff": cfg["intermediate_size"], "num_heads": cfg["num_attention_heads"],
        "num_kv_heads": cfg["num_key_value_heads"], "head_dim": cfg["head_dim"],
        "num_experts": cfg["num_local_experts"], "experts_per_token": cfg["num_experts_per_tok"],
        "vocab_size": cfg["vocab_size"], "rms_eps": cfg["rms_norm_eps"], "rope_theta": cfg["rope_theta"],
        "tie_embeddings": cfg["tie_word_embeddings"], "dtype": cfg["torch_dtype"],
        "capacity_factor": prog["capacity_factor"],
    }
    changed = {k: (getattr(base, k), v) for k, v in sizes.items() if getattr(base, k) != v}
    pc = dataclasses.replace(base, **sizes)
    if pc.sliding_window or pc.attn_logit_softcap or pc.shared_expert_ff or pc.act != "silu":
        device.fail(f"{prog['arch']}: the reference has no sliding window, softcap, shared expert or {pc.act}")
    if log:
        log(f"program: {prog['arch']} (preset values changed by the file: {changed}), "
            f"moe_dispatch {pc.moe_dispatch}, capacity_factor {pc.capacity_factor}")
    return pc


def _select(served: List[Dict], tr: dict, seed: int) -> List[Dict]:
    """Seeded sample of the requests served by the close: the longest, then
    greedy and top-k requests until each kind has ``tokens_per_kind``."""
    if not served:
        return []
    rng = seeds.rng(seed, 5)
    longest = max(served, key=lambda r: len(r["generated"]))
    chosen, count = [longest], {True: 0, False: 0}
    count[longest["greedy"]] += len(longest["generated"])
    rest = [r for r in served if r is not longest]
    for i in rng.permutation(len(rest)):
        r = rest[i]
        if count[r["greedy"]] < tr["check"]["tokens_per_kind"]:
            chosen.append(r)
            count[r["greedy"]] += len(r["generated"])
    return chosen


def gaps(ref, cfg: dict, w, reqs: List[Dict], max_seq: int, tr: dict, precision: str = "float32",
         seed: int = 0) -> Dict:
    """Per served token, the gap of the token's reference logit below the
    reference's k-th best (k = 1 greedy, ``topk`` sampled), in standard
    deviations of that reference row.  With ``precision`` other than
    float32 (the control) the token is instead the one that precision
    would serve at that position: its best for a greedy request, and for a
    top-k request one drawn from its own top ``topk`` at the request's
    temperature, as the engine's sampler draws (keys from ``seed``)."""
    import jax
    import jax.numpy as jnp

    out = {"greedy": [], "topk": []}
    kmax = tr["topk"]
    for r in reqs:
        seq = np.concatenate([r["prompt"], np.asarray(r["generated"], np.int32)])[:-1]
        pad = np.zeros(max_seq, np.int32)
        pad[: len(seq)] = seq
        at = np.arange(len(r["prompt"]) - 1, len(seq))  # positions that produced served tokens
        k = 1 if r["greedy"] else kmax
        want = ref.logits(cfg, w, pad)[at]
        vals, _ = jax.lax.top_k(want, kmax)
        if precision == "float32":
            tok = jnp.asarray(r["generated"], jnp.int32)
        else:
            cvals, cidx = jax.lax.top_k(ref.logits(cfg, w, pad, precision)[at], kmax)
            if r["greedy"]:
                tok = cidx[:, 0]
            else:
                choice = jax.random.categorical(seeds.jax_key(seed, 6, r["uid"]), cvals / tr["temperature"])
                tok = jnp.take_along_axis(cidx, choice[:, None], axis=1)[:, 0]
        g = (vals[:, k - 1] - jnp.take_along_axis(want, tok[:, None], axis=1)[:, 0]) / jnp.std(want, axis=1)
        out["greedy" if r["greedy"] else "topk"].extend(np.asarray(g).tolist())
    return out


def flips_at_wide_gaps(ref, cfg: dict, w, reqs: List[Dict], max_seq: int, wide: float) -> Dict:
    """For the served greedy tokens: how many lie more than ``wide`` below
    the reference's best, and at how many of those, and of all, the
    reference's top-2 experts change in some layer at the token's own
    position when its router reads bfloat16 (``ref.router_flips``)."""
    import jax.numpy as jnp

    counts = {"tokens": 0, "flipped": 0, "wide": 0, "wide_flipped": 0}
    for r in reqs:
        if not r["greedy"]:
            continue
        seq = np.concatenate([r["prompt"], np.asarray(r["generated"], np.int32)])[:-1]
        pad = np.zeros(max_seq, np.int32)
        pad[: len(seq)] = seq
        at = np.arange(len(r["prompt"]) - 1, len(seq))
        want = ref.logits(cfg, w, pad)[at]
        tok = jnp.asarray(r["generated"], jnp.int32)
        g = np.asarray((jnp.max(want, axis=1) - jnp.take_along_axis(want, tok[:, None], axis=1)[:, 0])
                       / jnp.std(want, axis=1))
        f = np.asarray(jnp.any(ref.router_flips(cfg, w, pad), axis=1))[at]  # at the token's own position
        counts["tokens"] += len(g)
        counts["flipped"] += int(f.sum())
        counts["wide"] += int((g > wide).sum())
        counts["wide_flipped"] += int((f & (g > wide)).sum())
    return counts


def drive(engine, tr: dict, seed: int, vocab: int, window: Window):
    """The closed loop; returns (requests, steps) as host records."""
    import jax
    from repro.serving.engine import Request

    stream = plan(tr, seed, vocab)
    live: Dict[int, Dict] = {}
    reqs: List[Dict] = []
    steps: List[Dict] = []

    def send(now: float, client: int) -> None:
        spec = dict(next(stream), client=client, greedy=client < tr["greedy_clients"])
        temp = 0.0 if spec["greedy"] else tr["temperature"]
        req = Request(uid=spec["uid"], prompt=spec["prompt"], max_new_tokens=spec["max_new"],
                      temperature=temp, topk=tr["topk"])
        r = dict(spec, req=req, submit=now, times=[], end=None, status=None, generated=[])
        engine.submit(req)
        live[req.uid] = r
        reqs.append(r)

    for c in range(tr["clients"]):
        send(time.monotonic(), c)
    opened = False
    while True:
        s = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.step"):
            engine.step()
        e = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.client"):
            step = {"start": s, "end": e, "tokens": 0, "first_token": False, "positions": []}
            for uid in list(live):
                r = live[uid]
                gen = r["req"].generated
                for j in range(len(r["times"]), len(gen)):
                    r["times"].append(e)
                    step["tokens"] += 1
                    if j == 0:  # the prompt was processed for it
                        step["first_token"] = True
                        step["positions"].extend(range(len(r["prompt"])))
                    else:  # one decode position: the previous token
                        step["positions"].append(len(r["prompt"]) + j - 1)
                if uid in engine.done:
                    r["end"], r["status"] = e, engine.done[uid].status
                    r["generated"] = list(gen)
                    del live[uid]
                    send(time.monotonic(), r["client"])
            steps.append(step)
        if not opened:
            busy = sum(1 for r in live.values() if r["times"])
            if busy >= tr["slots"]:
                opened = True
                window.open()
            continue
        if window.over(e):
            break
    for r in live.values():  # still in flight at the close: the tokens served so far
        r["generated"] = list(r["req"].generated)
    return reqs, steps


def setup(cell: dict, seed: int, log=print):
    """Weights from the seed and the engine, warmed on every shape the traffic uses."""
    import jax
    from repro.serving.engine import Request, ServingEngine

    cfg, tr = cell["config"], cell["traffic"]
    ref = registry.reference(cell["config_name"])
    pc = program_config(cfg, log)
    w = jax.block_until_ready(ref.weights(cfg, seeds.jax_key(seed, 1)))
    engine = ServingEngine(pc, w, batch=tr["slots"], max_seq=tr["max_seq"], seed=seed % (1 << 32))
    # warm-up: the decode step (slots, 1), the greedy and the top-k sampler
    for uid, temp in ((-1, 0.0), (-2, tr["temperature"])):
        engine.submit(Request(uid=uid, prompt=np.ones(2, np.int32), max_new_tokens=2,
                              temperature=temp, topk=tr["topk"]))
    report = engine.run_until_done()
    if not report.ok():
        device.fail(f"warm-up requests did not complete: {report.statuses}")
    jax.block_until_ready(engine.caches)
    return ref, pc, w, engine


def run(cell: dict, seed: int, seconds: float, traced: bool, devices, t0: float, log) -> Record:
    import jax

    cfg, tr = cell["config"], cell["traffic"]
    ref, pc, w, engine = setup(cell, seed, log)
    window = Window(seconds, traced, os.path.join(registry.CHECKOUT, TRACE_DIR))
    try:
        reqs, steps = drive(engine, tr, seed, cfg["vocab_size"], window)
    finally:
        window.close()
    rec = Record(
        kind="serving", cell=cell, peaks=device.peaks(devices[0].device_kind),
        work=registry.work(cell["config_name"]), setup_s=window.start - t0,
        window=(window.start, window.end), traced_window=window.traced_window,
        requests=reqs, steps=steps,
    )
    rec.memory_peak_bytes = device.memory_peak_bytes(devices)
    del engine  # the program's state (caches); the weights stay for the reference
    gc.collect()
    lo, hi = rec.window
    ended = [r for r in reqs if r["end"] is not None and lo < r["end"] <= hi]
    served = [r for r in reqs if r["generated"]]
    rec.attempted = len(ended)
    rec.failed = sum(r["status"] != "completed" for r in ended)
    n_tok = sum(s["tokens"] for s in steps if s["start"] >= lo and s["end"] <= hi)
    n_first = sum(1 for r in reqs if r["times"] and lo < r["times"][0] <= hi)
    log(f"window {rec.window_s:.3f} s (programs compiled, loaded from cache in it: {window.compiled}): "
        f"{len(ended)} requests ended ({rec.failed} not completed), "
        f"{n_tok} tokens, {n_first} first tokens, {sum(1 for s in steps if lo < s['end'] <= hi)} steps")
    rec.trace = window.read()
    sample = _select(served, tr, seed)
    g = gaps(ref, cfg, w, sample, tr["max_seq"], tr)
    log(f"check: {len(sample)} requests; gaps (greedy, top-k): {_summary(g['greedy'])}, {_summary(g['topk'])}")
    lim = tr["check"]
    rec.checks = {
        "failed_requests": {"value": rec.failed, "limit": 0},
        "greedy_gap_p90": {"value": p90(g["greedy"]), "limit": lim["greedy_gap_p90"]},
    }
    return rec


def p90(values: List[float]) -> float:
    """The 90th percentile of the gaps; with nothing compared, a reading no
    limit admits."""
    return percentile(values, 90) if values else 1e9


def _summary(values: List[float]) -> Dict:
    if not values:
        return {"n": 0}
    return {"n": len(values), "max": max(values), "p99": percentile(values, 99),
            "p95": percentile(values, 95), "p90": percentile(values, 90), "p50": percentile(values, 50),
            "mean": statistics.fmean(values), "above_0.1": sum(v > 0.1 for v in values)}


def control(cell: dict, seed: int, seconds: float, devices, log) -> dict:
    """The numbers compared, for the program (a window of ``seconds`` at the
    cell's own load) and for the control (the reference at ``fp8``, serving
    the tokens it would serve) on the same prompts and served tokens, and
    whether router flips explain the program's widest greedy gaps."""
    import jax

    cfg, tr = cell["config"], cell["traffic"]
    ref, pc, w, engine = setup(cell, seed, log)
    window = Window(seconds, False, "")
    try:
        reqs, steps = drive(engine, tr, seed, cfg["vocab_size"], window)
    finally:
        window.close()
    del engine
    gc.collect()
    sample = _select([r for r in reqs if r["generated"]], tr, seed)
    prog = gaps(ref, cfg, w, sample, tr["max_seq"], tr)
    ctrl = gaps(ref, cfg, w, sample, tr["max_seq"], tr, precision="fp8", seed=seed)
    out = {"program": {k + "_gap": _summary(v) for k, v in prog.items()},
           "control": {k + "_gap": _summary(v) for k, v in ctrl.items()},
           "router_flips": flips_at_wide_gaps(ref, cfg, w, sample, tr["max_seq"], tr["check"]["greedy_gap_p90"]),
           "requests": len(sample), "served_tokens": sum(len(r["generated"]) for r in sample),
           "not_completed": sum(r["status"] not in (None, "completed") for r in reqs)}
    del w
    gc.collect()
    return out
