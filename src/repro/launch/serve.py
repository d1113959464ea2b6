"""Batched serving entry point.

Toy widths (``.reduced()``, the default) for CPU examples:

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --requests 6 --batch 2 --max-new 8

Published widths with the depth cut to ``--layers`` (random weights from
``--seed``), e.g. phi3.5-moe on one chip:

    PYTHONPATH=src python -m repro.launch.serve --arch phi3.5-moe \
        --widths published --layers 2 --requests 4 --batch 4 \
        --prompt-len 128 --max-new 16 --max-seq 160

Exits non-zero unless every request completed (``ServingReport.ok()``).
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.models import init_params
from repro.serving.engine import Request, ServingEngine, ServingReport
from repro.utils.compile_cache import enable_compile_cache


def main(argv=None) -> ServingReport:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--widths", choices=("reduced", "published"), default="reduced")
    ap.add_argument("--layers", type=int, default=0, help="cut depth to this many layers (0 = keep)")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--prompt-len", type=int, default=0, help="0 = random lengths in [4, 12)")
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.widths == "reduced":
        cfg = cfg.reduced()
    if args.layers:
        cfg = dataclasses.replace(cfg, num_layers=args.layers)
    params = jax.jit(init_params, static_argnums=0)(cfg, jax.random.key(args.seed))
    engine = ServingEngine(cfg, params, batch=args.batch, max_seq=args.max_seq, seed=args.seed)

    rng = np.random.default_rng(args.seed)
    t0 = time.time()
    for uid in range(args.requests):
        plen = args.prompt_len or int(rng.integers(4, 12))
        prompt = rng.integers(1, cfg.vocab_size, size=plen).astype(np.int32)
        engine.submit(Request(uid=uid, prompt=prompt, max_new_tokens=args.max_new,
                              temperature=args.temperature))
    report = engine.run_until_done()
    dt = time.time() - t0
    total_tokens = sum(len(r.generated) for r in engine.done.values())
    print(f"served {len(engine.done)} requests ({cfg.name}, {cfg.num_layers} layers), "
          f"{total_tokens} tokens in {dt:.2f}s; statuses {report.statuses}")
    for uid in sorted(engine.done):
        print(f"  req {uid}: {engine.done[uid].generated}")
    if not report.ok():
        raise SystemExit(f"serving degraded: {report.statuses} {report.reasons}")
    return report


if __name__ == "__main__":
    main()
