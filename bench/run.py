#!/usr/bin/env python3
"""Run one benchmark cell once on the TPU and print one JSON result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration and a
traffic mix; both, the runner of the traffic's kind and every metric's
reader are found by name under ``bench/`` (see ``harness/registry.py``).
Inputs and weights come from ``--seed``.  Set-up (data, weights, warming
every shape the traffic uses) ends where the measured window of
``--seconds`` starts.  After the window the outputs the window produced are
compared with the configuration's plain reference; ``correct`` and each
number compared, beside its limit, end the output.  ``--trace 1`` also
profiles the window and reports the per-layer metrics instead of the
end-to-end ones.  Without a TPU the run ends with exit code 1 and no
result; there is no CPU fallback.
"""

import time

T0 = time.monotonic()  # set-up is timed from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import device, registry, result  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = registry.cell(args.workload, registry.benchmark())
    device.import_program()
    devices = device.require_tpu(cell["chips"])
    device.enable_compile_cache()
    kind = registry.kind(cell["traffic"]["kind"])
    rec = kind.run(cell, seed=args.seed, seconds=args.seconds, traced=bool(args.trace),
                   devices=devices, t0=T0, log=lambda msg: print(f"{device.tag(devices)} {msg}",
                                                               file=sys.stderr, flush=True))
    result.emit(result.line(cell, rec, devices, bool(args.trace)), devices)


if __name__ == "__main__":
    main()
