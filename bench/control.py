#!/usr/bin/env python3
"""Read the numbers that decide ``correct`` for the program and for its
control, on several seeds in one process (not part of a benchmark run).

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--seconds 20] [--out FILE]

For each seed it prints one JSON line: the program's numbers (a window of
``--seconds`` at the cell's own load) and the control's (the reference in
the next precision below the configuration's, or with the guarantee the
configuration states broken) on the same inputs.  The limits in
``bench/traffic/<mix>.json`` are set between the two.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import device, registry  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    cell = registry.cell(args.workload, registry.benchmark())
    device.import_program()
    devices = device.require_tpu(cell["chips"])
    device.enable_compile_cache()
    kind = registry.kind(cell["traffic"]["kind"])
    tag = device.tag(devices)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.monotonic()
        res = kind.control(cell, seed, args.seconds, devices,
                           lambda msg: print(f"{tag} {msg}", file=sys.stderr, flush=True))
        line = json.dumps({"workload": args.workload, "seed": seed, "device": device.describe(devices),
                           "seconds": time.monotonic() - t, **res})
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")


if __name__ == "__main__":
    main()
