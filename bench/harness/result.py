"""The last line of a run: metrics read by name, the device, the checks."""

from __future__ import annotations

import json
import sys
from typing import Dict

from . import device, registry


def metrics(cell: Dict, rec, traced: bool) -> Dict[str, Dict]:
    """Each metric the cell reports in this kind of run, by its reader.

    End-to-end metrics (``--trace 0``) must all be read; a per-layer reader
    (``--trace 1``) that finds nothing to read is left out of the line."""
    out = {}
    for m in cell["per_layer"] if traced else cell["end_to_end"]:
        value = registry.reader(m["name"]).read(rec)
        if value is None:
            if traced:
                continue
            device.fail(f"end-to-end metric {m['name']} read nothing in cell {cell['name']}")
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def line(cell: Dict, rec, devices, traced: bool) -> Dict:
    """The result object; ``checks`` comes last, each number beside its limit."""
    checks = rec.checks
    dev = dict(device.describe(devices), memory_peak_bytes=rec.memory_peak_bytes)
    out = {
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": rec.attempted,
        "failed": rec.failed,
        "metrics": metrics(cell, rec, traced),
        "device": dev,
    }
    if traced:
        dev["busy_s"] = rec.trace.busy_s()
        dev["window_s"] = rec.trace.window_s
        out["breakdown"] = rec.trace.breakdown()
    out["checks"] = checks
    return out


def emit(out: Dict, devices) -> None:
    tag = device.tag(devices)
    for name, c in out["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"{tag} check {name} {c['value']!r} limit {c['limit']!r} {ok}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
