"""Arithmetic that several metric readers share."""

from __future__ import annotations

from typing import Optional


def op_roofline(rec, op: str) -> Optional[float]:
    """Share (%) of the HBM roofline that the awaited calls of ``op`` in the
    traced window reach: the op's least bytes (``work.least_bytes``) at the
    peak bandwidth, over the device-busy time inside those calls' spans."""
    if rec.kind != "library" or rec.cell["traffic"]["op"] != op or rec.trace is None:
        return None
    tr = rec.trace
    lo, hi = tr.window
    spans = [(s, e) for s, e in tr.span_intervals("bench.call") if s >= lo and e <= hi]
    busy = sum(tr.busy_s(s, e) for s, e in spans)
    if not spans or busy <= 0:
        return None
    rows = rec.calls[0][2]
    least = len(spans) * rec.work.least_bytes(rec.cell["config"], op, rows) / rec.peaks["hbm_bytes_per_s"]
    return 100.0 * least / busy


def idle_share(rec, kind: str) -> Optional[float]:
    """Share (%) of the traced window in which no operation ran on the device."""
    if rec.kind != kind or rec.trace is None:
        return None
    return rec.trace.idle_share()
