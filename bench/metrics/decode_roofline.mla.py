"""decode_roofline.mla: the decode calls' share of the HBM roofline in a
latent-attention serving cell (device trace).

The steps whose ``bench.step`` spans lie in the traced window: the least
bytes of their decode calls (``work.least_bytes`` of the step's counts:
calls, experts reached, latent positions attended) at the peak bandwidth,
over the device-busy time of the ``jit_decode_step`` operations inside
those spans.  A cell without the counts (a program that lacks them, or a
configuration whose work file has no ``least_bytes``) reads nothing."""

from harness import stats

PROGRAM = "jit_decode_step/"
COUNTS = ("serving.decode_calls", "moe.experts_reached", "mla.latent_positions")


def _overlap(union, spans):
    """Seconds of the sorted disjoint ``union`` inside the sorted disjoint ``spans``."""
    total, i = 0.0, 0
    for s, e in spans:
        while i < len(union) and union[i][1] <= s:
            i += 1
        j = i
        while j < len(union) and union[j][0] < e:
            total += min(e, union[j][1]) - max(s, union[j][0])
            j += 1
    return total


def read(rec):
    if rec.kind != "serving" or rec.trace is None or not hasattr(rec.work, "least_bytes"):
        return None
    tr = rec.trace
    lo, hi = rec.layer_window()
    steps = [s for s in rec.steps if s["start"] >= lo and s["end"] <= hi]
    if not steps or not tr.devices or any("counts" not in s for s in steps):
        return None
    least = rec.work.least_bytes(rec.cell["config"], *(sum(s["counts"][k] for s in steps) for k in COUNTS))
    tlo, thi = tr.window
    spans = sorted((s, e) for s, e in tr.span_intervals("bench.step") if s >= tlo and e <= thi)
    ops = [(s, e) for d, name, s, e in tr.ops if d == tr.devices[0] and name.startswith(PROGRAM)]
    busy = _overlap(stats.union(ops, tlo, thi), spans)
    if not least or busy <= 0:
        return None
    return 100.0 * least / rec.peaks["hbm_bytes_per_s"] / busy
