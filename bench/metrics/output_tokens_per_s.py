"""output_tokens_per_s: output tokens delivered by the steps of the window over
the window (host clock)."""


def read(rec):
    if rec.kind != "serving":
        return None
    lo, hi = rec.window
    return sum(s["tokens"] for s in rec.steps if s["start"] >= lo and s["end"] <= hi) / rec.window_s
