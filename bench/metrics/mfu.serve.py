"""mfu.serve: the model FLOPs of every position processed (prompt and output)
in the steps of the traced window, per second, over the chip's bf16 peak.
FLOPs per position from the configuration's work function; host clock."""


def read(rec):
    if rec.kind != "serving":
        return None
    lo, hi = rec.layer_window()
    cfg = rec.cell["config"]
    flops = sum(rec.work.flops_per_token(cfg, p + 1)
                for s in rec.steps if s["start"] >= lo and s["end"] <= hi for p in s["positions"])
    return 100.0 * flops / (hi - lo) / rec.peaks["bf16_flops_per_s"] if flops else None
