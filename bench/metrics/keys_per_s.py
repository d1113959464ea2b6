"""keys_per_s: output rows of all calls in the window over the window
(host clock; the window ends when its last call has returned)."""


def read(rec):
    if rec.kind != "library" or not rec.calls:
        return None
    return sum(n for _, _, n in rec.calls) / rec.window_s
