"""setup_s: process start to the start of the measured window (host clock):
loading, data or weights, compilation or the cache, warm-up."""


def read(rec):
    return rec.setup_s
