"""device_idle_share.serve: share of the traced window of a serving cell with
no operation on the device: the engine's host loop, per-slot sampling round
trips and the harness's client bookkeeping."""

from harness.readers import idle_share


def read(rec):
    return idle_share(rec, "serving")
