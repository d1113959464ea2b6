"""device_idle_share.keys: share of the traced window of a library cell with
no operation on the device: host dispatch, the guard and Python between calls."""

from harness.readers import idle_share


def read(rec):
    return idle_share(rec, "library")
