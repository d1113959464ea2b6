"""sort_kv_roofline: the sort_kv calls' share of the HBM roofline (device trace)."""

from harness.readers import op_roofline


def read(rec):
    return op_roofline(rec, "sort_kv")
