"""Least HBM traffic of the key-value operations on TPC-H order keys.

Whatever implements it, a key-value sort or merge must read each key and
row id once and write each once: with int32 keys and row ids that is 16
bytes per output row.
"""

_BYTES = {"int32": 4, "int64": 8, "float32": 4}


def least_bytes(cfg: dict, op: str, rows_out: int) -> int:
    if op not in ("sort_kv", "merge_kv"):
        raise KeyError(f"no work function for {op}")
    row = _BYTES[cfg["key_dtype"]] + _BYTES[cfg["payload_dtype"]]
    return 2 * row * rows_out
