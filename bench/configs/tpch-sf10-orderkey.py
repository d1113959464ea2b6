"""TPC-H order keys at the configuration's scale factor, generated on the
device from the seed.

``tables(cfg, seed, variant)`` returns, for each table, its order-key
column and int32 row ids in dbgen's order (clustered by key): ``orders``
with the spec's sparse keys and ``lineitem`` with 1-7 lines per order,
exactly ``lineitem_rows`` rows.  Each ``variant`` draws its own line
counts.  The plain reference of the operations lives with each operation
(``bench/ops/<op>.py``); this file makes the data.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5))
def _tables(n_orders, n_lines, used, stride, lo, hi, key):
    i = jnp.arange(n_orders, dtype=jnp.int32)
    okeys = (i // used) * stride + i % used + 1
    counts = jax.random.randint(key, (n_orders,), lo, hi + 1, dtype=jnp.int32)
    short = n_lines - jnp.sum(counts)  # > 0: add lines; < 0: remove lines
    room = jnp.where(short > 0, hi - counts, counts - lo)
    later = jnp.cumsum(room[::-1])[::-1] - room  # room in the orders after each
    adjust = jnp.clip(jnp.abs(short) - later, 0, room)  # taken from the last orders first
    counts = counts + jnp.sign(short).astype(jnp.int32) * adjust
    ends = jnp.cumsum(counts)
    order_of_line = jnp.searchsorted(ends, jnp.arange(n_lines, dtype=jnp.int32), side="right")
    lkeys = okeys[order_of_line]
    return {
        "orders": (okeys, i),
        "lineitem": (lkeys, jnp.arange(n_lines, dtype=jnp.int32)),
        "counts": counts,
    }


def tables(cfg: dict, key) -> dict:
    """``{"orders": (keys, row_ids), "lineitem": (keys, row_ids), "counts"}``."""
    return _tables(
        cfg["orders_rows"], cfg["lineitem_rows"], cfg["orderkey_used"], cfg["orderkey_stride"],
        cfg["lines_per_order_min"], cfg["lines_per_order_max"], key,
    )
