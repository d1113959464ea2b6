"""``ops.merge_kv`` of two tables' sorted keys with tagged row ids.

Traffic keys: ``a`` and ``b`` (the tables, each sorted by key as stored)
and ``tag_bit`` (set in the second table's row ids, so each output row
names its table).  The reference is a plain stable argsort of the
concatenation, which is the A-priority stable merge; the control puts the
second table's rows first among equal keys.
"""

import jax
import jax.numpy as jnp


def operands(tables: dict, traffic: dict, key) -> tuple:
    ak, av = tables[traffic["a"]]
    bk, bv = tables[traffic["b"]]
    return ak, av, bk, bv | jnp.int32(1 << traffic["tag_bit"])


def call(ops, operands: tuple):
    return ops.merge_kv(*operands)


def n_out(operands: tuple) -> int:
    return int(operands[0].shape[0] + operands[2].shape[0])


@jax.jit
def reference(ak, av, bk, bv):
    keys, values = jnp.concatenate([ak, bk]), jnp.concatenate([av, bv])
    perm = jnp.argsort(keys, stable=True)
    return keys[perm], values[perm]


@jax.jit
def control(ak, av, bk, bv):
    keys, values = jnp.concatenate([bk, ak]), jnp.concatenate([bv, av])
    perm = jnp.argsort(keys, stable=True)
    return keys[perm], values[perm]
