"""``ops.sort_kv`` over one table's shuffled keys with their row ids.

Traffic keys: ``table`` (which table's keys and row ids).  The operands of
each input are a seeded shuffle of that table.  The reference is a plain
stable argsort; the control breaks stability (equal keys in reverse input
order), the guarantee an unstable sort would drop.
"""

import jax
import jax.numpy as jnp


def operands(tables: dict, traffic: dict, key) -> tuple:
    keys, ids = tables[traffic["table"]]
    perm = jax.random.permutation(key, keys.shape[0])
    return keys[perm], ids[perm]


def call(ops, operands: tuple):
    return ops.sort_kv(*operands)


def n_out(operands: tuple) -> int:
    return int(operands[0].shape[0])


@jax.jit
def reference(keys, values):
    perm = jnp.argsort(keys, stable=True)
    return keys[perm], values[perm]


@jax.jit
def control(keys, values):
    n = keys.shape[0]
    perm = n - 1 - jnp.argsort(keys[::-1], stable=True)  # equal keys: last input first
    return keys[perm], values[perm]
