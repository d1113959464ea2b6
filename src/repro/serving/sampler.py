"""Token samplers built on the merge-path top-k (paper integration #2).

``sample_batch`` is what the serving engine calls: every slot of a tick,
greedy and top-k rows alike, in one jitted device call on the logits the
decode step left on the device (candidates from ``jax.lax.top_k``, whose
tie order is the merge-path top-k's).  The functions below keep their
backends as library API and as test oracles.

``topk_sample`` / ``topp_sample`` use the *batched* merge-path top-k
(``repro.core.topk_batched``): all batch rows ride one fused kv-sort —
every diagonal binary search of every row's merge rounds shares a single
vectorized Algorithm 2 pass — instead of a vmapped per-row sort.  On a
vocab-sharded mesh, ``backend="distributed"`` routes the candidate step
through ``repro.core.distributed_topk_batched``: per-shard batched top-k,
then a butterfly (or gather) merge-path combine that replicates the
global ``(B, k)`` candidates — ``k * log2(P)`` candidates moved per
device instead of the whole vocab (see core/distributed.py).

**Masked vocab** (``vocab_lens``): serving vocabularies are padded to
lane-friendly widths, so only a prefix of every logit row is real.
Instead of faking it by ``-inf``-filling the tail (which collides with
genuinely ``-inf`` logits — banned tokens — once keys are flipped for
the descending sort), the samplers route through
``repro.core.topk_batched_ragged``: the valid length bounds the sort
itself, masked slots return index ``-1``/probability 0, and — when
``vocab_lens[r] >= k`` so both draws see the same candidate count — a
padded row is sampled *bit-identically* to its unpadded truncation.
(With fewer valid tokens than ``k`` the candidate tensor is shaped
differently, so the draw consumes the PRNG differently: the sampled
*distribution* still matches, the exact token for a given key may not.)

Contract for degenerate rows: a row with ``vocab_lens[r] == 0`` has no
valid token to sample, so the samplers return ``-1`` for it — the same
out-of-band marker the ragged top-k uses.  Callers must treat negative
token ids as "no token" (never feed them to a gather, where JAX's
negative indexing would silently wrap to the last vocab entry).  Rows
with ``vocab_lens[r] >= 1`` always return a valid in-prefix id.

**Backend** (``backend="pallas"``): the candidate sort runs on the
bitonic tile engine (``repro.kernels.ops.topk_batched{,_ragged}``)
instead of the fused pure-JAX path — same stable contract and the same
ragged semantics, with ``(tile, leaf)`` either passed explicitly or
resolved from the autotune table (``repro.kernels.tune``).  Production
vocab widths (32K-256K) sit squarely in the regime where the kernel's
flat sort rounds win.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import topk_batched, topk_batched_ragged
from repro.core.merge_path import min_sentinel


def greedy(logits: jax.Array) -> jax.Array:
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def sample_batch(
    logits: jax.Array,  # (B, V)
    key: jax.Array,
    temperature: jax.Array,  # (B,) float32; a row at <= 0 is greedy
    k: int,
    row_k: Optional[jax.Array] = None,  # (B,) int32: a row's own top-k, 1..k (default k)
) -> Tuple[jax.Array, jax.Array]:
    """Every row's next token in one call: ``(tokens (B,) int32, next_key)``.

    A row with ``temperature <= 0`` takes :func:`greedy`'s first maximum.
    Any other row draws from its top ``row_k`` candidates at its own
    temperature: the candidates are ``jax.lax.top_k``'s, whose order among
    equal logits (smallest index first) is :func:`repro.core.topk_batched`'s,
    so a row's candidate set is the merge-path top-k's.  One key is split
    per call and drives every row's draw.  When no row samples, the top-k
    branch is skipped inside the same program (``lax.cond``), so an
    all-greedy batch pays for an argmax alone.
    """
    key, sub = jax.random.split(key)
    best = greedy(logits)
    drawn = temperature > 0

    def draw(_):
        vals, idx = jax.lax.top_k(logits, k)
        scaled = vals.astype(jnp.float32) / jnp.where(drawn, temperature, 1.0)[:, None]
        if row_k is not None:
            scaled = jnp.where(jnp.arange(k) < row_k[:, None], scaled, min_sentinel(scaled.dtype))
        choice = jax.random.categorical(sub, scaled)
        tok = jnp.take_along_axis(idx, choice[:, None], axis=-1)[:, 0].astype(jnp.int32)
        return jnp.where(drawn, tok, best)

    return jax.lax.cond(jnp.any(drawn), draw, lambda _: best, None), key


def _topk_candidates(
    logits: jax.Array,
    k: int,
    vocab_lens,
    backend: str = "core",
    tile: Optional[int] = None,
    leaf: Optional[int] = None,
    mesh=None,
    axis: str = "x",
) -> Tuple[jax.Array, jax.Array]:
    """Per-row top-k candidates, optionally over a ragged valid-vocab prefix."""
    if backend == "distributed":
        from repro.core import distributed_topk_batched  # deferred: mesh layer optional

        if vocab_lens is not None:
            raise ValueError(
                "vocab_lens is not supported with backend='distributed' — pad "
                "the sharded vocab with -inf ban logits instead"
            )
        return distributed_topk_batched(logits, k, mesh=mesh, axis=axis)
    if backend == "pallas":
        from repro.kernels import ops as kops  # deferred: kernels layer optional here

        if vocab_lens is None:
            return kops.topk_batched(logits, k, tile=tile, leaf=leaf)
        return kops.topk_batched_ragged(logits, k, vocab_lens, tile=tile, leaf=leaf)
    if vocab_lens is None:
        return topk_batched(logits, k)
    return topk_batched_ragged(logits, k, vocab_lens)


def topk_sample(
    logits: jax.Array,  # (B, V)
    key: jax.Array,
    k: int = 40,
    temperature: float = 1.0,
    vocab_lens=None,  # optional (B,) or scalar: valid vocab prefix per row
    backend: str = "core",  # "core" | "pallas" | "distributed" (vocab-sharded)
    tile: Optional[int] = None,  # kernel tile override (None = autotuned)
    leaf: Optional[int] = None,  # kernel leaf override (None = autotuned)
    mesh=None,  # backend="distributed": mesh whose `axis` shards the vocab
    axis: str = "x",
) -> jax.Array:
    vals, idx = _topk_candidates(logits, k, vocab_lens, backend, tile, leaf, mesh, axis)
    probs = jax.nn.softmax(vals.astype(jnp.float32) / jnp.maximum(temperature, 1e-6), axis=-1)
    loglik = jnp.log(jnp.maximum(probs, 1e-30))
    # masked-vocab slots are -inf, not floor-probability: they can never be
    # drawn while any valid candidate exists (a lens==0 row returns -1)
    loglik = jnp.where(idx >= 0, loglik, min_sentinel(loglik.dtype))
    choice = jax.random.categorical(key, loglik)
    return jnp.take_along_axis(idx, choice[:, None], axis=-1)[:, 0].astype(jnp.int32)


def topp_sample(
    logits: jax.Array,
    key: jax.Array,
    p: float = 0.9,
    k_max: int = 128,
    temperature: float = 1.0,
    vocab_lens=None,
    backend: str = "core",  # "core" | "pallas" | "distributed" (vocab-sharded)
    tile: Optional[int] = None,
    leaf: Optional[int] = None,
    mesh=None,  # backend="distributed": mesh whose `axis` shards the vocab
    axis: str = "x",
) -> jax.Array:
    """Nucleus sampling over the merge-path-sorted top-k_max candidates."""
    vals, idx = _topk_candidates(logits, k_max, vocab_lens, backend, tile, leaf, mesh, axis)
    probs = jax.nn.softmax(vals.astype(jnp.float32) / jnp.maximum(temperature, 1e-6), axis=-1)
    probs = jnp.where(idx >= 0, probs, 0.0)
    cum = jnp.cumsum(probs, axis=-1)
    keep = cum - probs < p  # always keeps the first candidate
    probs = jnp.where(keep, probs, 0.0)
    loglik = jnp.log(jnp.maximum(probs, 1e-30))
    loglik = jnp.where(idx >= 0, loglik, min_sentinel(loglik.dtype))  # see topk_sample
    choice = jax.random.categorical(key, loglik)
    return jnp.take_along_axis(idx, choice[:, None], axis=-1)[:, 0].astype(jnp.int32)
