"""Reusable gradient-check harness for the kernel-path VJP tests.

Two complementary checks:

* :func:`fd_check` — central finite differences in **float64** against
  the VJP of the same (pure-JAX) function.  Validates the *math* of a
  reference route; run it on oracle implementations, which execute fine
  under ``jax.enable_x64``.
* :func:`vjp_compare` — VJP-vs-VJP between the kernel route and the
  oracle route with an identical random cotangent.  The permutation
  VJPs are exact inverse gathers, so for them the comparison is
  **bit-identical** (``bit=True``); recompute-based backwards (SSM)
  compare under atol/rtol.

Both operate on functions of positional array args and tolerate pytree
outputs; integer/float0 gradient leaves are skipped in comparisons.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp


def _is_float_leaf(x) -> bool:
    try:
        return jnp.issubdtype(jnp.asarray(x).dtype, jnp.inexact)
    except (TypeError, ValueError):  # float0 zeros etc.
        return False


def random_cotangent(out, seed: int = 0):
    """A fixed pseudo-random cotangent matching ``out``'s pytree/shapes.

    Works on concrete outputs and ``jax.eval_shape`` structs; integer
    output leaves get the ``float0`` cotangent JAX requires.
    """
    leaves, treedef = jax.tree_util.tree_flatten(out)
    cts = []
    for i, leaf in enumerate(leaves):
        shape, dtype = leaf.shape, jnp.dtype(leaf.dtype)
        if jnp.issubdtype(dtype, jnp.inexact):
            rng = np.random.default_rng(seed + i)
            cts.append(jnp.asarray(rng.standard_normal(shape), dtype))
        else:
            cts.append(np.zeros(shape, jax.dtypes.float0))
    return jax.tree_util.tree_unflatten(treedef, cts)


def fd_check(f, args, *, eps: float = 1e-5, rtol: float = 1e-6, atol: float = 1e-8,
             seed: int = 0):
    """Central-difference (f64) vs VJP gradients of ``f`` at ``args``.

    ``f`` maps positional arrays to an array/pytree; the check contracts
    the output with a fixed random cotangent ``u`` so one scalar
    functional ``g(x) = <u, f(x)>`` is differentiated both ways.  All
    float args are promoted to float64 (requires ``f`` be pure JAX —
    oracle routes, not Pallas calls).
    """
    with jax.enable_x64(True):
        args64 = [
            jnp.asarray(np.asarray(a, np.float64)) if _is_float_leaf(a) else jnp.asarray(a)
            for a in args
        ]
        u = random_cotangent(jax.eval_shape(f, *args64), seed)

        @jax.jit  # FD evaluates 2x per input element: compile once
        def scalar(*a):
            out = f(*a)
            return sum(
                jnp.vdot(jnp.asarray(ct, jnp.float64), jnp.asarray(o).astype(jnp.float64))
                for o, ct in zip(jax.tree.leaves(out), jax.tree.leaves(u))
                if _is_float_leaf(o)
            )

        grads = jax.grad(
            scalar, argnums=tuple(i for i, a in enumerate(args64) if _is_float_leaf(a))
        )(*args64)
        gi = iter(grads)
        for i, a in enumerate(args64):
            if not _is_float_leaf(a):
                continue
            g_ad = np.asarray(next(gi))
            g_fd = np.zeros_like(g_ad)
            flat = np.asarray(a, np.float64).ravel()
            for j in range(flat.size):
                hi, lo = flat.copy(), flat.copy()
                hi[j] += eps
                lo[j] -= eps
                fp = float(scalar(*args64[:i], jnp.asarray(hi.reshape(a.shape)), *args64[i + 1:]))
                fm = float(scalar(*args64[:i], jnp.asarray(lo.reshape(a.shape)), *args64[i + 1:]))
                g_fd.ravel()[j] = (fp - fm) / (2 * eps)
            np.testing.assert_allclose(
                g_ad, g_fd, rtol=rtol, atol=atol,
                err_msg=f"FD-vs-VJP mismatch on arg {i}",
            )


def vjp_grads(f, args, ct=None, seed: int = 0):
    """(primal_out, grads) of ``f`` at ``args`` under cotangent ``ct``."""
    out, pullback = jax.vjp(f, *args)
    if ct is None:
        ct = random_cotangent(out, seed)
    return out, pullback(ct)


def vjp_compare(f_kernel, f_oracle, args, *, bit: bool = True,
                rtol: float = 0.0, atol: float = 0.0, seed: int = 0):
    """Assert kernel-route and oracle-route primals AND grads agree.

    ``bit=True`` (permutation VJPs) demands exact equality; otherwise
    atol/rtol bounds apply (recompute backwards).  Returns the kernel
    grads for extra caller-side assertions.
    """
    out_k, pullback_k = jax.vjp(f_kernel, *args)
    out_o, pullback_o = jax.vjp(f_oracle, *args)
    ct = random_cotangent(out_k, seed)
    for lk, lo in zip(jax.tree.leaves(out_k), jax.tree.leaves(out_o)):
        if bit:
            np.testing.assert_array_equal(np.asarray(lk), np.asarray(lo),
                                          err_msg="primal mismatch kernel vs oracle")
        else:
            np.testing.assert_allclose(
                np.asarray(lk, np.float32), np.asarray(lo, np.float32),
                rtol=rtol, atol=atol, err_msg="primal mismatch kernel vs oracle",
            )
    g_k, g_o = pullback_k(ct), pullback_o(ct)
    for i, (lk, lo) in enumerate(zip(jax.tree.leaves(g_k), jax.tree.leaves(g_o))):
        if not (_is_float_leaf(lk) and _is_float_leaf(lo)):
            continue
        if bit:
            np.testing.assert_array_equal(
                np.asarray(lk), np.asarray(lo),
                err_msg=f"grad leaf {i} not bit-identical kernel vs oracle",
            )
        else:
            np.testing.assert_allclose(
                np.asarray(lk, np.float32), np.asarray(lo, np.float32),
                rtol=rtol, atol=atol, err_msg=f"grad leaf {i} kernel vs oracle",
            )
    return g_k
