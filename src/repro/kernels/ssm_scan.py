"""Fused selective-scan Pallas kernel — the TPU answer to the falcon-mamba
memory wall found in §Perf.

The XLA associative-scan path materializes (B, S, d_inner, state) f32
decay/update/state tensors (log2(S) levels of them): ~50 TB accessed per
train step per device for falcon-mamba train_4k.  The CUDA mamba kernel
avoids this by keeping the recurrence state in SRAM; this kernel is the
VMEM version:

* grid = (batch, d_inner tiles, seq chunks), sequential over seq (TPU
  grid order guarantees the scratch carries across the seq dimension);
* the (d_tile, state) hidden state lives in a VMEM scratch buffer and is
  NEVER written to HBM (forward only checkpoints it once per seq chunk);
* HBM traffic = read dt/x (B,S,D), B/C (B,S,st), write y (B,S,D):
  ~3*B*S*D + 2*B*S*st elements total vs >= 2*log2(S)*B*S*D*st for the
  associative scan — a ~100x reduction at D=8192, st=16, S=4096.

**Differentiable** (``jax.custom_vjp``): the forward kernel additionally
writes the carried state at every seq-chunk *start* (the checkpoint
tensor ``(B, S/chunk, D, st)`` — a factor ``chunk`` smaller than the
activations the XLA path would save), and the backward is a second Pallas
kernel on the same ``(batch, d_tile, seq-chunk)`` grid running the seq
chunks in **reversed** order: each chunk recomputes its per-step states
from the checkpoint (one extra forward pass — the same VMEM-residency
argument as the forward), then runs the reverse linear-recurrence
accumulation ``g_{t-1} = g_t * decay_t`` entirely in VMEM, emitting
``d_dt / d_x / d_B / d_C / d_A`` in one pass.  Validated in interpret
mode against ``jax.grad`` of :func:`ssm_scan_ref` (``tests/
test_ssm_kernel.py``).

Cost model of the backward: HBM reads = the forward's inputs + dy +
checkpoints, writes = the five gradients; compute = 2x the forward
(recompute + reverse pass).  VMEM high-water = ``(chunk+1) * d_tile *
st`` f32 for the recomputed states — pick ``(chunk, d_tile)`` so that
fits (see docs/architecture.md §Training path).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis.registry import kernel_contract

from .merge_path import _interp


def ssm_scan_ref(dt, x, bmat, cmat, a):
    """Oracle: direct linear recurrence in fp32.

    dt, x: (B, S, D); bmat, cmat: (B, S, st); a: (D, st).
    Returns y (B, S, D), h_final (B, D, st).
    """
    bsz, s, d = x.shape
    st = bmat.shape[-1]
    decay = jnp.exp(dt[..., None].astype(jnp.float32) * a[None, None])
    upd = (dt * x)[..., None].astype(jnp.float32) * bmat[:, :, None, :].astype(jnp.float32)

    def step(h, inputs):
        dec, up, c = inputs
        h = dec * h + up
        y = jnp.sum(h * c[:, None, :], axis=-1)
        return h, y

    h0 = jnp.zeros((bsz, d, st), jnp.float32)
    h_final, ys = jax.lax.scan(
        step, h0,
        (decay.transpose(1, 0, 2, 3), upd.transpose(1, 0, 2, 3),
         cmat.astype(jnp.float32).transpose(1, 0, 2)),
    )
    return ys.transpose(1, 0, 2).astype(x.dtype), h_final


def _kernel(dt_ref, x_ref, b_ref, c_ref, a_ref, y_ref, hlast_ref, *rest,
            chunk: int, checkpoints: bool):
    """Forward over one (batch row, d-tile, seq chunk) cell.

    Operands are f32.  The state is kept transposed, ``(st, d_tile)``:
    ``d_tile`` on the lanes, so a state slab holds no lane padding (a
    ``(d_tile, st=16)`` slab would be 8x its size in VMEM).
    """
    if checkpoints:
        hstart_ref, h_scr = rest
    else:
        (h_scr,) = rest
    s_idx = pl.program_id(2)
    n_s = pl.num_programs(2)

    @pl.when(s_idx == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    if checkpoints:
        # state at the START of this chunk — what the backward recomputes from
        hstart_ref[0, 0] = h_scr[...]

    a = a_ref[...]  # (st, d_tile)

    def body(i, h):
        dt_i = dt_ref[0, pl.ds(i, 1), :]  # (1, d_tile)
        x_i = x_ref[0, pl.ds(i, 1), :]
        b_i = b_ref[0, i, :][:, None]  # (st, 1)
        c_i = c_ref[0, i, :][:, None]
        h = jnp.exp(dt_i * a) * h + b_i * (dt_i * x_i)
        y_ref[0, pl.ds(i, 1), :] = jnp.sum(h * c_i, axis=0, keepdims=True)
        return h

    h = jax.lax.fori_loop(0, chunk, body, h_scr[...])
    h_scr[...] = h

    @pl.when(s_idx == n_s - 1)
    def _final():
        hlast_ref[0] = h


def _fwd_call(dt, x, bmat, cmat, a_t, chunk: int, d_tile: int, interpret: bool,
              checkpoints: bool):
    """Forward launch; ``a_t`` is ``A`` transposed ``(st, D)``, and the
    states it returns are transposed too: ``h_final (B, st, D)``,
    checkpoints ``(B, S/chunk, st, D)``."""
    bsz, s, d = x.shape
    st = bmat.shape[-1]
    assert s % chunk == 0, (s, chunk)
    assert d % d_tile == 0, (d, d_tile)
    n_s = s // chunk
    grid = (bsz, d // d_tile, n_s)

    out_specs = [
        pl.BlockSpec((1, chunk, d_tile), lambda b, dd, ss: (b, ss, dd)),  # y
        pl.BlockSpec((1, st, d_tile), lambda b, dd, ss: (b, 0, dd)),  # h_final
    ]
    out_shape = [
        jax.ShapeDtypeStruct((bsz, s, d), jnp.float32),
        jax.ShapeDtypeStruct((bsz, st, d), jnp.float32),
    ]
    if checkpoints:
        out_specs.append(
            pl.BlockSpec((1, 1, st, d_tile), lambda b, dd, ss: (b, ss, 0, dd))
        )
        out_shape.append(jax.ShapeDtypeStruct((bsz, n_s, st, d), jnp.float32))

    return pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, checkpoints=checkpoints),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, d_tile), lambda b, dd, ss: (b, ss, dd)),  # dt
            pl.BlockSpec((1, chunk, d_tile), lambda b, dd, ss: (b, ss, dd)),  # x
            pl.BlockSpec((1, chunk, st), lambda b, dd, ss: (b, ss, 0)),  # B
            pl.BlockSpec((1, chunk, st), lambda b, dd, ss: (b, ss, 0)),  # C
            pl.BlockSpec((st, d_tile), lambda b, dd, ss: (0, dd)),  # A^T
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((st, d_tile), jnp.float32)],
        interpret=interpret,
    )(dt, x, bmat, cmat, a_t)


def _bwd_kernel(dt_ref, x_ref, b_ref, c_ref, a_ref, hstart_ref, dy_ref, dhfin_ref,
                ddt_ref, dx_ref, db_ref, dc_ref, da_ref,
                h_scr, g_scr, da_scr, *, chunk: int):
    """Reverse pass over one (batch row, d-tile, seq chunk) cell.

    Grid is ``(batch, seq chunk, d tile)`` with the seq axis REVERSED by
    the index maps (grid step ``ss`` touches chunk ``n_s - 1 - ss``) and
    the d-tile axis innermost so the dB/dC partial sums over d-tiles
    accumulate into a block that stays VMEM-resident between consecutive
    grid steps.  Per-(b, d-tile) reverse carries live in scratch slabs
    indexed by the d-tile id.  States, carries and dA are ``(st, d_tile)``
    (transposed, lane-dense) as in the forward.
    """
    b_idx = pl.program_id(0)
    s_idx = pl.program_id(1)  # 0 == LAST seq chunk (reversed index maps)
    d_idx = pl.program_id(2)
    n_b = pl.num_programs(0)
    n_s = pl.num_programs(1)

    a = a_ref[...]  # (st, d_tile)

    # 1) recompute this chunk's states from the checkpoint:
    #    h_scr[i] = state BEFORE step i (h_scr[chunk] = state after the chunk)
    def fwd_body(i, h):
        h_scr[i] = h
        dt_i = dt_ref[0, pl.ds(i, 1), :]
        x_i = x_ref[0, pl.ds(i, 1), :]
        b_i = b_ref[0, i, :][:, None]
        return jnp.exp(dt_i * a) * h + b_i * (dt_i * x_i)

    h_scr[chunk] = jax.lax.fori_loop(0, chunk, fwd_body, hstart_ref[0, 0])

    # 2) reverse accumulation; g = dL/dh_t carried right-to-left
    @pl.when(s_idx == 0)
    def _init_g():
        g_scr[d_idx] = dhfin_ref[0]

    # dB/dC: partial sums over d-tiles; the (b, chunk) output block is
    # revisited consecutively as d_idx advances, so accumulate in place
    @pl.when(d_idx == 0)
    def _db_init():
        db_ref[...] = jnp.zeros_like(db_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)

    def bwd_body(i, carry):
        g, da_acc = carry
        t = chunk - 1 - i
        dt_t = dt_ref[0, pl.ds(t, 1), :]  # (1, d_tile)
        x_t = x_ref[0, pl.ds(t, 1), :]
        dy_t = dy_ref[0, pl.ds(t, 1), :]
        b_t = b_ref[0, t, :][:, None]  # (st, 1)
        c_t = c_ref[0, t, :][:, None]
        h_prev = h_scr[t]  # (st, d_tile)
        h_t = h_scr[t + 1]
        dc_ref[0, t, :] = dc_ref[0, t, :] + jnp.sum(h_t * dy_t, axis=1)
        g = g + c_t * dy_t
        decay = jnp.exp(dt_t * a)
        gdec = g * h_prev * decay  # = dL/d(dt_t ⊗ a), chained through exp
        s_gb = jnp.sum(g * b_t, axis=0, keepdims=True)  # (1, d_tile) = dL/d(dt_t * x_t)
        ddt_ref[0, pl.ds(t, 1), :] = jnp.sum(gdec * a, axis=0, keepdims=True) + x_t * s_gb
        dx_ref[0, pl.ds(t, 1), :] = dt_t * s_gb
        db_ref[0, t, :] = db_ref[0, t, :] + jnp.sum(g * (dt_t * x_t), axis=1)
        da_acc = da_acc + dt_t * gdec
        g = g * decay
        return g, da_acc

    g, da_acc = jax.lax.fori_loop(0, chunk, bwd_body, (g_scr[d_idx], jnp.zeros_like(a)))
    g_scr[d_idx] = g

    # dA: accumulated over batch AND seq in scratch, written once at the
    # final visit of this d-tile
    first = jnp.logical_and(b_idx == 0, s_idx == 0)

    @pl.when(first)
    def _da_init():
        da_scr[d_idx] = da_acc

    @pl.when(jnp.logical_not(first))
    def _da_acc():
        da_scr[d_idx] = da_scr[d_idx] + da_acc

    @pl.when(jnp.logical_and(b_idx == n_b - 1, s_idx == n_s - 1))
    def _da_out():
        da_ref[...] = da_scr[d_idx]


def _bwd_call(dt, x, bmat, cmat, a_t, hstart, dy, dhfin_t,
              chunk: int, d_tile: int, interpret: bool):
    """Backward launch on the transposed state layout of :func:`_fwd_call`
    (``a_t (st, D)``, ``dhfin_t (B, st, D)``); returns dA as ``(st, D)``."""
    bsz, s, d = x.shape
    st = bmat.shape[-1]
    n_s = s // chunk
    n_d = d // d_tile
    grid = (bsz, n_s, n_d)
    rev = lambda ss: n_s - 1 - ss  # noqa: E731 — seq chunks in reverse

    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, d_tile), lambda b, ss, dd: (b, rev(ss), dd)),  # dt
            pl.BlockSpec((1, chunk, d_tile), lambda b, ss, dd: (b, rev(ss), dd)),  # x
            pl.BlockSpec((1, chunk, st), lambda b, ss, dd: (b, rev(ss), 0)),  # B
            pl.BlockSpec((1, chunk, st), lambda b, ss, dd: (b, rev(ss), 0)),  # C
            pl.BlockSpec((st, d_tile), lambda b, ss, dd: (0, dd)),  # A^T
            pl.BlockSpec((1, 1, st, d_tile), lambda b, ss, dd: (b, rev(ss), 0, dd)),  # hstart
            pl.BlockSpec((1, chunk, d_tile), lambda b, ss, dd: (b, rev(ss), dd)),  # dy
            pl.BlockSpec((1, st, d_tile), lambda b, ss, dd: (b, 0, dd)),  # dhfin^T
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, d_tile), lambda b, ss, dd: (b, rev(ss), dd)),  # ddt
            pl.BlockSpec((1, chunk, d_tile), lambda b, ss, dd: (b, rev(ss), dd)),  # dx
            pl.BlockSpec((1, chunk, st), lambda b, ss, dd: (b, rev(ss), 0)),  # dB
            pl.BlockSpec((1, chunk, st), lambda b, ss, dd: (b, rev(ss), 0)),  # dC
            pl.BlockSpec((st, d_tile), lambda b, ss, dd: (0, dd)),  # dA^T
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, d), f32),
            jax.ShapeDtypeStruct((bsz, s, d), f32),
            jax.ShapeDtypeStruct((bsz, s, st), f32),
            jax.ShapeDtypeStruct((bsz, s, st), f32),
            jax.ShapeDtypeStruct((st, d), f32),
        ],
        scratch_shapes=[
            pltpu.VMEM((chunk + 1, st, d_tile), f32),  # recomputed chunk states
            pltpu.VMEM((n_d, st, d_tile), f32),  # g carry, one slab per d-tile
            pltpu.VMEM((n_d, st, d_tile), f32),  # dA accumulator per d-tile
        ],
        interpret=interpret,
    )(dt, x, bmat, cmat, a_t, hstart, dy, dhfin_t)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _ssm_scan(dt, x, bmat, cmat, a, chunk, d_tile, interpret):
    y, h_final_t = _fwd_call(dt, x, bmat, cmat, a.T, chunk, d_tile, interpret,
                             checkpoints=False)
    return y, jnp.swapaxes(h_final_t, 1, 2)


def _ssm_scan_fwd(dt, x, bmat, cmat, a, chunk, d_tile, interpret):
    y, h_final_t, hstart = _fwd_call(dt, x, bmat, cmat, a.T, chunk, d_tile, interpret,
                                     checkpoints=True)
    return (y, jnp.swapaxes(h_final_t, 1, 2)), (dt, x, bmat, cmat, a, hstart)


def _ssm_scan_bwd(chunk, d_tile, interpret, res, cts):
    dt, x, bmat, cmat, a, hstart = res
    dy, dhfin = cts
    ddt, dx, db, dc, da_t = _bwd_call(
        dt, x, bmat, cmat, a.T, hstart, dy, jnp.swapaxes(dhfin, 1, 2), chunk, d_tile, interpret
    )
    return ddt, dx, db, dc, da_t.T


_ssm_scan.defvjp(_ssm_scan_fwd, _ssm_scan_bwd)


def _ssm_scan_launch(dt, x, bmat, cmat, a, chunk, d_tile, interpret):
    """Pad-to-chunk + fused-kernel dispatch (the guarded primary attempt).

    The kernels compute in f32 and take f32 operands (a row of a packed
    bf16 block cannot be addressed at a dynamic step): narrower inputs are
    widened here, ``y`` is narrowed back to ``x.dtype``, and the casts'
    VJPs return each gradient in its input's dtype.
    """
    bsz, s, d = x.shape
    out_dtype = x.dtype
    f32 = jnp.float32
    dt, x, bmat, cmat, a = (t.astype(f32) for t in (dt, x, bmat, cmat, a))
    pad = (-s) % chunk
    if pad:
        widen = lambda t: jnp.pad(t, ((0, 0), (0, pad), (0, 0)))  # noqa: E731
        dt, x, bmat, cmat = widen(dt), widen(x), widen(bmat), widen(cmat)
    y, h_final = _ssm_scan(dt, x, bmat, cmat, a, chunk, d_tile, interpret)
    if pad:
        y = y[:, :s]
    return y.astype(out_dtype), h_final


@kernel_contract(kind="scan", batched=True, differentiable=True)
def ssm_scan_pallas(
    dt: jax.Array,  # (B, S, D)
    x: jax.Array,
    bmat: jax.Array,  # (B, S, st)
    cmat: jax.Array,
    a: jax.Array,  # (D, st)
    *,
    chunk: int = 256,
    d_tile: int = 512,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Fused, differentiable scan; returns (y (B,S,D), h_final (B,D,st)).

    ``jax.grad`` through this runs the chunk-recompute backward kernel
    (see module docstring).  ``S`` need not divide ``chunk``: the tail is
    padded with identity steps (``dt = 0`` ⇒ ``decay = 1, upd = 0``), so
    ``h_final`` and the trimmed ``y`` — and their gradients — are exact.
    ``interpret=None`` follows the backend (compiled on a TPU) like every
    :mod:`repro.kernels.ops` wrapper.

    Eager calls route through guarded dispatch: preflight checks the scan
    VMEM model against the A005 budget, and a launch failure degrades to
    :func:`ssm_scan_ref` — the fp32 ``lax.scan`` twin the kernel is
    tested against (see ``docs/robustness.md``).  Traced calls (the
    training step) dispatch the kernel directly.
    """
    from repro.runtime import faults as _faults
    from repro.runtime import resilience as _res

    bsz, s, d = x.shape
    st = a.shape[-1]
    chunk = max(1, min(chunk, s))
    d_tile = max(1, min(d_tile, d))
    while d % d_tile:  # largest divisor of D at or below the requested tile
        d_tile -= 1
    itp = _interp(interpret)
    if not _res.guard_enabled() or _res.is_tracing(dt, x, bmat, cmat, a):
        return _ssm_scan_launch(dt, x, bmat, cmat, a, chunk, d_tile, itp)
    idx = _faults.next_index("ssm_scan_pallas")
    meta = {
        "n": s, "batch": bsz, "dtype": str(x.dtype), "seq": s, "d_model": d,
        "state": st, "chunk": chunk, "d_tile": d_tile,
    }
    return _res.guarded_call(
        "ssm_scan_pallas",
        [
            ("pallas-scan",
             lambda: _ssm_scan_launch(dt, x, bmat, cmat, a, chunk, d_tile, itp)),
            ("core-ref", lambda: ssm_scan_ref(dt, x, bmat, cmat, a)),
        ],
        index=idx,
        meta=meta,
    )


# primary public name (the kernel the training path differentiates through)
ssm_scan = ssm_scan_pallas


def fused_hbm_bytes(bsz: int, s: int, d: int, st: int, elem: int = 2) -> int:
    """Analytic HBM traffic of the fused kernel (for §Perf napkin math)."""
    return elem * (3 * bsz * s * d + 2 * bsz * s * st) + 4 * bsz * d * st


def bwd_hbm_bytes(bsz: int, s: int, d: int, st: int, chunk: int, elem: int = 2) -> int:
    """Analytic HBM traffic of the recompute backward: forward inputs + dy +
    checkpoints in, five gradients out."""
    fwd_in = elem * (3 * bsz * s * d + 2 * bsz * s * st)
    ckpt = 4 * bsz * (s // max(1, chunk)) * d * st
    grads_out = 4 * (2 * bsz * s * d + 2 * bsz * s * st + d * st)
    return fwd_in + ckpt + grads_out


def xla_scan_hbm_bytes(bsz: int, s: int, d: int, st: int, elem: int = 4) -> int:
    """Lower bound for the associative-scan path: 2 tensors (decay, upd) of
    (B,S,D,st) read+written per scan level."""
    import math

    levels = max(1, int(math.log2(max(2, s))))
    return elem * 2 * 2 * bsz * s * d * st * levels
