"""Guarded kernel dispatch: preflight checks, fallback chains, health counters.

Every public ``repro.kernels.ops`` entry point (and the distributed merge /
sort / top-k wrappers in ``repro.core.distributed``) routes through
:func:`guarded_call`.  For each call the guard walks an explicit attempt
chain — ``pallas-hier -> pallas-matrix -> core`` for the single-host
kernels, ``window -> gather`` for the distributed exchange — and returns
the first attempt that

1. passes **preflight**: runtime preconditions checked against the PR 7
   ``@kernel_contract`` registry (tile legality, the closed-form VMEM
   high-water model vs the A005 budget, length bounds);
2. **launches**: any exception out of the attempt (XLA launch failure,
   Pallas lowering error, injected :class:`~repro.runtime.faults.InjectedFault`)
   is caught here — and *only* here; lint rule L006 forbids swallowing
   kernel-launch failures anywhere else;
3. **verifies** (when verification is active): an op-specific output check
   (tok-space sortedness of the produced keys) rejects corrupted results.

Degradation is loud: each taken fallback edge emits a
:class:`FallbackWarning` and increments per-op health counters, surfaced by
``benchmarks/run.py`` so a silently-degraded CI run cannot report healthy
numbers.  When the whole chain is exhausted, :class:`GuardedDispatchError`
carries the per-attempt failure log.

Verification policy
-------------------
Output verification costs a host-side O(n) pass per call, which would blow
the CI perf anchors on the hot eager paths.  It is therefore **off by
default for the single-host kernels** and turns on automatically whenever a
fault plan is active (``repro.runtime.faults.active()``), or explicitly via
``REPRO_GUARD_VERIFY=1`` (``=0`` forces it off even under faults).  The
distributed wrappers verify by default — their perf anchor gates exchanged
bytes, not wall-clock.

Tracing bypass
--------------
The guard needs concrete operands: under ``jit`` / ``grad`` / ``vmap`` /
``eval_shape`` the inputs are tracers, Python control flow cannot branch on
device failures, and ``jax.custom_vjp`` traces its function.  When any
operand is a tracer (or ``REPRO_GUARD=0``) the wrapper dispatches the
primary attempt directly — the guard protects the eager call boundary, and
traced code is reached through an already-guarded eager entry point in the
serving and benchmark paths.

Environment knobs
-----------------
``REPRO_GUARD=0``         disable guarded dispatch (primary attempt only).
``REPRO_GUARD_VERIFY``    ``1`` always verify, ``0`` never; unset = only
                          while a fault plan is active.
``REPRO_GUARD_DEVICE``    key into ``VMEM_BUDGET_BYTES`` (e.g. ``tpu-v4``)
                          for the preflight budget; unset = the attached
                          TPU's kind (an unknown TPU kind raises), or the
                          most permissive budget when no TPU is attached.
"""

from __future__ import annotations

import os
import warnings
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax

from repro.core import merge_path as _mp
from repro.runtime import faults as _faults
from repro.telemetry import get_telemetry

__all__ = [
    "FallbackWarning",
    "GuardedDispatchError",
    "OpHealth",
    "VerificationError",
    "guard_enabled",
    "guarded_call",
    "health",
    "health_summary",
    "is_tracing",
    "preflight",
    "reset_health",
    "sorted_kv_verifier",
    "sorted_verifier",
    "topk_verifier",
    "verify_active",
]


class FallbackWarning(UserWarning):
    """Emitted once per taken fallback edge (structured, never silent)."""


class GuardedDispatchError(RuntimeError):
    """Every attempt in a dispatch chain failed; carries the attempt log."""

    def __init__(self, op: str, log: List[str]):
        self.op = op
        self.log = list(log)
        super().__init__(f"guarded dispatch exhausted for {op!r}: " + "; ".join(log))


class VerificationError(RuntimeError):
    """An attempt produced output that failed its verifier."""


# ---------------------------------------------------------------------------
# policy knobs
# ---------------------------------------------------------------------------


def guard_enabled() -> bool:
    """Guarded dispatch is on unless ``REPRO_GUARD=0``."""
    return os.environ.get("REPRO_GUARD", "1") != "0"


def verify_active() -> bool:
    """Whether output verification runs for this call (see module docstring)."""
    raw = os.environ.get("REPRO_GUARD_VERIFY", "")
    if raw == "1":
        return True
    if raw == "0":
        return False
    return _faults.active()


def is_tracing(*values) -> bool:
    """True when any operand is a JAX tracer (guard must bypass)."""
    return any(isinstance(v, jax.core.Tracer) for v in values)


# jax ``device_kind`` -> key of ``VMEM_BUDGET_BYTES``
_TPU_KINDS = {
    "TPU v3": "tpu-v3",
    "TPU v4": "tpu-v4",
    "TPU v5 lite": "tpu-v5e",
    "TPU v5": "tpu-v5p",
    "TPU v5p": "tpu-v5p",
}


def _budget_device() -> str:
    """Budget row for preflight: ``REPRO_GUARD_DEVICE``, else the attached
    TPU's kind, else ``""`` (no TPU: kernels run interpreted)."""
    device = os.environ.get("REPRO_GUARD_DEVICE", "")
    if device or jax.default_backend() != "tpu":
        return device
    kind = jax.devices()[0].device_kind
    if kind not in _TPU_KINDS:
        raise ValueError(f"no VMEM budget known for TPU kind {kind!r}")
    return _TPU_KINDS[kind]


def _budget_bytes() -> int:
    from repro.analysis.checker import VMEM_BUDGET_BYTES, VMEM_USABLE_FRACTION

    device = _budget_device()
    if device and device not in VMEM_BUDGET_BYTES:
        raise ValueError(f"no VMEM budget row {device!r} (known: {sorted(VMEM_BUDGET_BYTES)})")
    budget = VMEM_BUDGET_BYTES[device] if device else max(VMEM_BUDGET_BYTES.values())
    return int(budget * VMEM_USABLE_FRACTION)


# ---------------------------------------------------------------------------
# health counters
# ---------------------------------------------------------------------------


@dataclass
class OpHealth:
    """Mutable per-op counters (one instance per guarded op name)."""

    calls: int = 0
    fallbacks: int = 0
    precondition_rejects: int = 0
    launch_failures: int = 0
    verify_failures: int = 0
    faults_injected: int = 0
    exhausted: int = 0
    served_by: Dict[str, int] = field(default_factory=dict)
    fallback_edges: Dict[str, int] = field(default_factory=dict)

    def as_dict(self) -> dict:
        return {
            "calls": self.calls,
            "fallbacks": self.fallbacks,
            "precondition_rejects": self.precondition_rejects,
            "launch_failures": self.launch_failures,
            "verify_failures": self.verify_failures,
            "faults_injected": self.faults_injected,
            "exhausted": self.exhausted,
            "served_by": dict(self.served_by),
            "fallback_edges": dict(self.fallback_edges),
        }


# Per-op health records live in the active telemetry registry
# (``get_telemetry().health``) so traces, bench summaries, and the
# ``python -m repro.telemetry`` CLI all see the same counters; these
# helpers keep the PR 8 call sites working unchanged.


def health(op: str) -> OpHealth:
    """The (auto-created) health record for ``op``."""
    store = get_telemetry().health
    rec = store.get(op)
    if rec is None:
        rec = store[op] = OpHealth()
    return rec


def reset_health() -> None:
    """Zero every per-op health record."""
    get_telemetry().health.clear()


def health_summary() -> dict:
    """``{op: counters}`` plus a ``"totals"`` roll-up across all ops."""
    store = get_telemetry().health
    totals = OpHealth()
    per_op = {}
    for op in sorted(store):
        rec = store[op]
        per_op[op] = rec.as_dict()
        totals.calls += rec.calls
        totals.fallbacks += rec.fallbacks
        totals.precondition_rejects += rec.precondition_rejects
        totals.launch_failures += rec.launch_failures
        totals.verify_failures += rec.verify_failures
        totals.faults_injected += rec.faults_injected
        totals.exhausted += rec.exhausted
    per_op["totals"] = totals.as_dict()
    return per_op


# ---------------------------------------------------------------------------
# preflight: runtime preconditions against the @kernel_contract registry
# ---------------------------------------------------------------------------

_MAX_N = 2**31 - 1  # cut tables and ranks are int32


def preflight(op: str, meta: Optional[dict], label: str, index: int) -> List[str]:
    """Reasons this attempt must not launch (empty list == go).

    ``meta`` carries the concrete call geometry (``n``, ``batch``,
    ``dtype``, ``tile``, ``leaf`` — or the scan geometry).  Checks:

    * length bounds: ``0 <= n <= int32 max`` (rank arithmetic is int32);
    * tile legality: ``tile >= 1``, ``1 <= leaf <= tile``, power-of-two
      tile when the contract demands it;
    * the A005 closed-form VMEM high-water model vs the device budget,
      for Pallas attempts only (``core`` twins never touch VMEM);
    * an injected ``vmem`` fault counts as a modeled breach.
    """
    if meta is None:
        return []
    reasons: List[str] = []
    n = meta.get("n")
    if n is not None and not (0 <= int(n) <= _MAX_N):
        reasons.append(f"n={n} outside [0, {_MAX_N}]")
    tile, leaf = meta.get("tile"), meta.get("leaf")
    if tile is not None:
        if int(tile) < 1:
            reasons.append(f"tile={tile} < 1")
        if leaf is not None and not (1 <= int(leaf) <= int(tile)):
            reasons.append(f"leaf={leaf} outside [1, tile={tile}]")
    is_pallas = label.startswith("pallas")
    if is_pallas and not reasons:
        from repro.analysis.checker import vmem_bytes
        from repro.analysis.lattice import LatticeConfig
        from repro.analysis.registry import REGISTRY

        contract = REGISTRY.get(op)
        if contract is not None:
            if contract.pow2_tile and tile is not None and (int(tile) & (int(tile) - 1)) != 0:
                reasons.append(f"tile={tile} not a power of two (contract {op})")
            engine = label.split("-", 1)[1] if "-" in label else meta.get("engine", "hier")
            cfg = LatticeConfig(
                dtype=meta.get("dtype", "float32"),
                n=int(meta.get("n", 4096) or 1),
                batch=int(meta.get("batch", 1) or 1),
                tile=int(tile or 512),
                leaf=int(leaf or 32),
                engine=engine,
                ragged=bool(meta.get("ragged", False)),
                seq=int(meta.get("seq", 256)),
                d_model=int(meta.get("d_model", 128)),
                state=int(meta.get("state", 8)),
                chunk=int(meta.get("chunk", 64)),
                d_tile=int(meta.get("d_tile", 64)),
            )
            try:
                need = vmem_bytes(contract, cfg)
            except Exception:  # model not defined for this geometry
                need = 0
            budget = _budget_bytes()
            if _faults.should_fire("vmem", op, index, label=label):
                health(op).faults_injected += 1
                reasons.append(f"injected vmem fault: modeled breach for {label}")
            elif need > budget:
                reasons.append(f"modeled VMEM {need}B exceeds budget {budget}B for {label}")
    return reasons


# ---------------------------------------------------------------------------
# output verifiers (tok-space order checks on host)
# ---------------------------------------------------------------------------


def _tok_np(x) -> np.ndarray:
    """Host copy of the IEEE-754 total-order keys for ``x`` (2-D)."""
    tok = np.asarray(_mp.total_order_keys(x))
    return tok[None, :] if tok.ndim == 1 else tok


def _rows_nondecreasing(tok: np.ndarray, lens, descending: bool = False) -> bool:
    # Elementwise comparisons, not diffs: an int64 difference between the
    # two key extremes wraps around and would flag correct output.
    if tok.shape[1] < 2:
        return True
    tok = tok.astype(np.int64)
    ok = tok[:, 1:] <= tok[:, :-1] if descending else tok[:, :-1] <= tok[:, 1:]
    if lens is None:
        return bool(np.all(ok))
    lens = np.asarray(lens, dtype=np.int64).reshape(-1)
    cols = np.arange(tok.shape[1] - 1, dtype=np.int64)[None, :]
    in_prefix = cols < (lens[:, None] - 1)
    return bool(np.all(ok | ~in_prefix))


def sorted_verifier(lens=None) -> Callable:
    """Verifier: output keys are nondecreasing in tok space.

    ``lens`` (per-row valid lengths) restricts the check to the valid
    prefix of each row — the padded tail of a ragged merge holds key
    sentinels that are checked by construction, and a NaN inside the valid
    prefix would otherwise sort *before* a float ``+inf`` pad and trip a
    full-row check on correct output.
    """

    def check(out) -> Optional[str]:
        keys = out[0] if isinstance(out, tuple) else out
        if not _rows_nondecreasing(_tok_np(keys), lens):
            return "output keys not nondecreasing in total-order space"
        return None

    return check


def sorted_kv_verifier(lens=None) -> Callable:
    """Alias of :func:`sorted_verifier` (tuple outputs verify keys)."""
    return sorted_verifier(lens)


def topk_verifier(descending: bool = True) -> Callable:
    """Verifier for ``(values, indices)`` top-k output.

    Checks the per-row *valid* slots (``indices >= 0``; masked ragged
    slots carry ``-1``) are nonincreasing in total-order space.  The check
    runs on ``tok(values)`` directly rather than through ``flip_desc``
    (negating a NaN is still a NaN): in tok space NaN is the *largest*
    key, so the NaN-first descending order produced by the core top-k on
    NaN-laced input verifies as correct.
    """

    def check(out) -> Optional[str]:
        vals, idx = out
        tok = _tok_np(vals)
        idx_np = np.asarray(idx)
        if idx_np.ndim == 1:
            idx_np = idx_np[None, :]
        lens = (idx_np >= 0).sum(axis=1)
        if not _rows_nondecreasing(tok, lens, descending=descending):
            return "top-k values not nonincreasing over valid slots"
        return None

    return check


# ---------------------------------------------------------------------------
# the dispatch loop
# ---------------------------------------------------------------------------


def guarded_call(
    op: str,
    attempts: Sequence[Tuple[str, Callable[[], object]]],
    *,
    index: Optional[int] = None,
    meta: Optional[dict] = None,
    verifier: Optional[Callable] = None,
    verify: Optional[bool] = None,
):
    """Walk the attempt chain for one call of ``op``; return the first good result.

    ``attempts`` is an ordered list of ``(label, thunk)``; the last entry
    is the oracle of record.  ``index`` is this call's position in the
    per-op stream (from ``faults.next_index``); when ``None`` it is taken
    here.  ``verify=None`` defers to the global policy
    (:func:`verify_active`); the distributed wrappers pass ``True``.
    """
    if index is None:
        index = _faults.next_index(op)
    rec = health(op)
    rec.calls += 1
    run_verify = verify_active() if verify is None else verify
    log: List[str] = []
    last_err: Optional[BaseException] = None
    n_att = len(attempts)
    attrs = {k: v for k, v in (meta or {}).items() if v is not None}
    with get_telemetry().span(f"op/{op}", index=index, **attrs) as sp:
        for i, (label, thunk) in enumerate(attempts):
            last = i == n_att - 1
            reasons = preflight(op, meta, label, index)
            if reasons:
                rec.precondition_rejects += 1
                log.append(f"{label}: preflight rejected ({'; '.join(reasons)})")
                continue
            if _faults.should_fire("launch", op, index, label=label, last=last):
                rec.faults_injected += 1
                rec.launch_failures += 1
                err = _faults.InjectedFault(f"injected launch failure: {op}[{index}] {label}")
                last_err = err
                log.append(f"{label}: {err}")
                continue
            try:
                out = thunk()
            except Exception as err:  # the one sanctioned launch-catch (L006)
                rec.launch_failures += 1
                last_err = err
                log.append(f"{label}: {type(err).__name__}: {err}")
                continue
            if _faults.should_fire("exchange", op, index, label=label, last=last):
                rec.faults_injected += 1
                out = _faults.corrupt(out, f"{op}:{index}:{label}")
            if run_verify and verifier is not None:
                problem = verifier(out)
                if problem is not None:
                    rec.verify_failures += 1
                    last_err = VerificationError(f"{op}[{index}] {label}: {problem}")
                    log.append(f"{label}: verify failed ({problem})")
                    continue
            rec.served_by[label] = rec.served_by.get(label, 0) + 1
            sp.set("served_by", label)
            if i > 0:
                rec.fallbacks += 1
                edge = f"{attempts[0][0]}->{label}"
                rec.fallback_edges[edge] = rec.fallback_edges.get(edge, 0) + 1
                sp.set("degraded", edge)
                warnings.warn(
                    f"guarded dispatch: {op}[{index}] degraded {edge} ({log[-1] if log else 'unknown'})",
                    FallbackWarning,
                    stacklevel=3,
                )
            return out
        rec.exhausted += 1
        sp.set("exhausted", True)
    raise GuardedDispatchError(op, log) from last_err
