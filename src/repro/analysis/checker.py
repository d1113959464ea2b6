"""Engine 1 of the static checker: abstract kernel-contract analysis.

For every contract in :mod:`repro.analysis.registry` this module proves,
**without launching a single kernel**, that the invariants hold across
the parameter lattice:

* **A000 registry completeness** — every public entry point of
  ``kernels/ops.py`` (plus ``ssm_scan_pallas``) carries a contract; an
  un-annotated kernel is an unchecked kernel.
* **A001 shape contract** — ``jax.eval_shape`` of the real wrapper (pure
  abstract tracing) must produce exactly the output shapes/dtypes the
  contract's model predicts, on every lattice point.
* **A002 block divisibility** — output BlockSpecs tile the padded
  extents exactly (Cor. 7's equal output partition), sort tiles are
  powers of two with ``tile | 2 * width`` for every wide round, and a
  wrapper given a non-pow2 sort tile must *reject* it loudly rather
  than silently running a different tile.
* **A003 prefetch bounds** — the scalar-prefetched window starts,
  bounded analytically from Algorithm 2's search interval
  (``lo >= max(0, d - |B|)``, ``hi <= min(d, |A|)``), can never slice
  past the sentinel-padded buffer ends.
* **A004 sentinel policy** — any contract that carries values or ragged
  lengths must use PR 2's masked (pads-excluded-by-index) rank form;
  unmasked keys-only contracts must state their tie-then-stability
  justification.  This is exactly the class of bug where a window pad
  tied with a real ``+inf`` / ``iinfo.max`` key leaked a zero value.
* **A005 VMEM budget** — a closed-form model of each kernel's per-grid-
  step VMEM high-water (window blocks + engine working set, or the SSM
  backward's ``(chunk+1) * d_tile * st`` recompute slab) must fit the
  per-device budget table.
* **A006 gradient shapes** — for ``differentiable`` contracts,
  ``jax.grad`` is traced abstractly through the ``custom_vjp`` (this
  traces the backward Pallas kernel too) and every cotangent must come
  back with its primal's shape and dtype.

Every check is an ordinary function taking explicit parameters, so the
test suite can aim them at known-bad configurations (a VMEM-overflowing
tile, a padding model with the sentinel tail removed) and assert the
rules actually fire.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from .lattice import LatticeConfig, model_lattice, scan_lattice, trace_lattice
from .registry import REGISTRY, KernelContract, registered_contracts

# Per-device VMEM budgets (bytes).  ~16 MiB/core across current TPU
# generations (see the Pallas TPU guide's memory-hierarchy table); the
# model must fit with headroom for double buffering, so the checker
# budgets a USABLE fraction of the physical size.
VMEM_BUDGET_BYTES: Dict[str, int] = {
    "tpu-v3": 16 * 2**20,
    "tpu-v4": 16 * 2**20,
    "tpu-v5e": 16 * 2**20,
    "tpu-v5p": 16 * 2**20,
}
VMEM_USABLE_FRACTION = 0.9

VALUE_DTYPE = "float32"  # payload dtype used by the abstract sweeps


@dataclass(frozen=True)
class Violation:
    """One contract violation found by the checker."""

    rule: str  # "A000".."A006"
    kernel: str
    config: str
    message: str

    def __str__(self) -> str:
        return f"[{self.rule}] {self.kernel} ({self.config}): {self.message}"


def _dt(name: str):
    import jax.numpy as jnp

    return jnp.dtype(name)


def _esize(name: str) -> int:
    return _dt(name).itemsize


def _is_float(name: str) -> bool:
    import jax.numpy as jnp

    return jnp.issubdtype(_dt(name), jnp.inexact)


def _split(n: int) -> Tuple[int, int]:
    """Uneven |A|, |B| split (na != nb exercises the clamped diagonals)."""
    na = max(1, (2 * n) // 3)
    return na, n - na


def _pow2_ceil(n: int) -> int:
    return 1 << max(0, (max(1, n) - 1).bit_length())


# ---------------------------------------------------------------------------
# Abstract call builders: contract name -> (callable, arg specs, expected
# outputs, differentiable argnums).  These are the checker's model of each
# entry point's *signature*; A001 compares them against reality.
# ---------------------------------------------------------------------------


def _sds(shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(tuple(shape), _dt(dtype))


def _build(contract: KernelContract, cfg: LatticeConfig):
    """Return ``(fn, args, expected_outputs, diff_argnums)`` for one config.

    ``expected_outputs`` is a list of ``(shape, dtype_name)``;
    ``diff_argnums`` indexes the float-differentiable arguments (empty
    for non-differentiable contracts or int-key configs).
    """
    import repro.kernels.ops as ops
    from repro.kernels import ssm_scan as scan_mod

    name, dt, vd = contract.name, cfg.dtype, VALUE_DTYPE
    kw = dict(tile=cfg.tile, leaf=cfg.leaf, engine=cfg.engine)
    n, bsz, k = cfg.n, cfg.batch, min(cfg.k, cfg.n)
    na, nb = _split(n)
    lens32 = _sds((bsz,), "int32")

    if name == "merge":
        return (lambda a, b: ops.merge(a, b, **kw),
                [_sds((na,), dt), _sds((nb,), dt)], [((n,), dt)], ())
    if name == "merge_kv":
        return (lambda ak, av, bk, bv: ops.merge_kv(ak, av, bk, bv, **kw),
                [_sds((na,), dt), _sds((na,), vd), _sds((nb,), dt), _sds((nb,), vd)],
                [((n,), dt), ((n,), vd)], ())
    if name == "merge_batched":
        return (lambda a, b: ops.merge_batched(a, b, **kw),
                [_sds((bsz, na), dt), _sds((bsz, nb), dt)], [((bsz, n), dt)], ())
    if name == "merge_kv_batched":
        return (lambda ak, av, bk, bv: ops.merge_kv_batched(ak, av, bk, bv, **kw),
                [_sds((bsz, na), dt), _sds((bsz, na), vd),
                 _sds((bsz, nb), dt), _sds((bsz, nb), vd)],
                [((bsz, n), dt), ((bsz, n), vd)], ())
    if name == "merge_batched_ragged":
        return (lambda a, b, la, lb: ops.merge_batched_ragged(a, b, la, lb, **kw),
                [_sds((bsz, na), dt), _sds((bsz, nb), dt), lens32, lens32],
                [((bsz, n), dt)], ())
    if name == "merge_kv_batched_ragged":
        return (lambda ak, av, bk, bv, la, lb:
                ops.merge_kv_batched_ragged(ak, av, bk, bv, la, lb, **kw),
                [_sds((bsz, na), dt), _sds((bsz, na), vd),
                 _sds((bsz, nb), dt), _sds((bsz, nb), vd), lens32, lens32],
                [((bsz, n), dt), ((bsz, n), vd)], ())
    if name == "sort":
        return (lambda x: ops.sort(x, **kw), [_sds((n,), dt)], [((n,), dt)],
                (0,) if _is_float(dt) else ())
    if name == "sort_kv":
        return (lambda ks, vs: ops.sort_kv(ks, vs, **kw),
                [_sds((n,), dt), _sds((n,), vd)], [((n,), dt), ((n,), vd)],
                (0, 1) if _is_float(dt) else (1,))
    if name == "sort_batched":
        return (lambda x: ops.sort_batched(x, **kw),
                [_sds((bsz, n), dt)], [((bsz, n), dt)],
                (0,) if _is_float(dt) else ())
    if name == "sort_kv_batched":
        return (lambda ks, vs: ops.sort_kv_batched(ks, vs, **kw),
                [_sds((bsz, n), dt), _sds((bsz, n), vd)],
                [((bsz, n), dt), ((bsz, n), vd)],
                (0, 1) if _is_float(dt) else (1,))
    if name == "merge_k":
        n_run = max(1, n // cfg.runs)
        return (lambda runs, lens: ops.merge_k(runs, lens, **kw),
                [_sds((cfg.runs, n_run), dt), _sds((cfg.runs,), "int32")],
                [((cfg.runs * n_run,), dt)], ())
    if name == "topk_batched":
        return (lambda x: ops.topk_batched(x, k, **kw),
                [_sds((bsz, n), dt)], [((bsz, k), dt), ((bsz, k), "int32")],
                (0,) if _is_float(dt) else ())
    if name == "topk_batched_ragged":
        return (lambda x, ln: ops.topk_batched_ragged(x, k, ln, **kw),
                [_sds((bsz, n), dt), lens32],
                [((bsz, k), dt), ((bsz, k), "int32")],
                (0,) if _is_float(dt) else ())
    if name == "ssm_scan_pallas":
        b, s, d, st = cfg.batch, cfg.seq, cfg.d_model, cfg.state
        return (lambda dtt, x, bm, cm, a: scan_mod.ssm_scan_pallas(
                    dtt, x, bm, cm, a, chunk=cfg.chunk, d_tile=cfg.d_tile),
                [_sds((b, s, d), dt), _sds((b, s, d), dt),
                 _sds((b, s, st), dt), _sds((b, s, st), dt), _sds((d, st), dt)],
                [((b, s, d), dt), ((b, d, st), "float32")],
                (0, 1, 2, 3, 4) if _is_float(dt) else ())
    raise KeyError(f"no abstract builder for contract {name!r} — add one "
                   f"to repro.analysis.checker._build")


# ---------------------------------------------------------------------------
# A001: eval_shape vs the contract's signature model
# ---------------------------------------------------------------------------


def shape_violations(contract: KernelContract, cfg: LatticeConfig) -> List[Violation]:
    import jax

    fn, args, expected, _ = _build(contract, cfg)
    try:
        out = jax.eval_shape(fn, *args)
    except Exception as e:  # tracing itself must not fail on a valid config
        return [Violation("A001", contract.name, cfg.describe(),
                          f"abstract trace failed: {type(e).__name__}: {e}")]
    outs = list(out) if isinstance(out, (tuple, list)) else [out]
    if len(outs) != len(expected):
        return [Violation("A001", contract.name, cfg.describe(),
                          f"expected {len(expected)} outputs, traced {len(outs)}")]
    vs = []
    for i, (o, (shape, dtype)) in enumerate(zip(outs, expected)):
        if tuple(o.shape) != tuple(shape) or o.dtype != _dt(dtype):
            vs.append(Violation(
                "A001", contract.name, cfg.describe(),
                f"output {i}: traced {o.shape}/{o.dtype}, contract says "
                f"{tuple(shape)}/{dtype}"))
    return vs


# ---------------------------------------------------------------------------
# A002: block divisibility (+ loud rejection of non-pow2 sort tiles)
# ---------------------------------------------------------------------------


def _wide_widths(m: int, tile: int) -> List[int]:
    """Run widths handled by the flat kernel rounds for an m-element row."""
    w = 1
    while w < m and 2 * w <= tile:
        w *= 2
    out = []
    while w < m:
        out.append(w)
        w *= 2
    return out


def block_divisibility_violations(contract: KernelContract, cfg: LatticeConfig) -> List[Violation]:
    vs = []
    name, tile, leaf = contract.name, cfg.tile, cfg.leaf
    if contract.kind == "scan":
        # the wrapper must normalize (chunk, d_tile) to divisors
        chunk = max(1, min(cfg.chunk, cfg.seq))
        if (-cfg.seq) % chunk and chunk > cfg.seq:
            vs.append(Violation("A002", name, cfg.describe(),
                                f"chunk {chunk} cannot pad seq {cfg.seq}"))
        d_tile = max(1, min(cfg.d_tile, cfg.d_model))
        while cfg.d_model % d_tile:
            d_tile -= 1
        if cfg.d_model % d_tile:
            vs.append(Violation("A002", name, cfg.describe(),
                                f"d_tile {d_tile} does not divide d_model {cfg.d_model}"))
        return vs
    if not 1 <= min(leaf, tile):
        vs.append(Violation("A002", name, cfg.describe(), f"leaf {leaf} unusable"))
    if contract.pow2_tile:
        if tile & (tile - 1):
            vs.append(Violation(
                "A002", name, cfg.describe(),
                f"sort tile {tile} is not a power of two (flat rounds need "
                f"tile | 2 * width with pow2 widths)"))
        else:
            m = _pow2_ceil(cfg.n)
            for w in _wide_widths(m, tile):
                if (2 * w) % tile:
                    vs.append(Violation(
                        "A002", name, cfg.describe(),
                        f"round width {w}: tile {tile} does not divide 2*width"))
    else:
        # merge kinds: the output BlockSpec must tile the padded extent
        nt = -(-cfg.n // tile)
        if (nt * tile) % tile:
            vs.append(Violation("A002", name, cfg.describe(),
                                "padded output extent not a multiple of the tile"))
    return vs


def rejection_violations(contract: KernelContract, bad_tile: int = 96) -> List[Violation]:
    """pow2_tile contracts must REJECT a non-pow2 tile, not run it."""
    import jax

    if not contract.pow2_tile:
        return []
    cfg = LatticeConfig(n=4096, tile=bad_tile, leaf=8)
    fn, args, _, _ = _build(contract, cfg)
    try:
        jax.eval_shape(fn, *args)
    except ValueError:
        return []  # loud rejection — exactly what the contract demands
    except Exception as e:
        return [Violation("A002", contract.name, cfg.describe(),
                          f"non-pow2 tile {bad_tile} raised {type(e).__name__} "
                          f"instead of ValueError")]
    return [Violation("A002", contract.name, cfg.describe(),
                      f"non-pow2 tile {bad_tile} was silently accepted")]


# ---------------------------------------------------------------------------
# A003: scalar-prefetch window starts stay inside the padded buffers
# ---------------------------------------------------------------------------


def prefetch_violations(
    contract: KernelContract,
    cfg: LatticeConfig,
    pad_elems: Optional[int] = None,
) -> List[Violation]:
    """Bound the prefetched starts analytically and check every windowed
    read ``[start, start + tile)`` lands inside the padded buffer.

    ``pad_elems`` overrides the modeled sentinel padding (the `_prepare`
    family appends ``tile`` sentinels); the tests pass ``0`` to model a
    kernel that forgot its padding and assert this rule fires.
    """
    tile = cfg.tile
    pad = tile if pad_elems is None else pad_elems
    name = contract.name
    vs = []
    if contract.kind in ("merge", "merge_k"):
        if contract.kind == "merge_k":
            # tournament rounds run the ragged batched kernel on (k/2, n_run*2)
            na = nb = max(1, cfg.n // cfg.runs)
            n = na + nb
        else:
            na, nb = _split(cfg.n)
            n = cfg.n
        nt = -(-n // tile)
        # Alg. 2 invariant: a_start in [max(0, d - nb), min(d, na)]
        max_diag = min((nt - 1) * tile, n)
        max_a = min(max_diag, na)
        max_b = min(max_diag, nb)
        if max_a + tile > na + pad:
            vs.append(Violation(
                "A003", name, cfg.describe(),
                f"A window read can reach {max_a + tile} but the padded "
                f"buffer holds {na + pad} elements"))
        if max_b + tile > nb + pad:
            vs.append(Violation(
                "A003", name, cfg.describe(),
                f"B window read can reach {max_b + tile} but the padded "
                f"buffer holds {nb + pad} elements"))
    elif contract.kind in ("sort", "topk"):
        if cfg.tile & (cfg.tile - 1):
            return vs  # rejected configs never reach the prefetch tables
        m_row = _pow2_ceil(cfg.n)
        m = m_row * (cfg.batch if contract.batched else 1)
        for w in _wide_widths(m_row, tile):
            npairs = m // (2 * w)
            tpp = (2 * w) // tile
            max_d = (tpp - 1) * tile
            base = (npairs - 1) * 2 * w
            max_fa = base + min(max_d, w)
            max_fb = base + w + min(max_d, w)
            hi = max(max_fa, max_fb)
            if hi + tile > m + pad:
                vs.append(Violation(
                    "A003", name, cfg.describe(),
                    f"round width {w}: flat window read can reach "
                    f"{hi + tile} but the buffer holds {m + pad} elements"))
    return vs


# ---------------------------------------------------------------------------
# A004: sentinel / masked-rank policy
# ---------------------------------------------------------------------------


def sentinel_violations(contract: KernelContract) -> List[Violation]:
    if contract.kind == "scan":
        return []  # no rank path, no sentinels
    vs = []
    if (contract.carries_values or contract.ragged) and not contract.masked_ranks:
        what = "values" if contract.carries_values else "ragged lengths"
        vs.append(Violation(
            "A004", contract.name, "-",
            f"carries {what} on an UNMASKED rank path: a window pad tied "
            f"with a real +inf / iinfo.max key can steal its slot and leak "
            f"a zero value (PR 2's sentinel-collision bug class)"))
    if not contract.masked_ranks and not contract.tie_safe:
        vs.append(Violation(
            "A004", contract.name, "-",
            "unmasked rank path without a tie_safe justification — state "
            "why sentinel-tied real keys still merge bit-exactly"))
    return vs


# ---------------------------------------------------------------------------
# A005: modeled VMEM high-water vs the device budget table
# ---------------------------------------------------------------------------


def vmem_bytes(contract: KernelContract, cfg: LatticeConfig) -> int:
    """Closed-form per-grid-step VMEM high-water model (bytes).

    Tile kernels: two input windows + output block(s) plus the engine
    working set — the ``(T, T)`` merge matrix and one-hot select for the
    matrix engine (its defining cost), the bitonic network's element
    arrays for the ``hier`` engine (``leaf`` does not enter).  SSM scan: the
    BlockSpec'd operands plus the scratch slabs, with the backward's
    ``(chunk+1) * d_tile * st`` recompute buffer dominating (the formula
    next to ``ssm_scan.bwd_hbm_bytes``).
    """
    e = _esize(cfg.dtype)
    if contract.kind == "scan":
        b, s, d, st = 1, cfg.chunk, cfg.d_tile, cfg.state  # one grid step
        f32 = 4
        fwd = (3 * s * d * e          # dt, x, y blocks
               + 2 * s * st * e       # B, C blocks
               + d * st * e           # A block
               + 2 * d * st * f32)    # h scratch + checkpoint block
        if not contract.differentiable:
            return fwd
        n_d = max(1, cfg.d_model // max(1, cfg.d_tile))
        bwd = (3 * s * d * e                 # dt, x blocks + dy
               + 2 * s * st * e + d * st * e  # B, C, A blocks
               + 2 * d * st * f32            # hstart + dhfin blocks
               + 2 * s * d * f32             # ddt, dx out blocks
               + 2 * s * st * f32 + d * st * f32  # dB, dC, dA out blocks
               + (s + 1) * d * st * f32      # recomputed chunk states
               + 2 * n_d * d * st * f32)     # g carry + dA accumulator slabs
        return max(fwd, bwd)
    tile = cfg.tile
    v = _esize(VALUE_DTYPE) if contract.carries_values else 0
    io = 3 * tile * (e + v)  # two input windows + one output block (per operand)
    i32 = 4
    if cfg.engine == "matrix":
        # (T, T) bool merge matrix + the (T, T) one-hot select staged per
        # operand dtype (the widest where() intermediate dominates)
        work = tile * tile * (1 + max(e, v)) + 2 * tile * i32
    else:
        # (key, int32 index[, value]) over the pow2-padded tile, held five
        # times in a stage (the elements, both rotations, the partners, the
        # result) + three int32-wide compare masks
        p = 1 << max(0, (tile - 1).bit_length())
        work = 5 * p * (e + i32 + v) + 3 * p * i32
    return io + work


def vmem_violations(
    contract: KernelContract,
    cfg: LatticeConfig,
    budgets: Optional[Dict[str, int]] = None,
    usable_fraction: float = VMEM_USABLE_FRACTION,
) -> List[Violation]:
    budgets = VMEM_BUDGET_BYTES if budgets is None else budgets
    hw = vmem_bytes(contract, cfg)
    vs = []
    for dev, cap in sorted(budgets.items()):
        limit = int(cap * usable_fraction)
        if hw > limit:
            vs.append(Violation(
                "A005", contract.name, cfg.describe(),
                f"modeled VMEM high-water {hw} B exceeds the {dev} budget "
                f"{limit} B ({usable_fraction:.0%} of {cap} B)"))
    return vs


# ---------------------------------------------------------------------------
# A006: abstract gradient tracing for differentiable contracts
# ---------------------------------------------------------------------------


def grad_violations(contract: KernelContract, cfg: LatticeConfig) -> List[Violation]:
    import jax
    import jax.numpy as jnp

    if not contract.differentiable:
        return []
    fn, args, _, argnums = _build(contract, cfg)
    if not argnums:
        return []

    def scalar_loss(*xs):
        outs = fn(*xs)
        outs = outs if isinstance(outs, (tuple, list)) else (outs,)
        tot = jnp.zeros((), jnp.float32)
        for o in outs:
            if jnp.issubdtype(o.dtype, jnp.inexact):
                tot = tot + jnp.sum(o.astype(jnp.float32))
        return tot

    try:
        grads = jax.eval_shape(jax.grad(scalar_loss, argnums=argnums), *args)
    except Exception as e:
        return [Violation("A006", contract.name, cfg.describe(),
                          f"abstract backward trace failed: {type(e).__name__}: {e}")]
    vs = []
    for i, g in zip(argnums, grads if isinstance(grads, (tuple, list)) else [grads]):
        a = args[i]
        if tuple(g.shape) != tuple(a.shape) or g.dtype != a.dtype:
            vs.append(Violation(
                "A006", contract.name, cfg.describe(),
                f"cotangent of arg {i}: {g.shape}/{g.dtype} != primal "
                f"{a.shape}/{a.dtype}"))
    return vs


# ---------------------------------------------------------------------------
# A000 + the driver
# ---------------------------------------------------------------------------


def completeness_violations(contracts: Optional[Dict[str, KernelContract]] = None) -> List[Violation]:
    """Every public ``kernels.ops`` callable (and the SSM scan entry
    point) must be registered — an un-annotated kernel is unchecked."""
    import repro.kernels.ops as ops

    contracts = registered_contracts() if contracts is None else contracts
    vs = []
    for name, obj in sorted(vars(ops).items()):
        if name.startswith("_") or not callable(obj) or isinstance(obj, type):
            continue
        if getattr(obj, "__module__", None) != "repro.kernels.ops":
            continue
        if name not in contracts:
            vs.append(Violation(
                "A000", name, "-",
                "public kernels.ops entry point has no kernel_contract "
                "annotation — add one (see docs/analysis.md)"))
    if "ssm_scan_pallas" not in contracts:
        vs.append(Violation("A000", "ssm_scan_pallas", "-",
                            "the fused SSM scan has no kernel_contract annotation"))
    return vs


def _configs_for(contract: KernelContract, fast: bool, trace: bool) -> List[LatticeConfig]:
    if contract.kind == "scan":
        return scan_lattice(fast)
    return trace_lattice(fast) if trace else model_lattice()


def check_contract(
    contract: KernelContract,
    *,
    fast: bool = False,
    budgets: Optional[Dict[str, int]] = None,
    trace: bool = True,
) -> List[Violation]:
    """All rules for one contract over its applicable lattice slices."""
    vs = sentinel_violations(contract)
    for cfg in _configs_for(contract, fast, trace=False):
        vs += block_divisibility_violations(contract, cfg)
        vs += prefetch_violations(contract, cfg)
        vs += vmem_violations(contract, cfg, budgets)
    if trace:
        vs += rejection_violations(contract)
        trace_cfgs = _configs_for(contract, fast, trace=True)
        for cfg in trace_cfgs:
            vs += shape_violations(contract, cfg)
        # one backward trace per contract is enough to prove the VJP
        # machinery composes abstractly — pick the first float config
        for cfg in trace_cfgs:
            if _is_float(cfg.dtype):
                vs += grad_violations(contract, cfg)
                break
    return vs


def check_kernels(
    *,
    fast: bool = False,
    budgets: Optional[Dict[str, int]] = None,
    trace: bool = True,
) -> List[Violation]:
    """Run the full abstract analysis: registry completeness plus every
    rule on every registered contract.  ``fast=True`` shrinks the trace
    lattice (the arithmetic rules always sweep the full model lattice).
    No kernel is ever launched — everything goes through ``eval_shape``.
    """
    # importing the kernel modules populates the registry
    import repro.kernels.ops  # noqa: F401
    import repro.kernels.ssm_scan  # noqa: F401

    contracts = registered_contracts()
    vs = completeness_violations(contracts)
    for name in sorted(contracts):
        vs += check_contract(contracts[name], fast=fast, budgets=budgets, trace=trace)
    return vs
