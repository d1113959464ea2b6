#!/usr/bin/env python
"""Engine 2 of the static checker: repo-specific AST lint over ``src/``.

Every rule here encodes a bug class that already cost a PR to find and
fix (the catalog with full rationale lives in ``docs/analysis.md``):

* **L001** — no literal ``interpret=True`` / ``interpret=False`` at call
  sites.  The interpret default must route through
  ``ops.default_interpret()`` (compiled on a TPU backend, interpreted
  elsewhere; ``REPRO_PALLAS_INTERPRET`` overrides it for tests),
  otherwise a hard-coded call site silently pins interpret mode on a
  real TPU — or compiled mode on the CPU CI box.
* **L002** — no ``-x`` negation of keys to get descending order.  For
  int keys ``-x`` overflows at ``iinfo.min`` and collapses ties'
  stability; the sanctioned form is ``repro.core.merge_path.flip_desc``
  (bit-flip ``~x``), exact at every representable value.
* **L003** — no raw ``iinfo`` / ``finfo`` / ``.inf`` sentinel
  construction outside the one sanctioned helper module
  (``src/repro/core/merge_path.py``: ``max_sentinel`` / ``min_sentinel``
  / ``flip_desc``).  Scattered sentinel spellings are how the
  pad-vs-real-key collision bug slipped in.
* **L004** — no Python ``for`` loop in ``kernels/`` that launches a
  Pallas kernel per iteration (loop-over-pairs).  One launch per round
  with the pairing folded into the grid is the whole point of the flat
  round kernel; a Python loop re-introduces O(rounds * pairs) dispatch.
* **L005** — every ``custom_vjp`` forward must be paired with a
  registered gradient test: the outermost enclosing function's name
  (underscores stripped) must appear in some ``tests/*.py`` that
  exercises gradients.  An untested backward is how silent wrong
  gradients ship.
* **L006** — no bare ``except:`` / ``except Exception:`` around a kernel
  launch outside the guard layer
  (``src/repro/runtime/resilience.py``).  Swallowing a launch failure
  anywhere else bypasses the fallback chain, the health counters, and
  the ``FallbackWarning`` — exactly the silent degradation the guarded
  dispatch exists to prevent.
* **L007** — no raw ``time.perf_counter()`` / ``time.monotonic()``
  outside the telemetry clock layer (``src/repro/telemetry/``) and the
  shared bench timer (``benchmarks/_timing.py``).  The serving engine's
  traces replay bit-identically *because* every timestamp routes
  through the pluggable telemetry clock; a stray wall-clock read is how
  nondeterminism leaks back in.  Use ``repro.telemetry.wall_seconds``
  (or ``WALL`` / a ``Telemetry`` span) instead.

Suppression: append ``# lint: ok`` (any rule) or ``# lint: ok(L004)``
(one rule) to the flagged line.  Stdlib ``ast`` only — the container is
offline, so no third-party linters.
"""

from __future__ import annotations

import ast
import re
import sys
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Set, Tuple

REPO_ROOT = Path(__file__).resolve().parent.parent

# the one module allowed to spell sentinels from iinfo/finfo/inf
SANCTIONED_SENTINEL_FILES = ("src/repro/core/merge_path.py",)

# the one module allowed to catch launch failures broadly (guarded dispatch)
SANCTIONED_LAUNCH_CATCH_FILES = ("src/repro/runtime/resilience.py",)

# the places allowed to read the raw wall clock (L007): the telemetry
# clock layer itself and the shared benchmark timer
SANCTIONED_WALL_CLOCK_DIRS = ("src/repro/telemetry/",)
SANCTIONED_WALL_CLOCK_FILES = ("benchmarks/_timing.py",)

# raw-clock callables L007 forbids elsewhere
_WALL_CLOCK_NAMES = ("perf_counter", "monotonic")

# callables whose arguments are "keys" for L002's descending-order check
_KEYED_CALL = re.compile(r"(sort|topk|top_k|merge|argsort)", re.IGNORECASE)
# kernel-launching callees for L004
_LAUNCH_CALL = re.compile(r"(_pallas$|^pallas_call$)")

_SUPPRESS = re.compile(r"#\s*lint:\s*ok(?:\(([A-Z0-9, ]+)\))?")


@dataclass(frozen=True)
class LintViolation:
    rule: str  # "L001".."L005"
    path: str
    line: int
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


def _suppressions(source: str) -> Dict[int, Optional[Set[str]]]:
    """line -> None (suppress all rules) or a set of rule ids."""
    out: Dict[int, Optional[Set[str]]] = {}
    for i, line in enumerate(source.splitlines(), start=1):
        m = _SUPPRESS.search(line)
        if m:
            rules = m.group(1)
            out[i] = None if rules is None else {
                r.strip() for r in rules.split(",") if r.strip()
            }
    return out


def _suppressed(sup: Dict[int, Optional[Set[str]]], line: int, rule: str) -> bool:
    if line not in sup:
        return False
    rules = sup[line]
    return rules is None or rule in rules


def _callee_name(call: ast.Call) -> str:
    f = call.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return ""


def _is_custom_vjp_expr(node: ast.AST) -> bool:
    """``jax.custom_vjp`` / ``custom_vjp`` as a bare decorator, or
    ``functools.partial(jax.custom_vjp, ...)``."""
    if isinstance(node, ast.Attribute) and node.attr == "custom_vjp":
        return True
    if isinstance(node, ast.Name) and node.id == "custom_vjp":
        return True
    if isinstance(node, ast.Call):
        if _callee_name(node) == "custom_vjp":
            return True
        if _callee_name(node) == "partial" and node.args:
            return _is_custom_vjp_expr(node.args[0])
    return False


def _negated_key_args(call: ast.Call):
    """Yield ``-x`` arguments (non-literal unary minus) of a keyed call."""
    for arg in call.args:
        if (
            isinstance(arg, ast.UnaryOp)
            and isinstance(arg.op, ast.USub)
            and not isinstance(arg.operand, ast.Constant)
            # -x.inf spellings are L003's business, not a key negation
            and not (isinstance(arg.operand, ast.Attribute) and arg.operand.attr == "inf")
        ):
            yield arg


def lint_source(
    source: str,
    path: str,
    *,
    collect_vjp_owners: Optional[List[str]] = None,
) -> List[LintViolation]:
    """Lint one file's source.  ``path`` is repo-relative (used for the
    per-file rule scopes).  If ``collect_vjp_owners`` is given, the
    outermost function name owning each ``custom_vjp`` is appended to it
    for the cross-file L005 check."""
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return [LintViolation("L000", path, e.lineno or 0, f"syntax error: {e.msg}")]
    sup = _suppressions(source)
    posix = Path(path).as_posix()
    in_kernels = "/kernels/" in posix or posix.startswith("kernels/")
    sanctioned = any(posix.endswith(s) for s in SANCTIONED_SENTINEL_FILES)
    launch_catch_ok = any(posix.endswith(s) for s in SANCTIONED_LAUNCH_CATCH_FILES)
    wall_clock_ok = any(d in posix for d in SANCTIONED_WALL_CLOCK_DIRS) or any(
        posix.endswith(s) for s in SANCTIONED_WALL_CLOCK_FILES
    )
    vs: List[LintViolation] = []

    # ancestry map so custom_vjp sites resolve to their outermost function
    parents: Dict[ast.AST, ast.AST] = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            parents[child] = node

    def _outermost_function(node: ast.AST) -> Optional[str]:
        owner = None
        cur: Optional[ast.AST] = node
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                owner = cur.name
            cur = parents.get(cur)
        return owner

    for node in ast.walk(tree):
        # --- L001: literal interpret= at call sites -----------------------
        if isinstance(node, ast.Call):
            for kw in node.keywords:
                if (
                    kw.arg == "interpret"
                    and isinstance(kw.value, ast.Constant)
                    and isinstance(kw.value.value, bool)
                ):
                    line = kw.value.lineno
                    if not _suppressed(sup, line, "L001"):
                        vs.append(LintViolation(
                            "L001", path, line,
                            f"literal interpret={kw.value.value} at a call "
                            f"site — route through ops.default_interpret() "
                            f"(backend-derived) instead"))

        # --- L002: -x key negation for descending order -------------------
        if isinstance(node, ast.Call) and _KEYED_CALL.search(_callee_name(node)):
            for arg in _negated_key_args(node):
                if not _suppressed(sup, arg.lineno, "L002"):
                    vs.append(LintViolation(
                        "L002", path, arg.lineno,
                        f"unary minus on a key argument of "
                        f"{_callee_name(node)}() — int keys overflow at "
                        f"iinfo.min; use repro.core.merge_path.flip_desc"))

        # --- L003: raw sentinel construction outside the helper -----------
        if not sanctioned:
            if isinstance(node, ast.Call) and _callee_name(node) in ("iinfo", "finfo"):
                if not _suppressed(sup, node.lineno, "L003"):
                    vs.append(LintViolation(
                        "L003", path, node.lineno,
                        f"raw {_callee_name(node)}() sentinel construction — "
                        f"use repro.core.merge_path.max_sentinel / "
                        f"min_sentinel"))
            if (
                isinstance(node, ast.Attribute)
                and node.attr == "inf"
                and isinstance(node.value, ast.Name)
                and node.value.id in ("np", "jnp", "numpy", "math")
            ):
                if not _suppressed(sup, node.lineno, "L003"):
                    vs.append(LintViolation(
                        "L003", path, node.lineno,
                        f"raw {node.value.id}.inf sentinel — use "
                        f"repro.core.merge_path.max_sentinel / min_sentinel"))

        # --- L004: per-iteration kernel launches in kernels/ --------------
        if in_kernels and isinstance(node, ast.For):
            if not _suppressed(sup, node.lineno, "L004"):
                for inner in ast.walk(node):
                    if isinstance(inner, ast.Call) and _LAUNCH_CALL.search(
                        _callee_name(inner)
                    ):
                        vs.append(LintViolation(
                            "L004", path, node.lineno,
                            f"Python for-loop launching "
                            f"{_callee_name(inner)}() per iteration — fold "
                            f"the pairing into the kernel grid (one launch "
                            f"per round)"))
                        break

        # --- L006: broad except around a kernel launch outside the guard --
        if not launch_catch_ok and isinstance(node, ast.Try):
            launches = any(
                isinstance(inner, ast.Call) and _LAUNCH_CALL.search(_callee_name(inner))
                for stmt in node.body
                for inner in ast.walk(stmt)
            )
            if launches:
                for handler in node.handlers:
                    broad = handler.type is None or (
                        isinstance(handler.type, (ast.Name, ast.Attribute))
                        and (
                            handler.type.id
                            if isinstance(handler.type, ast.Name)
                            else handler.type.attr
                        )
                        in ("Exception", "BaseException")
                    )
                    if broad and not _suppressed(sup, handler.lineno, "L006"):
                        vs.append(LintViolation(
                            "L006", path, handler.lineno,
                            "broad except around a kernel launch — only the "
                            "guard layer (repro.runtime.resilience."
                            "guarded_call) may catch launch failures; route "
                            "the call through guarded dispatch instead"))

        # --- L007: raw wall-clock reads outside the telemetry layer -------
        if not wall_clock_ok:
            hit_name = None
            if (
                isinstance(node, ast.Attribute)
                and node.attr in _WALL_CLOCK_NAMES
                and isinstance(node.value, ast.Name)
                and node.value.id == "time"
            ):
                hit_name = f"time.{node.attr}"
            elif isinstance(node, ast.ImportFrom) and node.module == "time":
                for alias in node.names:
                    if alias.name in _WALL_CLOCK_NAMES:
                        hit_name = f"from time import {alias.name}"
                        break
            if hit_name is not None and not _suppressed(sup, node.lineno, "L007"):
                vs.append(LintViolation(
                    "L007", path, node.lineno,
                    f"raw {hit_name} outside src/repro/telemetry/ and "
                    f"benchmarks/_timing.py — wall-clock reads break the "
                    f"deterministic-tick trace guarantee; use "
                    f"repro.telemetry.wall_seconds (or a telemetry span)"))

        # --- L005 collection: custom_vjp owners ---------------------------
        if collect_vjp_owners is not None:
            hit = None
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if any(_is_custom_vjp_expr(d) for d in node.decorator_list):
                    hit = node
            if hit is not None:
                owner = _outermost_function(hit) or hit.name
                collect_vjp_owners.append(owner)

    return vs


def _grad_test_corpus(repo_root: Path) -> str:
    """Concatenated text of every tests/*.py that exercises gradients."""
    chunks = []
    tests = repo_root / "tests"
    if tests.is_dir():
        for p in sorted(tests.glob("*.py")):
            text = p.read_text()
            if "grad" in text:
                chunks.append(text)
    return "\n".join(chunks)


def vjp_pairing_violations(
    owners: Sequence[Tuple[str, str, int]], grad_corpus: str
) -> List[LintViolation]:
    """L005: each (owner, path, line) must appear word-boundary in the
    gradient test corpus, with leading underscores stripped (private
    forwards are tested through their public wrapper's name)."""
    vs = []
    for owner, path, line in owners:
        public = owner.lstrip("_")
        if not re.search(rf"\b{re.escape(public)}\b", grad_corpus):
            vs.append(LintViolation(
                "L005", path, line,
                f"custom_vjp forward {owner!r} has no registered gradient "
                f"test (no tests/*.py mentioning 'grad' references "
                f"{public!r})"))
    return vs


def _lint_paths(root: Path) -> List[Path]:
    """Files lint_tree covers: ``src/**`` and ``benchmarks/**`` (the bench
    timers are inside the L007 wall-clock perimeter)."""
    paths = sorted((root / "src").rglob("*.py"))
    bench = root / "benchmarks"
    if bench.is_dir():
        paths += sorted(bench.rglob("*.py"))
    return paths


def lint_tree(repo_root: Optional[Path] = None) -> List[LintViolation]:
    """Lint ``src/**/*.py`` + ``benchmarks/**/*.py`` plus the cross-file
    L005 pairing."""
    root = Path(repo_root) if repo_root else REPO_ROOT
    vs: List[LintViolation] = []
    owners: List[Tuple[str, str, int]] = []
    for p in _lint_paths(root):
        rel = p.relative_to(root).as_posix()
        per_file: List[str] = []
        vs += lint_source(p.read_text(), rel, collect_vjp_owners=per_file)
        # re-walk for line numbers of the collected owners
        if per_file:
            tree = ast.parse(p.read_text(), filename=rel)
            lines = {}
            for node in ast.walk(tree):
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    lines.setdefault(node.name, node.lineno)
            for owner in per_file:
                owners.append((owner, rel, lines.get(owner, 0)))
    vs += vjp_pairing_violations(owners, _grad_test_corpus(root))
    return vs


def main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--root", default=str(REPO_ROOT), help="repo root to lint")
    args = ap.parse_args(argv)
    vs = lint_tree(Path(args.root))
    if vs:
        for v in vs:
            print(f"lint: {v}", file=sys.stderr)
        print(f"lint: FAIL ({len(vs)} violations)", file=sys.stderr)
        return 1
    print("lint: OK (AST rules L001-L007 clean)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
