"""Fail if any public API symbol is missing from docs/architecture.md.

Public surface checked:

* every name in ``repro.core.__all__`` (the library's primary boundary);
* every public function defined in ``repro.kernels.ops`` (the kernel
  dispatch surface), plus its documented module-level switches;
* every name in ``repro.analysis.__all__`` (the static checker's surface);
* every name in ``repro.runtime.__all__`` (the self-healing execution
  layer: guarded dispatch, fault injection, fault tolerance) plus the
  serving degradation surface (``Request`` / ``ServingReport``);
* every name in ``repro.telemetry.__all__`` (spans, metrics, trace
  export).

Wired to ``make docs-check`` (and ``make ci``), so a PR that adds a public
symbol without documenting it fails CI.  Symbols may be documented in
``docs/architecture.md``, ``docs/robustness.md``, or
``docs/observability.md`` (the pages are searched as one corpus).  The check requires each symbol as a whole word
(word-boundary regex, so ``merge`` is not satisfied by
``merge_batched``) — the "Public API index" section lists every symbol
by name.
"""

from __future__ import annotations

import inspect
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))

DOCS = (
    os.path.join(ROOT, "docs", "architecture.md"),
    os.path.join(ROOT, "docs", "robustness.md"),
    os.path.join(ROOT, "docs", "observability.md"),
)


def public_symbols() -> dict:
    """Map of ``module -> sorted public symbol names`` to require."""
    import repro.analysis as analysis
    import repro.core as core
    import repro.kernels.ops as ops
    import repro.runtime as runtime
    import repro.telemetry as telemetry

    ops_names = sorted(
        name
        for name, obj in vars(ops).items()
        if not name.startswith("_")
        and inspect.isfunction(obj)
        and obj.__module__ == "repro.kernels.ops"
    )
    ops_names.append("default_interpret")  # re-exported from kernels.merge_path
    return {
        "repro.core": sorted(core.__all__),
        "repro.kernels.ops": ops_names,
        "repro.analysis": sorted(analysis.__all__),
        "repro.runtime": sorted(runtime.__all__),
        "repro.serving.engine": ["Request", "ServingReport", "ServingEngine"],
        "repro.telemetry": sorted(telemetry.__all__),
    }


def main() -> int:
    missing_docs = [d for d in DOCS if not os.path.exists(d)]
    if missing_docs:
        print(f"docs-check: FAIL — missing doc page(s): {', '.join(missing_docs)}")
        return 1
    text = "\n".join(open(d).read() for d in DOCS)
    missing = []
    for module, names in public_symbols().items():
        for name in names:
            if not re.search(rf"\b{re.escape(name)}\b", text):
                missing.append(f"{module}.{name}")
    if missing:
        print("docs-check: FAIL — public symbols missing from docs/ "
              "(architecture.md + robustness.md + observability.md):")
        for m in missing:
            print(f"  - {m}")
        return 1
    total = sum(len(v) for v in public_symbols().values())
    print(f"docs-check: OK ({total} public symbols documented)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
