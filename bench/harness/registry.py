"""Find everything a cell needs by the names in ``BENCHMARK.json``.

Nothing here knows a particular configuration, traffic mix or metric:

* configuration ``<c>``: ``configs/<c>.json`` (its sizes) and
  ``configs/<c>.py`` (its plain reference and how its data is made);
* traffic mix ``<t>``: ``traffic/<t>.json``, whose ``kind`` names the
  generator and runner ``kinds/<kind>.py``;
* metric ``<m>``: ``metrics/<m>.py`` with ``read(record) -> float | None``;
* work function of configuration ``<c>``: ``work/<c>.py``;
* library operation ``<o>`` (a library traffic's ``op``): ``ops/<o>.py``,
  which builds its operands, calls the program and holds its reference.

A cell added to ``BENCHMARK.json`` with new files beside these needs no
edit to any file that is already here.
"""

from __future__ import annotations

import importlib.util
import json
import os
from types import ModuleType
from typing import Dict, List

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH)


def _module(path: str) -> ModuleType:
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    name = "bench_" + os.path.relpath(path, BENCH).replace("/", "_").replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(checkout: str = CHECKOUT) -> dict:
    return _json(os.path.join(checkout, "BENCHMARK.json"))


def config(name: str) -> dict:
    return _json(os.path.join(BENCH, "configs", f"{name}.json"))


def traffic(name: str) -> dict:
    return _json(os.path.join(BENCH, "traffic", f"{name}.json"))


def reference(config_name: str) -> ModuleType:
    return _module(os.path.join(BENCH, "configs", f"{config_name}.py"))


def work(config_name: str) -> ModuleType:
    return _module(os.path.join(BENCH, "work", f"{config_name}.py"))


def kind(name: str) -> ModuleType:
    return _module(os.path.join(BENCH, "kinds", f"{name}.py"))


def op(name: str) -> ModuleType:
    return _module(os.path.join(BENCH, "ops", f"{name}.py"))


def reader(metric_name: str) -> ModuleType:
    return _module(os.path.join(BENCH, "metrics", f"{metric_name}.py"))


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, bench: dict) -> Dict:
    """The cell ``name`` with its configuration, traffic and metrics."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json (have {sorted(cells)})")
    w = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    cfg = _json(os.path.join(CHECKOUT, entry["file"]))
    per_layer: List[dict] = [m for m in bench["per_layer"] if _applies(m, name)]
    return {
        "name": name,
        "chips": int(w["chips"]),
        "config_name": w["config"],
        "config": cfg,
        "traffic_name": w["traffic"],
        "traffic": traffic(w["traffic"]),
        "end_to_end": [m for m in bench["end_to_end"] if _applies(m, name)],
        "per_layer": per_layer,
    }
