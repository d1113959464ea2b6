"""The accelerator: refuse anything but a TPU, name it, look up its peaks,
read its memory, and keep JAX's compilation cache inside the checkout."""

from __future__ import annotations

import json
import os
import sys
from typing import Dict, List

from . import registry


def fail(msg: str) -> None:
    """End the run with no result line."""
    print(f"bench: FAIL: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def import_program() -> None:
    """Put the program under test (``<checkout>/src``) on the path."""
    src = os.path.join(registry.CHECKOUT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        fail(f"no program under {src}: run from a checkout of the repository")
    sys.path.insert(0, src)


def enable_compile_cache() -> str:
    """JAX's persistent cache in ``$JAX_COMPILATION_CACHE_DIR`` or at the
    fixed ``<checkout>/.jax_cache``; every program is cached, however fast
    it compiled."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(registry.CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_tpu(chips: int) -> List:
    """The first ``chips`` TPU devices; any other backend ends the run."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX's devices are {devices[0].platform} x{len(devices)}")
    if len(devices) < chips:
        fail(f"the cell needs {chips} TPU chips, JAX sees {len(devices)}")
    return devices[:chips]


def describe(devices) -> Dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}


def tag(devices) -> str:
    """Prefix of every line a run prints: the device it ran on."""
    d = describe(devices)
    return f"[{d['platform']} {d['kind']} x{d['count']}]"


def peaks(kind: str) -> Dict[str, float]:
    with open(os.path.join(registry.BENCH, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        fail(f"device kind {kind!r} is not in peaks.json (have {sorted(table)})")
    return table[kind]


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip."""
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks_))
