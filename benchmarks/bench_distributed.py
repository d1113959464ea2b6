"""Distributed Merge Path benchmark: gather vs window exchange on a forced
8-device host mesh.

The interesting number is **bytes moved per device**, not wall-clock: on
the host-emulated mesh every "collective" is a memcpy, so wall time mostly
measures trace/compile overhead, while the bytes column is exactly what an
ICI would carry.  Per-device exchanged bytes come from
``repro.core.distributed.exchange_bytes``:

* ``gather``: every device receives the other P-1 shards — O(N).
* ``window`` payload: each device receives exactly its output segment's
  windows (``alen + blen = seg = N/P`` elements) plus the collective
  bisection's probe traffic — O(N/P).
* ``window`` wire (padded): what the dense static-shape ``all_to_all``
  ships with pieces padded to the provable max-piece bound; a
  ``ragged_all_to_all`` backend would collapse this to the payload number.

Because the main process must keep a single device (see tests/conftest),
the measurement runs in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` and reports JSON on
stdout.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from typing import Dict, List

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_INNER = """
import json, sys
import numpy as np, jax, jax.numpy as jnp
from repro.core import distributed_merge, distributed_sort
from repro.core.distributed import exchange_bytes
from benchmarks._timing import timeit
from repro.telemetry import get_telemetry
import repro.runtime.faults as faults
import repro.runtime.resilience as res

P = 8
n = int(sys.argv[1])
iters = int(sys.argv[2])
rng = np.random.default_rng(0)
na = nb = n // 2
a = jnp.asarray(np.sort(rng.standard_normal(na)).astype(np.float32))
b = jnp.asarray(np.sort(rng.standard_normal(nb)).astype(np.float32))
rows = []

eb = exchange_bytes(na, nb, P, 4)
ref = None
for exchange in ("gather", "window"):
    us = timeit(
        lambda: distributed_merge(a, b, exchange=exchange),
        iters=iters, warmup=1,
        label=f"distributed/merge_{exchange}_n{n}_p{P}",
    )
    out = np.asarray(distributed_merge(a, b, exchange=exchange))
    if ref is None:
        ref = out
    assert np.array_equal(out, ref), "exchange flavors disagree"
    bytes_dev = eb[exchange] if exchange == "gather" else eb["window_payload"]
    derived = (
        f"bytes/device={bytes_dev} total_bytes={(na + nb) * 4}"
        + ("" if exchange == "gather" else f" wire_padded={eb['window_wire_padded']}")
    )
    rows.append({
        "name": f"distributed/merge_{exchange}_n{n}_p{P}",
        "us_per_call": us,
        "derived": derived,
    })

x = jnp.asarray(rng.standard_normal(n).astype(np.float32))
for combine in ("onepass", "tournament"):
    us = timeit(
        lambda: distributed_sort(x, combine=combine)[0],
        iters=iters, warmup=1,
        label=f"distributed/sort_{combine}_n{n}_p{P}",
    )
    rows.append({
        "name": f"distributed/sort_{combine}_n{n}_p{P}",
        "us_per_call": us,
        "derived": "one all_to_all bucket round",
    })

hs = res.health_summary()
assert faults.active() or hs["totals"]["fallbacks"] == 0, hs
print(json.dumps({"rows": rows, "telemetry": get_telemetry().snapshot()}))
"""


def bench_distributed(rows: List[Dict], smoke: bool = False) -> None:
    """Run the distributed merge/sort benchmark in an 8-device subprocess."""
    n = 1 << 12 if smoke else 1 << 16
    iters = 2 if smoke else 5
    env = dict(os.environ)
    # the forced host mesh is CPU devices: the child must never reach for
    # an accelerator this process may already hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    # src for repro, repo root for benchmarks._timing
    env["PYTHONPATH"] = os.path.join(_ROOT, "src") + os.pathsep + _ROOT
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_INNER), str(n), str(iters)],
        env=env,
        capture_output=True,
        text=True,
        timeout=1200,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"bench_distributed subprocess failed:\n{proc.stdout}\n{proc.stderr}"
        )
    payload = json.loads(proc.stdout.strip().splitlines()[-1])
    rows.extend(payload["rows"])
    # fold the subprocess's counters/gauges/histograms (per-device window
    # sizes, exchange bytes, balance ratio, bench percentiles) into this
    # process's registry so run.py's telemetry summary carries them
    from repro.telemetry import get_telemetry

    get_telemetry().merge_snapshot(payload["telemetry"])
