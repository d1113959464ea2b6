"""The profiler trace of a run and its reduction to device time.

The run wraps the window it traces in a ``bench.trace`` annotation and its
own calls into the program in ``bench.*`` annotations
(``jax.profiler.TraceAnnotation``), so host spans and device operations
share the profiler's clock.  :class:`Trace` keeps only what the metrics
read: each device operation (device, name, start, end), the ``bench.*``
host spans and the traced window, all in seconds.
"""

from __future__ import annotations

import bisect
import collections
import contextlib
import glob
import gzip
import json
import os
import shutil
from typing import Dict, List, Optional, Tuple

from . import stats

PREFIX = "bench."
WINDOW = "bench.trace"
TRACE_DIR = ".bench_trace"  # under the checkout, git-ignored, replaced by every traced run


@contextlib.contextmanager
def capture(path: str):
    """Profile the body into ``path`` (emptied first)."""
    import jax

    shutil.rmtree(path, ignore_errors=True)
    jax.profiler.start_trace(path)
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            yield
    finally:
        jax.profiler.stop_trace()


def _device_id(plane_name: str) -> Optional[int]:
    # "/device:TPU:0" -> 0; host and other planes -> None
    head, _, idx = plane_name.rpartition(":")
    if head.startswith("/device:") and idx.isdigit() and "CPU" not in head:
        return int(idx)
    return None


class Trace:
    def __init__(self, ops, spans, window):
        self.ops: List[Tuple[int, str, float, float]] = ops  # (device, name, start, end)
        self.spans: List[Tuple[str, float, float]] = spans  # (name, start, end)
        self.window: Tuple[float, float] = window
        self.devices = sorted({d for d, *_ in ops})

    # -- loading --------------------------------------------------------

    @classmethod
    def from_xplane(cls, path: str) -> "Trace":
        """Read the newest ``*.xplane.pb`` under ``path``.  An operation is
        named ``<program>/<instruction>`` (``jit_topk_sample/%while.87``)."""
        from jax.profiler import ProfileData

        files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        pd = ProfileData.from_file(files[-1])
        ops, spans = [], []
        for plane in pd.planes:
            dev = _device_id(plane.name)
            if dev is None:
                if plane.name.startswith("/host"):
                    for line in plane.lines:
                        spans.extend((e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                                     for e in line.events if e.name.startswith(PREFIX))
                continue
            lines = {line.name: list(line.events) for line in plane.lines}
            mods = sorted((e.start_ns, e.end_ns, e.name.split("(")[0]) for e in lines.get("XLA Modules", []))
            starts = [m[0] for m in mods]
            for e in lines.get("XLA Ops", []):
                i = bisect.bisect_right(starts, e.start_ns) - 1
                mod = mods[i][2] if i >= 0 and e.start_ns <= mods[i][1] else "?"
                ops.append((dev, f"{mod}/{e.name.split(' = ')[0]}", e.start_ns * 1e-9, e.end_ns * 1e-9))
        windows = [(s, e) for n, s, e in spans if n == WINDOW]
        if not windows:
            raise ValueError(f"no {WINDOW} span in {files[-1]}")
        return cls(ops, [sp for sp in spans if sp[0] != WINDOW], windows[0])

    def to_json(self, path: str) -> None:
        """Write the kept events (gzip JSON; the tests read such a file)."""
        with gzip.open(path, "wt") as f:
            json.dump({"window": self.window, "ops": self.ops, "spans": self.spans}, f)

    @classmethod
    def from_json(cls, path: str) -> "Trace":
        with gzip.open(path, "rt") as f:
            d = json.load(f)
        return cls([tuple(o) for o in d["ops"]], [tuple(s) for s in d["spans"]], tuple(d["window"]))

    # -- reduction ------------------------------------------------------

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def _intervals(self, dev: int):
        return [(s, e) for d, _, s, e in self.ops if d == dev]

    def busy_s(self, lo: Optional[float] = None, hi: Optional[float] = None) -> float:
        """Seconds of ``[lo, hi]`` (default: the window) in which some
        operation ran on a device, averaged over the devices."""
        lo = self.window[0] if lo is None else lo
        hi = self.window[1] if hi is None else hi
        if not self.devices:
            return 0.0
        return sum(stats.covered(self._intervals(d), lo, hi) for d in self.devices) / len(self.devices)

    def idle_share(self) -> Optional[float]:
        """Share of the window with no operation running, in percent."""
        if not self.devices or self.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.busy_s() / self.window_s)

    def span_intervals(self, name: str) -> List[Tuple[float, float]]:
        return [(s, e) for n, s, e in self.spans if n == name and e > self.window[0] and s < self.window[1]]

    def top_ops(self, n: int = 10) -> List[List]:
        """The ``n`` device operations that took most time in the window,
        summed over their runs and averaged over the devices."""
        lo, hi = self.window
        tot: Dict[str, float] = collections.defaultdict(float)
        for _, name, s, e in self.ops:
            tot[name] += max(0.0, min(e, hi) - max(s, lo))
        k = max(1, len(self.devices))
        return [[name, t / k] for name, t in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def label(self, t: float) -> str:
        """The innermost ``bench.*`` host span running at ``t``."""
        best = None
        for name, s, e in self.spans:
            if s <= t <= e and (best is None or e - s < best[1]):
                best = (name, e - s)
        return best[0] if best else "outside bench spans"

    def idle_gaps(self, n: int = 10) -> List[List]:
        """The ``n`` longest stretches with no operation on device 0 (or
        the first traced device), each named by what the host was doing."""
        if not self.devices:
            return []
        lo, hi = self.window
        gs = stats.gaps(self._intervals(self.devices[0]), lo, hi)
        gs.sort(key=lambda g: g[0] - g[1])
        return [[self.label((s + e) / 2), e - s] for s, e in gs[:n]]

    def breakdown(self) -> Dict[str, List[List]]:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


TRACE_SECONDS = 10.0  # the profiler covers at most the window's first this many seconds
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration", "/jax/compilation_cache/cache_retrieval_time_sec")
_counts = {e: 0 for e in COMPILE_EVENTS}  # per process: a JAX listener cannot be removed
_listening = False


def _count(event: str, duration: float, **kw) -> None:
    if event in _counts:
        _counts[event] += 1


def compiles() -> tuple:
    """(programs compiled, programs loaded from the persistent cache) so far."""
    global _listening
    if not _listening:
        import jax

        jax.monitoring.register_event_duration_secs_listener(_count)
        _listening = True
    return tuple(_counts[e] for e in COMPILE_EVENTS)


class Window:
    """The measured window; when traced, the profiler covers its first
    ``min(seconds, TRACE_SECONDS)`` and is stopped inside it."""

    def __init__(self, seconds: float, traced: bool, path: str):
        self.seconds, self.traced, self.path = seconds, traced, path
        self.start = self.end = None
        self.traced_window = None
        self._ctx = None

    def open(self) -> float:
        import time

        self._compiles0 = compiles()
        if self.traced:
            self._ctx = capture(self.path)
            self._ctx.__enter__()
        self.start = time.monotonic()
        return self.start

    def _stop_trace(self, now: float) -> None:
        self._ctx.__exit__(None, None, None)
        self._ctx = None
        self.traced_window = (self.start, now)

    def over(self, now: float) -> bool:
        """Called after each unit of work returns at ``now``."""
        if self._ctx is not None and now - self.start >= min(self.seconds, TRACE_SECONDS):
            self._stop_trace(now)
        if now - self.start >= self.seconds:
            self.end = now
            self.compiled = tuple(b - a for a, b in zip(self._compiles0, compiles()))
            return True
        return False

    def close(self) -> None:
        if self._ctx is not None:  # left by an exception
            self._ctx.__exit__(None, None, None)
            self._ctx = None

    def read(self) -> Optional["Trace"]:
        return Trace.from_xplane(self.path) if self.traced else None
