"""What a run hands to the metric readers."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple


@dataclasses.dataclass
class Record:
    kind: str  # the traffic's kind: "library" | "serving"
    cell: Dict
    peaks: Dict[str, float]
    work: Any  # the configuration's work module
    setup_s: float
    window: Tuple[float, float]  # host clock (time.monotonic) of the measured window
    traced_window: Optional[Tuple[float, float]] = None  # host clock of the traced part
    trace: Any = None  # harness.trace.Trace of the traced part
    # library: one (start, end, output rows) per call
    calls: List[Tuple[float, float, int]] = dataclasses.field(default_factory=list)
    # serving: one dict per request and per engine step (see kinds/serving.py)
    requests: List[Dict] = dataclasses.field(default_factory=list)
    steps: List[Dict] = dataclasses.field(default_factory=list)
    attempted: int = 0
    failed: int = 0
    memory_peak_bytes: int = 0
    checks: Dict[str, Dict[str, float]] = dataclasses.field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def layer_window(self) -> Tuple[float, float]:
        """The host-clock interval per-layer metrics read: the traced part."""
        return self.traced_window or self.window
