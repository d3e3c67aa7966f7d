"""What one run hands to the per-layer metric readers and to the result line."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional


@dataclass
class RunData:
    workload: str
    seed: int
    config: dict
    traffic: dict
    device_kind: str = ""
    # counters the driver read over the traced window (program counters,
    # counts of the benchmark's own calls)
    counters: Dict[str, float] = field(default_factory=dict)
    # ``trace.reduce`` of the traced window, or None in an untraced run
    trace: Optional[dict] = None
    # driver-specific facts a reader needs (shapes, lengths served)
    facts: Dict[str, Any] = field(default_factory=dict)
