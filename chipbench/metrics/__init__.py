"""Per-layer metric readers, one file each, named as the metric.

A reader module has ``UNIT``, ``LAYER``, ``MOVES`` and ``read(r)``, which
takes a :class:`Readings` and returns the metric's value, or ``None`` where
it finds nothing to read (the harness then leaves the metric out).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional


@dataclasses.dataclass
class Readings:
    counters: Dict[str, Any]        # the system module's counts and host-clock readings
    trace: Optional[Any]            # tracing.TraceSummary of the traced window
    config: dict
    traffic: dict
    peak: dict                      # peaks.json entry of this device
