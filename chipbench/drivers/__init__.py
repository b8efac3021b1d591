"""Drivers: one file per kind of traffic, named by a traffic file's ``driver``."""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class RunResult:
    """What a driver hands back to run.py."""
    metrics: dict                 # end-to-end metrics by name (host clock)
    attempted: int
    failed: int
    window_s: float
    counts: dict                  # the driver's counts over the window
    stats: dict                   # the program's counters over the window
    readings: dict                # numbers to compare with their limits
    observed: dict                # further numbers of the comparison, not judged
    memory_peak_bytes: int | None
    trace: object = None          # trace_reduce.Trace of the traced span
