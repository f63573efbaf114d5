"""The program's counters over a traced run's window (``repro.obs``).

``repro.obs`` counts only while a profiler trace records, and a run of
``bench.run`` traces only its window: ``of_window`` gives those counts,
or ``None`` where the program has no such counters or the window made no
counted read.
"""
from __future__ import annotations


def of_window() -> dict | None:
    try:
        from repro import obs
    except ImportError:
        return None
    counts = obs.snapshot()
    return counts if counts["keys"] and counts["lane_slots"] else None
