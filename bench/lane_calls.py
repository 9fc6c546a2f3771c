"""The lane program's own records of the window's calls, for the lane
cells' per-layer readers.

A lane cell's window is ``counters["batches"]`` calls of the program's
``run_cells_lanes``, and nothing calls it after the window (the check runs
only the plain reference), so the window's calls are the last that many
records of ``repro.manyworld.evaluator.lane_calls``.  A program without
that function (these files may run over a checkout older than it), or
with fewer records, gives nothing.
"""
from __future__ import annotations

from typing import List, Optional


def window_calls(ctx) -> Optional[List[dict]]:
    """The window's call records, oldest first; None outside the lane
    driver or when the program keeps no such records."""
    n = ctx["counters"].get("batches")
    if ctx["driver"] != "lanes" or not n:
        return None
    try:
        from repro.manyworld.evaluator import lane_calls
    except ImportError:
        return None
    calls = lane_calls(n)
    return calls if len(calls) == n else None


def steps(call: dict) -> int:
    """Dependent steps of one call: outer cycles plus the iterations of
    both inner loops, over its buckets."""
    c = call["counts"]
    return c["n_cycles"] + c["wave_steps"] + c["completion_steps"]
