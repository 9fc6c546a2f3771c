"""Dependent device steps per population in the window: the lane
program's outer cycles plus the iterations of its completion and wave
loops (the program's own counts, ``bench/lane_calls.py``)."""
from benchlib import bench_file


def read(ctx):
    lc = bench_file("lane_calls.py")
    calls = lc.window_calls(ctx)
    if calls is None:
        return None
    return sum(lc.steps(c) for c in calls) / len(calls)
