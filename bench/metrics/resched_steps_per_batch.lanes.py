"""Iterations of the Alg. 3 stage's plan and eviction loops per
population in the window: the dependent device steps that Alg. 3 adds
inside the wave (the program's ``resched_steps``, ``bench/lane_calls.py``).
A program without that count gives nothing."""
from benchlib import bench_file


def read(ctx):
    calls = bench_file("lane_calls.py").window_calls(ctx)
    if calls is None:
        return None
    counts = [c["counts"] for c in calls]
    if not any("resched_steps" in c for c in counts):
        return None
    return sum(c.get("resched_steps", 0) for c in counts) / len(counts)
