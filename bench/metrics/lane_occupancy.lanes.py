"""Share of the lane program's lane-steps that did work in the window:
lanes with a pending candidate or a due completion in an inner-loop step,
plus active lanes in an outer cycle, over lanes times steps (the
program's own counts, ``bench/lane_calls.py``)."""
from benchlib import bench_file


def read(ctx):
    calls = bench_file("lane_calls.py").window_calls(ctx)
    if calls is None:
        return None
    offered = sum(c["counts"]["lane_steps"] for c in calls)
    if offered <= 0:
        return None
    used = sum(c["counts"]["busy_lane_steps"]
               + c["counts"]["active_lane_cycles"] for c in calls)
    return 100.0 * used / offered
