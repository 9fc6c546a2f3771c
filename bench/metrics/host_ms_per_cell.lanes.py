"""Host time of the lane path per lane cell in the window: each call's
``lanes.call`` span less its ``lanes.wait`` (the host blocked on the
device), summed over the window's calls (the program's own spans,
``bench/lane_calls.py``), over the cells evaluated."""
from benchlib import bench_file


def read(ctx):
    calls = bench_file("lane_calls.py").window_calls(ctx)
    cells = ctx["counters"].get("cells")
    if calls is None or not cells:
        return None
    host_s = sum(c["wall_s"] - c["self_s"].get("lanes.wait", 0.0)
                 for c in calls)
    return 1e3 * host_s / cells
