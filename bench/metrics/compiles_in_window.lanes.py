"""Lane-program compilations inside the window: the ``compiles`` of the
window's calls (the program's own count, ``bench/lane_calls.py``).  The
warm-up compiles every bucket the window runs, so a sound run reads 0."""
from benchlib import bench_file


def read(ctx):
    calls = bench_file("lane_calls.py").window_calls(ctx)
    if calls is None:
        return None
    return sum(c["compiles"] for c in calls)
