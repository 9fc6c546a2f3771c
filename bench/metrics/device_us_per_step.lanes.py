"""Device busy time in the traced window per dependent step of the lane
program: the trace's busy union (``bench/xplane.py``) over the steps the
program counted in the window's calls (``bench/lane_calls.py``)."""
from benchlib import bench_file


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr["devices"] or tr["busy_s"] <= 0:
        return None
    lc = bench_file("lane_calls.py")
    calls = lc.window_calls(ctx)
    if calls is None:
        return None
    n_steps = sum(lc.steps(c) for c in calls)
    if n_steps <= 0:
        return None
    return 1e6 * tr["busy_s"] / n_steps
