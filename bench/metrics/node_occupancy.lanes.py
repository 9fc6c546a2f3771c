"""Share of the autoscaled lane program's node records in use in the
window: live nodes (booting, ready or tainted) of the active lanes,
summed over the outer cycles, over lanes times cycles times the node
pad (the program's own counts, ``bench/lane_calls.py``).  A program
without those counts, or a window of static fleets only, gives nothing."""
from benchlib import bench_file


def read(ctx):
    calls = bench_file("lane_calls.py").window_calls(ctx)
    if calls is None:
        return None
    counts = [c["counts"] for c in calls]
    offered = sum(c.get("node_cycles", 0) for c in counts)
    if offered <= 0:
        return None
    used = sum(c.get("active_node_cycles", 0) for c in counts)
    return 100.0 * used / offered
