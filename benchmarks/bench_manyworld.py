"""Many-world lane evaluator benchmark: batched JAX lanes vs serial cells.

Measures the throughput of `repro.manyworld.run_cells_lanes` — thousands
of independent void/void cells lowered into one jitted fixed-shape cycle
program — against the serial `run_cell` reference on the same cell specs
(heavy-tail, best-fit, 40 jobs, 4 static nodes; one lane per seed).

Because the lane engine is bit-identical to the serial engine inside its
relaxed envelope (see ``tests/test_manyworld.py``), each lane performs
the same scheduling decisions as its serial twin — so lanes/second vs
cells/second is an apples-to-apples comparison.  The bench asserts that
parity on a row subset before reporting numbers.

Per lane count it records the *cold* wall (first call: jit trace +
compile for that ``(lanes, pods, nodes)`` shape) separately from the
*warm* wall (compile cache hit — the steady state a policy search lives
in), and derives ``speedup_vs_serial`` from the warm wall against the
serial per-cell time measured in the same process.

Usage::

    python benchmarks/bench_manyworld.py                     # 64/256/1024
    python benchmarks/bench_manyworld.py --lanes 256         # CI smoke
    python benchmarks/bench_manyworld.py --out /tmp/b.json   # elsewhere

Merges a ``manyworld`` entry into ``BENCH_sched.json`` (override with
``--out``; existing keys are preserved); prints
``name,us_per_call,derived`` CSV lines like the other benches.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

from repro.compile_cache import use_compile_cache
from repro.search.runner import CellSpec, run_cell

# One lane per seed: same scenario shape, different arrival realization —
# the policy-search shape (`run_cells(..., workers="lanes")` buckets
# these into a single (lanes, 64-pod, 4-node) jit program).
BENCH_SCENARIO = "heavy-tail"
BENCH_N_JOBS = 40
BENCH_NODES = 4
SERIAL_CELLS = 24
WARM_REPEATS = 3


def _cells(n_lanes: int):
    return [CellSpec(scenario=BENCH_SCENARIO, scheduler="best-fit",
                     autoscaler="void", rescheduler="void", seed=seed,
                     n_jobs=BENCH_N_JOBS, initial_workers=BENCH_NODES)
            for seed in range(n_lanes)]


def _strip(rows):
    # wall_s is timing, not behavior: serial measures one cell, a lane
    # reports its share of the batch wall.
    return [{k: v for k, v in r.items() if k != "wall_s"} for r in rows]


def bench_manyworld(lane_counts=(64, 256, 1024),
                    serial_cells=SERIAL_CELLS) -> dict:
    from repro.manyworld.evaluator import run_cells_lanes

    out = {
        "scenario": BENCH_SCENARIO, "n_jobs": BENCH_N_JOBS,
        "nodes": BENCH_NODES, "scheduler": "best-fit",
        "serial_cells_measured": serial_cells, "per_lanes": {},
    }
    # Serial baseline: per-cell wall over `serial_cells` cells, traces
    # pre-warmed (the lane path shares the same per-process trace cache,
    # so neither side is billed for scenario generation).
    sub = _cells(serial_cells)
    serial_rows = [run_cell(c) for c in sub]    # warm traces + result set
    serial_samples = []
    for _ in range(WARM_REPEATS):
        gc.collect()
        t0 = time.perf_counter()
        for cell in sub:
            run_cell(cell)
        serial_samples.append((time.perf_counter() - t0) / serial_cells)
    serial_per_cell_s = sorted(serial_samples)[len(serial_samples) // 2]
    out["serial_ms_per_cell"] = round(1e3 * serial_per_cell_s, 3)
    print(f"bench_manyworld.serial,{1e6 * serial_per_cell_s:.0f},"
          f"{1.0 / serial_per_cell_s:.0f}")

    for n_lanes in lane_counts:
        cells = _cells(n_lanes)
        gc.collect()
        t0 = time.perf_counter()
        rows = run_cells_lanes(cells)
        cold_s = time.perf_counter() - t0
        # Median of WARM_REPEATS: single samples wobble with box state
        # (same rationale as the sched bench's full_run/small medians).
        warm_samples = []
        for _ in range(WARM_REPEATS):
            t0 = time.perf_counter()
            rows = run_cells_lanes(cells)
            warm_samples.append(time.perf_counter() - t0)
        warm_s = sorted(warm_samples)[len(warm_samples) // 2]
        # Parity guard: the lanes must reproduce the serial rows bit-for-
        # bit, else the "same work" premise of the comparison is void.
        n_check = min(n_lanes, serial_cells)
        assert _strip(rows[:n_check]) == _strip(serial_rows[:n_check]), (
            f"lane rows diverged from serial rows at {n_lanes} lanes")
        assert all(r["completed"] for r in rows), "a bench lane ran to horizon"
        lanes_per_s = n_lanes / warm_s
        speedup = serial_per_cell_s * n_lanes / warm_s
        out["per_lanes"][str(n_lanes)] = {
            "cold_s": round(cold_s, 3),
            "warm_s": round(warm_s, 3),
            "lanes_per_s": round(lanes_per_s, 1),
            "speedup_vs_serial": round(speedup, 2),
        }
        print(f"bench_manyworld.lanes{n_lanes},{1e6 * warm_s:.0f},"
              f"{speedup:.2f}")
    return out


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--lanes", default="64,256,1024",
                    help="comma-separated lane counts to bench")
    ap.add_argument("--serial-cells", type=int, default=SERIAL_CELLS)
    ap.add_argument("--out", default="BENCH_sched.json")
    args = ap.parse_args(argv)
    lane_counts = tuple(int(x) for x in args.lanes.split(",") if x.strip())
    if not lane_counts:
        ap.error(f"--lanes must name at least one lane count "
                 f"(got {args.lanes!r})")

    use_compile_cache()
    report = bench_manyworld(lane_counts, serial_cells=args.serial_cells)
    report["generated_unix_s"] = int(time.time())
    # Merge, don't overwrite: the entry lives alongside the sched-
    # throughput report in the same committed baseline file.
    data = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            data = json.load(f)
    data["manyworld"] = report
    with open(args.out, "w") as f:
        json.dump(data, f, indent=2)
    print(f"# wrote {args.out}")
    return report


if __name__ == "__main__":
    main()
