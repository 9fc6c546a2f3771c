#!/usr/bin/env python3
"""Read a cell's control: the run's own comparison with the control in
the program's place, on several seeds, at the cell's own size.

    python3 bench/tools/control.py --workload <cell> --seeds 1,2,3 \
        [--out chiprun_out/<file>.jsonl]

Lane cells: the control is the plain reference computed with float32
memory, the precision step below the float64 that the deployment states.
It stands in for the program's ``run_cells`` under the timed path, in
set-up and window alike; the run then checks the window's rows as it
checks the program's, and ``rows_differing`` is printed for each seed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchlib import bench_file, use_program  # noqa: E402


def run_control(workload: str, seed: int, lanes: int | None = None,
                mem_dtype=None) -> dict:
    """One whole run of ``workload`` (set-up, a window of one population,
    the check) with the reference at ``mem_dtype`` (float32 unless given)
    in the program's place; ``lanes`` cuts the population for tests."""
    import numpy as np
    use_program()
    run = bench_file("run.py")
    driver = bench_file("drivers", "lanes.py")
    mem_dtype = np.float32 if mem_dtype is None else mem_dtype

    def control_rows(state):
        gen, ref, cfg = state["gen"], state["ref"], state["ctx"].config
        return [dict(ref.simulate(gen.job_columns(sk), cfg,
                                  mem_dtype=mem_dtype), wall_s=0.0)
                for sk in state["skeletons"]]

    real = driver._evaluate
    driver._evaluate = control_rows
    try:
        return run.run_cell(workload, seed, 0.0, False, require_tpu=False,
                            mix_overrides={"lanes": lanes} if lanes else None)
    finally:
        driver._evaluate = real


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        result = run_control(args.workload, seed)
        rec = {"workload": args.workload, "seed": seed,
               "correct": result["correct"], "attempted": result["attempted"],
               "checks": result["checks"],
               "wall_s": time.perf_counter() - t0}
        print(json.dumps(rec), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
