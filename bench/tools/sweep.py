#!/usr/bin/env python3
"""Run one cell several times, one process per run, and keep each result.

    python3 bench/tools/sweep.py --workload <cell> --seeds 11,12,13 \
        --seconds 10 [--trace 1] --out chiprun_out/<file>.jsonl

Each run is ``bench/run.py`` in a child process, one after the other (a
chip belongs to one process at a time; this parent never imports JAX).
Each line of ``--out`` holds the seed, the exit code, the wall time of the
process, the result line and the end of standard error.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

RUN = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "run.py")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", required=True)
    ap.add_argument("--timeout", type=float, default=1200)
    args = ap.parse_args(argv)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    worst = 0
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        cmd = [sys.executable, RUN, "--workload", args.workload, "--seed",
               str(seed), "--seconds", str(args.seconds), "--trace",
               str(args.trace)]
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=args.timeout)
        wall = time.perf_counter() - t0
        lines = proc.stdout.strip().splitlines()
        result = None
        if proc.returncode == 0 and lines:
            result = json.loads(lines[-1])
        rec = {"workload": args.workload, "seed": seed, "trace": args.trace,
               "rc": proc.returncode, "wall_s": wall, "result": result,
               "stderr_tail": proc.stderr[-3000:]}
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")
        short = {k: v["value"] for k, v in (result or {}).get(
            "metrics", {}).items()}
        print(f"{args.workload} seed={seed} rc={proc.returncode} "
              f"wall={wall:.1f}s correct={(result or {}).get('correct')} "
              f"{short} checks={(result or {}).get('checks')}", flush=True)
        worst = max(worst, proc.returncode)
    return worst


if __name__ == "__main__":
    sys.exit(main())
