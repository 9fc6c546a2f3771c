"""Scheduler-cycle throughput benchmark: array engine vs. seed object scans.

Measures the end-to-end cycle hot path of the discrete-event simulator —
pending-queue snapshot, wave placement (cached-buffer select + once-per-wave
``bind_wave`` commit) on the array engine vs. per-pod filter+select+bind on
the object engine, plus scale-in — on synthetic batch workloads at three
scales:

* ``small``  —    50 nodes x  2,000 pods (CI smoke; both engines run fully)
* ``medium`` —   500 nodes x 10,000 pods
* ``large``  — 2,000 nodes x 50,000 pods (the ROADMAP's production regime)

Because the two engines are bit-for-bit behaviour-identical (see
``tests/test_engine_parity.py``), cycle *i* performs identical scheduling
work under both — so cycle throughput (pods bound per second of cycle
compute, measured over the same post-warmup cycle window) is an
apples-to-apples comparison.  The object engine is capped to a bounded
number of cycles at the larger scales; the array engine additionally runs
the workload to completion for an end-to-end pods/second figure.

The array engine additionally runs each capped scale to completion for an
**end-to-end full-run** figure (arrival batching + bucketed completions +
incremental Table-5 sampling all live outside the capped cycle window, so
the full run is where they show up); the large scale records the speedup
against a committed reference wall time.

Usage::

    python benchmarks/bench_sched_throughput.py                  # all scales
    python benchmarks/bench_sched_throughput.py --scale small    # CI smoke
    python benchmarks/bench_sched_throughput.py --engines array  # skip seed
    python benchmarks/bench_sched_throughput.py --trace-replay   # + 100k trace

``--trace-replay`` (implied by ``--scale all``) replays a 100k-arrival
generated scenario (``repro.scenarios``) end-to-end through the columnar
ingest path — the regression gate for trace-native submission.

Writes ``BENCH_sched.json`` (override with ``--out``); prints
``name,us_per_call,derived`` CSV lines like the other benches.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

from repro.core import (Arrival, ExperimentSpec, PodKind, PodSpec,
                        Resources, gi, reset_id_counters)
from repro.core.experiment import build_simulation
from repro.core.simulation import SimConfig

# Average pod: 200m CPU / 614.4 MB on a 940m/3.5Gi node -> CPU binds first
# at ~4.7 pods/node.  Arrival rate targets ~70% steady-state occupancy.
_BATCH_TYPES = [
    PodSpec("bench_small", PodKind.BATCH, Resources(100, gi(0.3)),
            duration_s=120.0),
    PodSpec("bench_med", PodKind.BATCH, Resources(200, gi(0.6)),
            duration_s=180.0),
    PodSpec("bench_large", PodKind.BATCH, Resources(300, gi(0.9)),
            duration_s=240.0),
]
_AVG_CPU_M = 200.0
_AVG_DURATION_S = 180.0
_NODE_CPU_M = 940.0

SCALES = {
    #          nodes   pods   object-engine cycle cap (None = full run)
    "small": dict(nodes=50, pods=2_000, object_cap=None),
    "medium": dict(nodes=500, pods=10_000, object_cap=60),
    "large": dict(nodes=2_000, pods=50_000, object_cap=25),
}
WARMUP_CYCLES = 5
FULL_RUN_REPEATS = 3
# The small scale is the ci.sh regression smoke: like full_run, a single
# sample wobbles far more (observed ±25% on the 1-core container class)
# than the effects the -30% gate wants to resolve, so its per-engine
# measurement is the median of SMALL_SMOKE_REPEATS runs (cheap: ~0.3 s
# per array run).  The larger scales stay single-shot — their object-
# engine runs are the expensive part and they are not absolute-gated.
SMALL_SMOKE_REPEATS = 3

# Committed end-to-end full-run wall times at the large scale: PR 2
# (BENCH_sched.json @ ba0bc49, the telemetry/timeline reference) and PR 3
# (BENCH_sched.json @ b863234, the PodStore/SoA-pod-state reference).
PR2_FULL_RUN_WALL_S = {"large": 1.414}
PR3_FULL_RUN_WALL_S = {"large": 0.63}


def synth_arrivals(n_pods: int, n_nodes: int, seed: int = 0,
                   target_util: float = 0.7):
    """Poisson batch arrivals sized to keep the cluster ~target_util busy."""
    concurrency = target_util * n_nodes * (_NODE_CPU_M / _AVG_CPU_M)
    rate = concurrency / _AVG_DURATION_S
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rate, size=n_pods)
    times = np.cumsum(gaps)
    kinds = rng.integers(0, len(_BATCH_TYPES), size=n_pods)
    return [Arrival(float(t), _BATCH_TYPES[int(k)])
            for t, k in zip(times, kinds)]


def run_one(scale: str, engine: str, max_cycles=None) -> dict:
    # Fresh global id counters per run: both engines must start from the
    # same counter to perform identical per-cycle work (node ids order
    # lexicographically — same reason as test_engine_parity).
    reset_id_counters()
    # Measurement isolation (applies to every engine/scale equally): don't
    # let garbage from the previous run's ~50k-object graph bill its
    # collection pauses to this run's wall clock.
    gc.collect()

    cfg = SCALES[scale]
    spec = ExperimentSpec(
        workload=f"bench-{scale}", scheduler="best-fit", rescheduler="void",
        autoscaler="void", static_workers=cfg["nodes"], engine=engine,
        arrivals=synth_arrivals(cfg["pods"], cfg["nodes"]))
    sim = build_simulation(spec)
    sim.config = SimConfig(cycle_period_s=10.0, max_cycles=max_cycles,
                           record_cycle_times=True)
    t0 = time.perf_counter()
    result = sim.run()
    wall = time.perf_counter() - t0

    walls = np.asarray(sim.cycle_wall_s[WARMUP_CYCLES:])
    placed = np.asarray(sim.cycle_placed[WARMUP_CYCLES:])
    cycle_s = float(walls.sum()) if walls.size else 0.0
    out = {
        "engine": engine,
        "cycles": sim.n_cycles,
        "pods_placed_measured": int(placed.sum()),
        "cycle_compute_s": round(cycle_s, 4),
        "mean_cycle_ms": round(1e3 * float(walls.mean()), 3) if walls.size else 0.0,
        "p95_cycle_ms": round(1e3 * float(np.percentile(walls, 95)), 3) if walls.size else 0.0,
        "cycle_throughput_pods_per_s":
            round(float(placed.sum()) / cycle_s, 1) if cycle_s > 0 else 0.0,
        "wall_s": round(wall, 3),
        "completed": result.completed,
    }
    if max_cycles is None and result.completed:
        out["pods_per_s_end_to_end"] = round(cfg["pods"] / wall, 1)
    return out


def bench_scale(scale: str, engines) -> dict:
    cfg = SCALES[scale]
    row = {"nodes": cfg["nodes"], "pods": cfg["pods"], "engines": {}}
    cap = cfg["object_cap"]
    repeats = SMALL_SMOKE_REPEATS if scale == "small" else 1
    for engine in engines:
        # Both engines are measured over the same capped cycle window for the
        # speedup ratio; the array engine also runs to completion when the
        # object run was capped (for the end-to-end number).
        samples = sorted((run_one(scale, engine, max_cycles=cap)
                          for _ in range(repeats)),
                         key=lambda r: r["cycle_throughput_pods_per_s"])
        row["engines"][engine] = samples[len(samples) // 2]
        print(f"bench_sched.{scale}.{engine},"
              f"{1e3 * row['engines'][engine]['mean_cycle_ms']:.1f},"
              f"{row['engines'][engine]['cycle_throughput_pods_per_s']}")
    if "array" in engines and cap is not None:
        # Median of FULL_RUN_REPEATS: a single full-run sample wobbles by
        # +/-15% with interpreter/allocator state (the preceding capped
        # object run churns the heap), which is larger than the effects the
        # full-run gate wants to resolve.
        runs = sorted((run_one(scale, "array", max_cycles=None)
                       for _ in range(FULL_RUN_REPEATS)),
                      key=lambda r: r["wall_s"])
        full = runs[len(runs) // 2]
        entry = {
            "wall_s": full["wall_s"], "completed": full["completed"],
            "full_run_repeats": FULL_RUN_REPEATS,
            "pods_per_s_end_to_end": full.get("pods_per_s_end_to_end"),
        }
        prev = PR2_FULL_RUN_WALL_S.get(scale)
        if prev and full["wall_s"]:
            entry["pr2_wall_s"] = prev
            entry["speedup_vs_pr2"] = round(prev / full["wall_s"], 2)
        pr3 = PR3_FULL_RUN_WALL_S.get(scale)
        if pr3 and full["wall_s"]:
            entry["pr3_wall_s"] = pr3
            entry["speedup_vs_pr3"] = round(pr3 / full["wall_s"], 2)
            print(f"bench_sched.{scale}.full_run,"
                  f"{1e6 * full['wall_s']:.0f},{entry['speedup_vs_pr3']}")
        row["engines"]["array"]["full_run"] = entry
    if "array" in row["engines"] and "object" in row["engines"]:
        a = row["engines"]["array"]["cycle_throughput_pods_per_s"]
        o = row["engines"]["object"]["cycle_throughput_pods_per_s"]
        row["speedup_cycle_throughput"] = round(a / o, 1) if o else None
        print(f"bench_sched.{scale}.speedup,0,{row['speedup_cycle_throughput']}")
    return row


TRACE_REPLAY_JOBS = 100_000
TRACE_REPLAY_NODES = 2_000
TRACE_REPLAY_REPEATS = 3


def bench_trace_replay(n_jobs=TRACE_REPLAY_JOBS,
                       nodes=TRACE_REPLAY_NODES) -> dict:
    """Columnar trace replay at ingestion scale: a 100k-arrival heavy-tail
    scenario (``repro.scenarios``) runs end-to-end through
    ``Timeline`` → ``Orchestrator.submit_trace`` → ``PodStore.ingest_trace``
    on a static 2k-node cluster — the zero-per-arrival-object path this
    subsystem adds.  Reported wall time excludes trace generation (recorded
    separately as ``build_s``) and is the median of
    ``TRACE_REPLAY_REPEATS`` runs, same rationale as ``full_run``."""
    from repro.scenarios import HeavyTail

    cfg = HeavyTail(n_jobs=n_jobs, rate_per_s=30.0, cap_s=3600.0)
    t0 = time.perf_counter()
    trace = cfg.build(seed=0)
    build_s = time.perf_counter() - t0
    runs = []
    for _ in range(TRACE_REPLAY_REPEATS):
        reset_id_counters()
        gc.collect()
        spec = ExperimentSpec(trace=trace, scheduler="best-fit",
                              rescheduler="void", autoscaler="void",
                              static_workers=nodes)
        sim = build_simulation(spec)
        t0 = time.perf_counter()
        result = sim.run()
        runs.append((time.perf_counter() - t0, result.completed,
                     sim.n_cycles))
    runs.sort()
    wall, completed, cycles = runs[len(runs) // 2]
    out = {
        "scenario": trace.name, "n_jobs": n_jobs, "nodes": nodes,
        "repeats": TRACE_REPLAY_REPEATS,
        "trace_build_s": round(build_s, 3),
        "wall_s": round(wall, 3),
        "cycles": cycles,
        "completed": completed,
        "pods_per_s_end_to_end": round(n_jobs / wall, 1),
    }
    print(f"bench_sched.trace_replay,{1e6 * wall:.0f},"
          f"{out['pods_per_s_end_to_end']}")
    return out


def bench_sweep_pool(workers: int = 4, n_jobs: int = 300) -> dict:
    """Process-pool speedup of the `repro.search` cell runner on a fixed
    sweep grid (six scenario families × two autoscalers, full rescheduler
    chain), asserting the pool's rows are bit-identical to the serial
    ones before reporting the speedup.  Serial wall time is the unit of
    work; the pool must recover a real fraction of it or the hermetic-
    cell contract (per-process trace memoization, cheap spawn) regressed.
    """
    from repro.search.runner import CellSpec, run_cells

    scenarios = ("diurnal", "flash-crowd", "heavy-tail", "mix-ramp",
                 "scale-stress", "multi-tenant")
    cells = [CellSpec(scenario=sc, scheduler="best-fit", autoscaler=asc,
                      rescheduler="non-binding", seed=0, n_jobs=n_jobs)
             for sc in scenarios for asc in ("binding", "non-binding")]
    t0 = time.perf_counter()
    serial = run_cells(cells, workers=1)
    serial_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    pooled = run_cells(cells, workers=workers)
    pool_s = time.perf_counter() - t0
    strip = lambda rows: [{k: v for k, v in r.items() if k != "wall_s"}
                          for r in rows]
    identical = strip(serial) == strip(pooled)
    assert identical, "pool rows diverged from serial rows"
    speedup = serial_s / pool_s if pool_s > 0 else 0.0
    out = {"cells": len(cells), "n_jobs": n_jobs, "workers": workers,
           "serial_s": round(serial_s, 3), "pool_s": round(pool_s, 3),
           "speedup": round(speedup, 2), "identical": identical}
    print(f"bench_sched.sweep_pool,{1e6 * pool_s:.0f},{speedup:.2f}")
    return out


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scale", default="all",
                    choices=["all", "none"] + list(SCALES))
    ap.add_argument("--engines", default="array,object",
                    help="comma-separated subset of {array,object}")
    ap.add_argument("--trace-replay", action="store_true",
                    help="also run the 100k-arrival columnar trace-replay "
                         "bench (always included with --scale all)")
    ap.add_argument("--sweep-pool", action="store_true",
                    help="also measure the search cell runner's process-"
                         "pool speedup vs serial (always with --scale all)")
    ap.add_argument("--pool-workers", type=int, default=4)
    ap.add_argument("--out", default="BENCH_sched.json")
    args = ap.parse_args(argv)

    engines = [e.strip() for e in args.engines.split(",") if e.strip()]
    bad = [e for e in engines if e not in ("array", "object")]
    if bad or not engines:
        ap.error(f"--engines must name a non-empty subset of array,object "
                 f"(got {args.engines!r})")
    if args.scale == "all":
        scales = list(SCALES)
    elif args.scale == "none":   # e.g. --trace-replay standalone (CI gate)
        scales = []
    else:
        scales = [args.scale]
    report = {"bench": "sched_throughput",
              "generated_unix_s": int(time.time()),
              "warmup_cycles": WARMUP_CYCLES,
              "scales": {}}
    for scale in scales:
        report["scales"][scale] = bench_scale(scale, engines)
    if args.trace_replay or args.scale == "all":
        report["trace_replay"] = bench_trace_replay()
    if args.sweep_pool or args.scale == "all":
        report["sweep_pool"] = bench_sweep_pool(workers=args.pool_workers)
    # Preserve entries other benches merged into the same file (e.g. the
    # `manyworld` lane-evaluator entry from bench_manyworld.py) and, on a
    # partial --scale run, the scales this invocation didn't re-measure.
    if os.path.exists(args.out):
        try:
            with open(args.out) as f:
                prev = json.load(f)
        except (OSError, ValueError):
            prev = {}
        for key, value in prev.items():
            if key == "scales":
                for scale, row in value.items():
                    report["scales"].setdefault(scale, row)
            else:
                report.setdefault(key, value)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"# wrote {args.out}")
    return report


if __name__ == "__main__":
    main()
