#!/usr/bin/env python
"""Drive both device paths of the system once on one TPU chip, and check them.

All phases run in this one process: a chip belongs to one process at a
time.  Every input is generated from a seed.

1. **Device check.**  The first JAX device must be a TPU; otherwise the
   script exits 1 before running anything and prints no result.
2. **Lanes.**  For each scheduler, 256 heavy-tail cells at the family's
   native 2,000 jobs (seeds 0-255) on a static fleet of 64 ``m2.small``
   nodes, through ``run_cells(cells, workers="lanes")``: once cold (with
   compilation) and once warm.  Every cell sent must be ``lane_eligible``;
   schedulers outside the lane envelope are listed and not sent.  The
   first 16 rows must equal serial ``run_cell`` rows on every field but
   ``wall_s``.
3. **Forecaster.**  Train the mLSTM rate forecaster at its shipped width
   (d_model 32, 2 heads) for 60 steps on seeded flash-crowd and
   scale-stress windows (12 seeds each, native trace sizes); the loss must
   fall.  Drive one predictive
   flash-crowd experiment (600 jobs, non-binding rescheduler) with it to
   completion and audit the pod columns.  Compare the chip's predictions
   on 64 validation windows with the same parameters on the host CPU, both
   at float32 ("highest") matmul precision, within ``PRED_ATOL``.

The walls printed are one run's, not benchmark numbers.  The last line of
standard output is ``{"ok": true, "device": {...}}``; any failed check
exits 1 without it.

Usage::

    python chip_smoke.py                          # on one TPU chip
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny # small rehearsal on any
                                                  # backend; no result line
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

# Serial-vs-device agreement of the forecaster's log1p-rate predictions,
# both sides at float32 ("highest") matmul precision.
PRED_ATOL = 1e-4
SCHEDULER_WEIGHTS = (0.2, 0.5, 0.3)   # for the weighted scheduler

FULL = dict(cells=256, n_jobs=None, nodes=64, compare=16, train_steps=60,
            flash_jobs=600)
TINY = dict(cells=4, n_jobs=60, nodes=4, compare=2, train_steps=10,
            flash_jobs=300)


def lanes_phase(size, failures):
    from repro.core.scheduler import SCHEDULERS
    from repro.manyworld.evaluator import lane_eligible
    from repro.search.runner import _RESULT_FIELDS, CellSpec, run_cell, run_cells

    def same(a, b):
        keys = ("label", "n_jobs", "infeasible") + _RESULT_FIELDS
        return all(a[k] == b[k] for k in keys)

    narrowed = []
    for sched in SCHEDULERS:
        cells = [CellSpec(scenario="heavy-tail", scheduler=sched,
                          autoscaler="void", rescheduler="void", seed=seed,
                          n_jobs=size["n_jobs"],
                          initial_workers=size["nodes"],
                          scheduler_weights=(SCHEDULER_WEIGHTS
                                             if sched == "weighted" else None))
                 for seed in range(size["cells"])]
        eligible = [lane_eligible(c) for c in cells]
        if not any(eligible):
            narrowed.append(sched)
            continue
        if not all(eligible):
            failures.append(f"lanes {sched}: some cells are not lane-eligible")
            continue
        t0 = time.perf_counter()
        run_cells(cells, workers="lanes")
        cold_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        rows = run_cells(cells, workers="lanes")
        warm_s = time.perf_counter() - t0
        serial = [run_cell(c) for c in cells[:size["compare"]]]
        mismatches = sum(not same(a, b) for a, b in zip(rows, serial))
        completed = sum(bool(r["completed"]) for r in rows)
        print(f"lanes {sched}: cells={len(cells)} pods_per_cell="
              f"{rows[0]['n_jobs']} nodes={size['nodes']} cold_s={cold_s} "
              f"warm_s={warm_s} rows_compared={len(serial)} "
              f"mismatches={mismatches} completed={completed} "
              f"ran_to_horizon={len(rows) - completed}", flush=True)
        if mismatches:
            failures.append(f"lanes {sched}: {mismatches} rows differ "
                            f"from serial run_cell")
    print("lanes narrowed out (serial reference on every backend): "
          + (", ".join(narrowed) or "none"), flush=True)


def forecaster_phase(size, failures):
    import jax

    from repro.core import ExperimentSpec, build_simulation
    from repro.forecast import WindowConfig, make_dataset
    from repro.forecast import model as fmodel

    window = WindowConfig()
    # Native-size traces; seeds 3, 7 and 11 are the validation split.
    data = make_dataset(("flash-crowd", "scale-stress"), range(12), window)
    t0 = time.perf_counter()
    result = fmodel.train_forecaster(
        data["X_train"], data["y_train"], window=window,
        X_val=data["X_val"], y_val=data["y_val"], seed=0,
        steps=size["train_steps"])
    train_s = time.perf_counter() - t0
    k = max(1, size["train_steps"] // 6)
    first = float(np.mean(result.losses[:k]))
    last = float(np.mean(result.losses[-k:]))
    print(f"forecaster train: steps={size['train_steps']} d_model="
          f"{result.arch.d_model} heads={result.arch.num_heads} "
          f"loss_first={first} loss_last={last} val_mse={result.val_mse} "
          f"wall_s={train_s}", flush=True)
    if not last < first:
        failures.append(f"forecaster loss did not fall: {first} -> {last}")

    spec = ExperimentSpec(
        scenario="flash-crowd", scenario_jobs=size["flash_jobs"],
        rescheduler="non-binding", autoscaler="predictive",
        forecaster_obj=fmodel.LearnedForecaster(result.params, result.arch,
                                                window))
    t0 = time.perf_counter()
    sim = build_simulation(spec)
    res = sim.run()
    run_s = time.perf_counter() - t0
    sim.cluster.pod_store.audit_columns(sim.cluster)   # raises on drift
    print(f"forecaster experiment: flash-crowd jobs={size['flash_jobs']} "
          f"completed={res.completed} cost={res.cost} mean_pending_s="
          f"{res.mean_pending_s} scale_outs={res.scale_outs} audit=clean "
          f"wall_s={run_s}", flush=True)
    if not res.completed:
        failures.append("predictive flash-crowd experiment did not complete")

    cpu = jax.devices("cpu")[0]
    x = np.log1p(np.asarray(data["X_val"][:64], np.float32))
    apply = jax.jit(lambda p, xb: fmodel.apply_forecast(p, xb, result.arch))
    diff = {}
    for precision in ("float32", "default"):
        with jax.default_matmul_precision(precision):
            on_dev = np.asarray(apply(result.params, x))
            on_cpu = np.asarray(apply(jax.device_put(result.params, cpu),
                                      jax.device_put(x, cpu)))
        diff[precision] = float(np.max(np.abs(on_dev - on_cpu)))
    print(f"forecaster device-vs-cpu: windows={x.shape[0]} "
          f"max_abs_diff_float32={diff['float32']} (bound {PRED_ATOL}) "
          f"max_abs_diff_default={diff['default']} (not bounded)",
          flush=True)
    if not diff["float32"] <= PRED_ATOL:
        failures.append(f"forecaster predictions differ from the CPU by "
                        f"{diff['float32']} > {PRED_ATOL}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="rehearse at a tiny size on any backend; prints no "
                         "result line")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.tiny:
        print(f"chip_smoke: no TPU (first JAX device is {dev.platform}); "
              f"nothing was run", file=sys.stderr)
        return 1
    print(f"device: {dev.platform} {dev.device_kind} count={len(devices)}",
          flush=True)

    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.compile_cache import use_compile_cache
    print(f"compile cache: {use_compile_cache()}", flush=True)

    size = TINY if args.tiny else FULL
    failures = []
    lanes_phase(size, failures)
    forecaster_phase(size, failures)
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use: "
          f"{stats.get('peak_bytes_in_use', 'not reported')}", flush=True)

    if failures:
        for f in failures:
            print(f"FAILED: {f}", file=sys.stderr)
        return 1
    if dev.platform != "tpu":
        print(f"rehearsal passed on {dev.platform}; no result line without "
              f"a TPU", flush=True)
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
