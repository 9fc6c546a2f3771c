#!/usr/bin/env bash
# CI gate: tier-1 tests + a fast smoke of the scheduler-cycle throughput
# benchmark, so perf regressions in the cycle hot path fail loudly.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== docs checks (links + snippet references) =="
python scripts/docs_check.py

echo "== tier-1 tests (with wall-time budget) =="
# The parity suite grows with every engine refactor; --durations surfaces
# the slowest tests and the budget gate keeps total wall time bounded so
# new property tests must pay for themselves.  Override with
# TEST_BUDGET_S=<seconds>, or TEST_BUDGET_SKIP=1 on unusually slow runners.
TEST_BUDGET_S="${TEST_BUDGET_S:-480}"
test_t0=$SECONDS
python -m pytest -x -q --durations=15
test_elapsed=$(( SECONDS - test_t0 ))
if [ "${TEST_BUDGET_SKIP:-0}" = "1" ]; then
    echo "test-budget gate skipped (TEST_BUDGET_SKIP=1; took ${test_elapsed}s)"
elif [ "$test_elapsed" -gt "$TEST_BUDGET_S" ]; then
    echo "test-budget gate FAILED: suite took ${test_elapsed}s > ${TEST_BUDGET_S}s budget"
    exit 1
else
    echo "test-budget gate OK: ${test_elapsed}s <= ${TEST_BUDGET_S}s"
fi

echo "== scheduler throughput smoke (small scale, both engines) =="
python benchmarks/bench_sched_throughput.py --scale small \
    --out /tmp/BENCH_sched_smoke.json
python - <<'EOF'
import json
import os
row = json.load(open("/tmp/BENCH_sched_smoke.json"))["scales"]["small"]
arr = row["engines"]["array"]
assert arr["completed"], "array engine failed to complete the smoke workload"
# Machine-independent gate: the array engine must beat the seed object
# engine measured on the same box in the same run (~3-4x at this scale;
# 1.5 leaves slack for noisy CI runners).
speedup = row["speedup_cycle_throughput"]
assert speedup and speedup >= 1.5, f"cycle-path regression: speedup={speedup}"
print(f"smoke OK: {arr['cycle_throughput_pods_per_s']} pods/s "
      f"(speedup vs object engine: {speedup}x)")

# Bench-regression gate: the smoke's absolute cycle throughput must stay
# within BENCH_REGRESSION_TOLERANCE (default 30%) of the committed
# BENCH_sched.json baseline.  Machine-dependent by design — the committed
# numbers come from the same container class; set BENCH_REGRESSION_SKIP=1
# when running on unrelated hardware.
if os.environ.get("BENCH_REGRESSION_SKIP") == "1":
    print("bench-regression gate skipped (BENCH_REGRESSION_SKIP=1)")
else:
    tolerance = float(os.environ.get("BENCH_REGRESSION_TOLERANCE", "0.30"))
    base_row = json.load(open("BENCH_sched.json"))["scales"]["small"]
    base = base_row["engines"]["array"]["cycle_throughput_pods_per_s"]
    now = arr["cycle_throughput_pods_per_s"]
    floor = (1.0 - tolerance) * base
    assert now >= floor, (
        f"cycle-throughput regression: {now} pods/s < {floor:.0f} "
        f"(committed baseline {base} pods/s - {tolerance:.0%})")
    print(f"bench-regression gate OK: {now} pods/s vs committed {base} "
          f"(floor {floor:.0f})")
EOF

echo "== scenario smoke sweep (small scheduler x autoscaler x scenario grid) =="
# The scenario subsystem's end-to-end gate: a small grid over four generated
# scenario families must run to completion through trace-native replay.
python benchmarks/sweep_scenarios.py --smoke --out /tmp/SWEEP_smoke.json
python - <<'EOF'
import json
rep = json.load(open("/tmp/SWEEP_smoke.json"))
cells = rep["cells"]
assert len(cells) >= 16, f"smoke grid shrank to {len(cells)} cells"
bad = [(c["scenario"], c["scheduler"], c["autoscaler"])
       for c in cells if not c["completed"]]
assert not bad, f"sweep cells failed to complete: {bad}"
scenarios = sorted({c["scenario"] for c in cells})
assert len(scenarios) >= 4, f"too few scenario families: {scenarios}"
assert all(c["cost"] > 0 for c in cells), "a completed cell priced at $0"
print(f"scenario sweep OK: {len(cells)} cells over {scenarios}")
EOF

echo "== policy-search smoke (seeded micro-search, serial vs pool bit-identity) =="
# The search subsystem's end-to-end gate: a 2-gen x 6-individual NSGA-II
# micro-search must produce a non-empty Pareto front, and a 2-worker
# process pool must reproduce the serial run bit-for-bit (the script
# asserts both and exits non-zero on drift).
python scripts/search.py --smoke --out /tmp/SEARCH_smoke.json

echo "== sweep-pool gate (search cell runner process-pool overhead) =="
# The cell runner's perf gate: pool speedup on the fixed 12-cell sweep
# grid must stay within BENCH_REGRESSION_TOLERANCE (default 30%) of the
# committed BENCH_sched.json entry.  On the 1-core container class this
# guards pool *overhead* (committed speedup ~1.0); on wider hosts it
# guards real parallel speedup.  Machine-dependent like the other bench
# gates.
if [ "${BENCH_REGRESSION_SKIP:-0}" = "1" ]; then
    echo "sweep-pool gate skipped (BENCH_REGRESSION_SKIP=1)"
else
python benchmarks/bench_sched_throughput.py --scale none --sweep-pool \
    --out /tmp/BENCH_pool_smoke.json
python - <<'EOF'
import json
import os
tolerance = float(os.environ.get("BENCH_REGRESSION_TOLERANCE", "0.30"))
now = json.load(open("/tmp/BENCH_pool_smoke.json"))["sweep_pool"]
assert now["identical"], "pool rows diverged from serial rows"
base = json.load(open("BENCH_sched.json"))["sweep_pool"]
floor = (1.0 - tolerance) * base["speedup"]
assert now["speedup"] >= floor, (
    f"sweep-pool regression: speedup {now['speedup']} < {floor:.2f} "
    f"(committed {base['speedup']} - {tolerance:.0%})")
print(f"sweep-pool gate OK: speedup {now['speedup']} vs committed "
      f"{base['speedup']} (floor {floor:.2f}), rows bit-identical")
EOF
fi

echo "== chaos smoke (seeded disruption schedules, parity + column audits) =="
# The disruption subsystem's end-to-end gate: per chaos scenario, the
# unspied array fast path runs with PodStore.audit_columns after every
# disruption event, both engines must produce bit-identical event logs,
# and the array trace must match the committed golden chaos fixture.
python scripts/chaos.py --smoke --out /tmp/CHAOS_smoke.json

echo "== obs smoke (flight recorder -> export -> report, end to end) =="
# The observability pipeline's end-to-end gate: record a small run, assert
# the obs-on ExperimentResult is bit-identical to obs-off, round-trip the
# event log through .npz and .json bit-exactly, check every reactive
# scale-out request is attributed in the log, and render the report +
# Chrome trace.  (Obs *off* is the default path every other gate in this
# file runs — the throughput/full-run gates against the committed
# BENCH_sched.json baselines already pin its cost to within noise.)
python scripts/obsreport.py --smoke --limit 5

echo "== obs overhead gate (obs-on wall vs obs-off, same spec) =="
# Recording is passive but not free: the obs-on wall on the flash-crowd/
# predictive stress cell must stay within REPRO_OBS_OVERHEAD_MAX (default
# 2.0x, measured ~1.6x) of obs-off, and the results must stay
# bit-identical.  Machine-dependent timing — skipped with the other bench
# gates on unrelated hardware.
if [ "${BENCH_REGRESSION_SKIP:-0}" = "1" ]; then
    echo "obs overhead gate skipped (BENCH_REGRESSION_SKIP=1)"
else
    python scripts/obsreport.py --overhead-gate
fi

echo "== trace-replay gate (100k-arrival columnar ingest, array engine) =="
# Regression gate for the trace-native submission path (Timeline ->
# submit_trace -> PodStore.ingest_trace): end-to-end pods/s on a 100k-
# arrival generated scenario vs the committed BENCH_sched.json baseline.
# Machine-dependent like the other bench gates.
if [ "${BENCH_REGRESSION_SKIP:-0}" = "1" ]; then
    echo "trace-replay gate skipped (BENCH_REGRESSION_SKIP=1)"
else
python benchmarks/bench_sched_throughput.py --scale none --trace-replay \
    --out /tmp/BENCH_trace_smoke.json
python - <<'EOF'
import json
import os
tolerance = float(os.environ.get("BENCH_REGRESSION_TOLERANCE", "0.30"))
now = json.load(open("/tmp/BENCH_trace_smoke.json"))["trace_replay"]
assert now["completed"], "100k trace replay failed to complete"
base = json.load(open("BENCH_sched.json"))["trace_replay"]
floor = (1.0 - tolerance) * base["pods_per_s_end_to_end"]
assert now["pods_per_s_end_to_end"] >= floor, (
    f"trace-replay regression: {now['pods_per_s_end_to_end']} pods/s < "
    f"{floor:.0f} (committed {base['pods_per_s_end_to_end']} - {tolerance:.0%})")
print(f"trace-replay gate OK: {now['pods_per_s_end_to_end']} pods/s vs "
      f"committed {base['pods_per_s_end_to_end']} (floor {floor:.0f})")
EOF
fi

echo "== full-run gate (large scale, array engine) =="
# Cycle throughput alone misses regressions in the event path (arrival
# ingest, completion commits, telemetry): gate the *end-to-end* 2k-node x
# 50k-pod full-run wall time at -30% vs the committed BENCH_sched.json.
# Skipped wholesale on unrelated hardware — unlike the small smoke, this
# run exists only for the machine-dependent comparison.
if [ "${BENCH_REGRESSION_SKIP:-0}" = "1" ]; then
    echo "full-run gate skipped (BENCH_REGRESSION_SKIP=1)"
else
python benchmarks/bench_sched_throughput.py --scale large --engines array \
    --out /tmp/BENCH_sched_full_smoke.json
python - <<'EOF'
import json
import os
tolerance = float(os.environ.get("BENCH_REGRESSION_TOLERANCE", "0.30"))
row = json.load(open("/tmp/BENCH_sched_full_smoke.json"))
full = row["scales"]["large"]["engines"]["array"]["full_run"]
assert full["completed"], "large-scale full run failed to complete"
base = json.load(open("BENCH_sched.json"))
base_wall = base["scales"]["large"]["engines"]["array"]["full_run"]["wall_s"]
# -30% throughput == wall time growing past base / (1 - tolerance).
ceiling = base_wall / (1.0 - tolerance)
assert full["wall_s"] <= ceiling, (
    f"full-run regression: {full['wall_s']}s > {ceiling:.3f}s "
    f"(committed baseline {base_wall}s + {tolerance:.0%})")
print(f"full-run gate OK: {full['wall_s']}s vs committed {base_wall}s "
      f"(ceiling {ceiling:.3f}s)")
EOF
fi

echo "== forecaster smoke (train + predict on the JAX substrate) =="
# The learned-forecaster gate: a small train run must show decreasing
# loss and a checkpoint save/load round-trip that still serves the
# online observe/predict contract (the script asserts both).  Needs JAX
# (mLSTM + jitted train step); the numpy forecast pieces are covered by
# tier-1 either way.
if ! python -c "import jax" >/dev/null 2>&1; then
    echo "forecaster smoke skipped (JAX not importable)"
else
    python scripts/forecast.py --smoke --out /tmp/FORECAST_smoke.json
fi

echo "== predictive-autoscaler gate (flash-crowd dominance vs NBAS) =="
# The predictive autoscaler must beat the paper's non-binding autoscaler
# (Alg. 5) on mean pending time at equal-or-lower cost on the burst
# scenario prediction exists for — and, since sweep cells are fully
# deterministic, reproduce the committed BENCH_sched.json baseline pair
# exactly (no tolerance: same spec, same floats).
python benchmarks/sweep_scenarios.py --scenarios flash-crowd \
    --schedulers best-fit --autoscalers non-binding,predictive \
    --jobs 600 --out /tmp/SWEEP_predictive_smoke.json
python - <<'EOF'
import json
cells = {c["autoscaler"]: c
         for c in json.load(open("/tmp/SWEEP_predictive_smoke.json"))["cells"]}
nbas, pred = cells["non-binding"], cells["predictive"]
assert pred["mean_pending_s"] < nbas["mean_pending_s"], (
    f"predictive lost on pending: {pred['mean_pending_s']} vs "
    f"NBAS {nbas['mean_pending_s']}")
assert pred["cost"] <= nbas["cost"], (
    f"predictive dominance broke on cost: {pred['cost']} vs "
    f"NBAS {nbas['cost']}")
base = json.load(open("BENCH_sched.json"))["predictive_flash"]
for name, cell in (("non-binding", nbas), ("predictive", pred)):
    for metric in ("cost", "mean_pending_s"):
        got, want = cell[metric], base[name][metric]
        assert got == want, (
            f"{name} {metric} drifted from committed baseline: "
            f"{got} != {want} (deterministic cell — regen the baseline "
            f"only with an intended behavior change)")
print(f"predictive gate OK: mean pending {pred['mean_pending_s']}s vs "
      f"NBAS {nbas['mean_pending_s']}s at cost {pred['cost']} vs "
      f"{nbas['cost']}, matching committed baseline")
EOF

echo "== many-world lane gates (parity smoke + speedup + regression) =="
# The lane evaluator's end-to-end gates.  All of them need JAX, which
# the lane path requires.
if ! python -c "import jax" >/dev/null 2>&1; then
    echo "many-world gates skipped (JAX not importable)"
else
# Lane-parity smoke: every scheduler in the lane envelope, two seeds —
# `workers="lanes"` must reproduce the serial rows bit-for-bit (wall_s
# excepted: a lane reports its share of the batch wall).
python - <<'EOF'
from repro.manyworld.lanes import SCHEDULERS
from repro.search.runner import CellSpec, run_cells

cells = [CellSpec(scenario="heavy-tail", scheduler=sched, autoscaler="void",
                  rescheduler="void", seed=seed, n_jobs=30,
                  initial_workers=3)
         for sched in SCHEDULERS for seed in (0, 1)]
strip = lambda rows: [{k: v for k, v in r.items() if k != "wall_s"}
                      for r in rows]
serial = run_cells(cells, workers=1)
lanes = run_cells(cells, workers="lanes")
assert strip(lanes) == strip(serial), "lane rows diverged from serial rows"
print(f"lane-parity smoke OK: {len(cells)} cells over "
      f"{len(SCHEDULERS)} schedulers, rows bit-identical")
EOF
# Speedup gate (machine-independent: lanes vs serial measured on the
# same box in the same run; the bench re-asserts row parity internally):
# the 256-lane warm batch must clear the 5x bar over serial cells.
python benchmarks/bench_manyworld.py --lanes 256 \
    --out /tmp/BENCH_manyworld_smoke.json
python - <<'EOF'
import json
import os

cur = json.load(open("/tmp/BENCH_manyworld_smoke.json"))
cur = cur["manyworld"]["per_lanes"]["256"]
assert cur["speedup_vs_serial"] >= 5.0, (
    f"lane-evaluator speedup collapsed: {cur['speedup_vs_serial']}x < 5x")
print(f"lane-speedup gate OK: {cur['speedup_vs_serial']}x at 256 lanes")
# Bench-regression gate: warm lanes/s within tolerance of the committed
# BENCH_sched.json baseline.  Machine-dependent like the other bench
# gates; skipped with BENCH_REGRESSION_SKIP=1.
if os.environ.get("BENCH_REGRESSION_SKIP") == "1":
    print("lane-regression gate skipped (BENCH_REGRESSION_SKIP=1)")
else:
    tolerance = float(os.environ.get("BENCH_REGRESSION_TOLERANCE", "0.30"))
    base = json.load(open("BENCH_sched.json"))["manyworld"]["per_lanes"]["256"]
    floor = (1.0 - tolerance) * base["lanes_per_s"]
    assert cur["lanes_per_s"] >= floor, (
        f"lane-evaluator regression: {cur['lanes_per_s']} lanes/s < "
        f"{floor:.0f} (committed {base['lanes_per_s']} - {tolerance:.0%})")
    print(f"lane-regression gate OK: {cur['lanes_per_s']} lanes/s vs "
          f"committed {base['lanes_per_s']} (floor {floor:.0f})")
EOF
fi
