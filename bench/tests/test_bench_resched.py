"""The deployment with Alg. 3 (``paper-resched19``): its plain reference
against the program's serial engine, two faults planted in the
reference's Alg. 3, the reader of ``resched_steps_per_batch.lanes`` on
hand-made call records, and a traced CPU rehearsal of its cell."""
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
from benchlib import bench_file, use_program  # noqa: E402

use_program()
from repro.manyworld import evaluator  # noqa: E402

gen = bench_file("traffic", "generator.py")
ref = bench_file("configs", "paper-resched19.py")
CELL = "paper-resched19.bursty"
READER = "resched_steps_per_batch.lanes"


def config():
    with open(os.path.join(BENCH, "configs", "paper-resched19.json")) as f:
        return json.load(f)


def serial_pairs(workload, n, seed, cfg):
    """``(serial run_cell row, job columns)`` of ``n`` traces of one of
    the paper's workloads, dealt from ``seed``."""
    from repro.scenarios import register
    from repro.search.runner import CellSpec, run_cell
    mix = {"family": "paper", "workload": workload, "shape_seed": 90}
    skeletons = [gen.dealt(gen.skeleton(mix, lane=i), seed, lane=i)
                 for i in range(n)]
    # The program memoizes traces by (scenario, seed, n_jobs): the name
    # carries the deal.
    name = f"bench.test.resched.{workload}.{seed}"
    register(name, lambda lane, _n: gen.to_trace(skeletons[lane], name),
             overwrite=True)
    return [(run_cell(CellSpec(scenario=name, scheduler=cfg["scheduler"],
                               autoscaler=cfg["autoscaler"],
                               rescheduler=cfg["rescheduler"], seed=lane,
                               max_pod_age_s=cfg["max_pod_age_s"],
                               initial_workers=int(cfg["nodes"]))),
             gen.job_columns(sk))
            for lane, sk in enumerate(skeletons)]


@pytest.mark.parametrize("workload", ["bursty", "slow", "mixed"])
@pytest.mark.parametrize("nodes,age", [(1, 60.0), (3, 0.0)])
def test_reference_matches_serial_engine(workload, nodes, age):
    """Every field of the row, on four traces of each of the paper's
    workloads, from one static worker with the paper's age gate and from
    three with none."""
    cfg = dict(config(), nodes=nodes, max_pod_age_s=age)
    pairs = serial_pairs(workload, 4, 2**31 + 11, cfg)
    for row, jobs in pairs:
        want = ref.simulate(jobs, cfg)
        assert {f: row[f] for f in want} == want
    assert sum(r["evictions"] for r, _ in pairs) > 0


@pytest.mark.parametrize("key,fault", [("evictees_rejoin", "same_wave"),
                                       ("candidate_order", "ascending")])
def test_planted_faults_make_rows_differ(key, fault):
    """Evictees placed again in the wave that evicted them, and victims
    tried least free memory first: either makes the reference's rows
    differ from the serial engine's on the cell's traffic."""
    cfg = config()
    pairs = serial_pairs("bursty", 12, 2**31 + 13, cfg)

    def differing(dep):
        n = 0
        for row, jobs in pairs:
            want = ref.simulate(jobs, dep)
            n += {f: row[f] for f in want} != want
        return n

    assert differing(cfg) == 0
    assert differing(dict(cfg, **{key: fault})) > 0


def record(call, resched_steps):
    counts = {"n_cycles": 10, "wave_steps": 5, "completion_steps": 5,
              "busy_lane_steps": 1, "active_lane_cycles": 1,
              "lane_steps": 20}
    if resched_steps is not None:
        counts["resched_steps"] = resched_steps
    return {"call": call, "lanes": 4, "buckets": 1, "compiles": 0,
            "wall_s": 1.0, "self_s": {"lanes.call": 1.0}, "counts": counts}


def ctx(driver="lanes", batches=2):
    return {"driver": driver, "config": {}, "trace": None,
            "counters": {"cells": 8, "batches": batches, "window_s": 5.0},
            "device_kind": "TPU v5 lite"}


@pytest.mark.parametrize("calls,want", [
    ([record(0, 999), record(1, 300), record(2, 500)], 400.0),
    # a bucket without Alg. 3 adds no steps
    ([record(1, None), record(2, 48)], 24.0),
    ([record(1, 0), record(2, 0)], 0.0),
    ([record(1, None), record(2, None)], None),
])
def test_reader_on_hand_made_records(monkeypatch, calls, want):
    monkeypatch.setattr(evaluator, "lane_calls",
                        lambda n: [dict(c) for c in calls[-n:]] if n else [])
    got = bench_file("metrics", f"{READER}.py").read(ctx())
    assert got == want


@pytest.mark.parametrize("case", ["other driver", "no window", "too few",
                                  "no records"])
def test_reader_gives_nothing_without_its_records(monkeypatch, case):
    calls = [record(1, 30), record(2, 50)]
    monkeypatch.setattr(evaluator, "lane_calls",
                        lambda n: [dict(c) for c in calls[-n:]] if n else [])
    c = ctx()
    if case == "other driver":
        c = ctx(driver="predictive")
    elif case == "no window":
        c["counters"] = {}
    elif case == "too few":
        c = ctx(batches=3)
    else:                               # a program before lane_calls
        monkeypatch.delattr(evaluator, "lane_calls")
    assert bench_file("metrics", f"{READER}.py").read(c) is None


def test_traced_rehearsal_reports_resched_steps():
    """A traced CPU rehearsal of the cell is correct and reports Alg. 3's
    steps, above 0, beside the lane metrics the program's counts feed,
    and runs every lane on the lane program, none serially."""
    run = bench_file("run.py")
    result = run.run_cell(CELL, 2**31 + 19, 0.2, True, require_tpu=False,
                          mix_overrides={"lanes": 6})
    assert result["correct"] is True
    got = result["metrics"]
    assert got[READER]["value"] > 0
    for name in ("steps_per_batch.lanes", "lane_occupancy.lanes",
                 "node_occupancy.lanes", "host_ms_per_cell.lanes"):
        assert got[name]["value"] > 0, name
    assert got["compiles_in_window.lanes"]["value"] == 0
    rec = evaluator.lane_calls(1)[0]
    assert rec["lanes"] == 6 and rec["counts"]["lane_fallbacks"] == 0
    assert rec["counts"]["resched_plans"] > 0
