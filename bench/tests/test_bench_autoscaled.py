"""The autoscaled deployment (``paper-bas19``): its plain reference
against the program's serial engine, its node-occupancy reader on
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
ref = bench_file("configs", "paper-bas19.py")
CELL = "paper-bas19.bursty"
READER = "node_occupancy.lanes"


def config():
    with open(os.path.join(BENCH, "configs", "paper-bas19.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("workload", ["bursty", "slow", "mixed"])
@pytest.mark.parametrize("nodes", [1, 3])
def test_reference_matches_serial_engine(workload, nodes):
    """Every field of the row, on three traces of each of the paper's
    workloads, from one static worker and from three."""
    from repro.scenarios import register
    from repro.search.runner import CellSpec, run_cell
    cfg = dict(config(), nodes=nodes)
    mix = {"family": "paper", "workload": workload, "shape_seed": 70}
    skeletons = [gen.dealt(gen.skeleton(mix, lane=i), 2**31 + 7, lane=i)
                 for i in range(3)]
    name = f"bench.test.autoscaled.{workload}"
    register(name, lambda lane, _n: gen.to_trace(skeletons[lane], name),
             overwrite=True)
    for lane, sk in enumerate(skeletons):
        row = run_cell(CellSpec(scenario=name, scheduler=cfg["scheduler"],
                                autoscaler=cfg["autoscaler"],
                                rescheduler=cfg["rescheduler"], seed=lane,
                                initial_workers=nodes))
        want = ref.simulate(gen.job_columns(sk), cfg)
        assert {f: row[f] for f in want} == want
        assert want["scale_ins"] > 0 and want["max_nodes"] > nodes


def record(call, active_nodes, node_cycles):
    counts = {"n_cycles": 10, "wave_steps": 5, "completion_steps": 5,
              "busy_lane_steps": 1, "active_lane_cycles": 1,
              "lane_steps": 20}
    if node_cycles is not None:
        counts.update(active_node_cycles=active_nodes,
                      node_cycles=node_cycles)
    return {"call": call, "lanes": 4, "buckets": 1, "compiles": 0,
            "wall_s": 1.0, "self_s": {"lanes.call": 1.0}, "counts": counts}


def ctx(driver="lanes", batches=2):
    return {"driver": driver, "config": {}, "trace": None,
            "counters": {"cells": 8, "batches": batches, "window_s": 5.0},
            "device_kind": "TPU v5 lite"}


@pytest.mark.parametrize("calls,want", [
    # (30 + 50) live node-cycles over (4 lanes x 10 cycles x 8 nodes) x 2
    ([record(0, 999, 1), record(1, 30, 320), record(2, 50, 320)],
     100.0 * 80 / 640),
    # a static bucket's call offers no node records
    ([record(1, 0, None), record(2, 48, 320)], 100.0 * 48 / 320),
    ([record(1, 0, None), record(2, 0, None)], None),
    ([record(1, 0, 0), record(2, 0, 0)], None),
])
def test_reader_on_hand_made_records(monkeypatch, calls, want):
    monkeypatch.setattr(evaluator, "lane_calls",
                        lambda n: [dict(c) for c in calls[-n:]] if n else [])
    got = bench_file("metrics", f"{READER}.py").read(ctx())
    assert got == (None if want is None else pytest.approx(want))


@pytest.mark.parametrize("case", ["other driver", "no window", "too few",
                                  "no records"])
def test_reader_gives_nothing_without_its_records(monkeypatch, case):
    calls = [record(1, 30, 320), record(2, 50, 320)]
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


def test_traced_rehearsal_reports_node_occupancy():
    """A traced CPU rehearsal of the cell is correct, reports the share of
    node records in use, above 0 and at most 100 %, beside the lane
    metrics the program's counts feed, and runs every lane on the lane
    program, none serially."""
    run = bench_file("run.py")
    result = run.run_cell(CELL, 2**31 + 9, 0.2, True, require_tpu=False,
                          mix_overrides={"lanes": 6})
    assert result["correct"] is True
    got = result["metrics"]
    assert 0 < got[READER]["value"] <= 100.0
    for name in ("steps_per_batch.lanes", "lane_occupancy.lanes",
                 "host_ms_per_cell.lanes"):
        assert got[name]["value"] > 0, name
    assert got["compiles_in_window.lanes"]["value"] == 0
    rec = evaluator.lane_calls(1)[0]
    assert rec["lanes"] == 6 and rec["counts"]["lane_fallbacks"] == 0
    assert rec["counts"]["scale_out_nodes"] > 0
