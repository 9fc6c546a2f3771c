"""The lane cells' readers of the program's own spans and step counts:
on hand-made call records and a hand-made reduced trace, their refusals,
and the CPU rehearsal of a traced run."""
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
from benchlib import bench_file, use_program  # noqa: E402

use_program()
from repro.manyworld import evaluator  # noqa: E402

NEW = ("host_ms_per_cell.lanes", "steps_per_batch.lanes",
       "device_us_per_step.lanes", "lane_occupancy.lanes",
       "compiles_in_window.lanes")
PROGRAM = ("host_ms_per_cell.lanes", "steps_per_batch.lanes",
           "lane_occupancy.lanes")


def record(call, wall, wait, cycles, wave, comp, busy, active, lanes,
           compiles=0):
    return {"call": call, "lanes": lanes, "buckets": 1, "compiles": compiles,
            "wall_s": wall,
            "self_s": {"lanes.call": 0.001, "lanes.wait": wait,
                       "lanes.rebuild": wall - wait - 0.001},
            "counts": {"n_cycles": cycles, "wave_steps": wave,
                       "completion_steps": comp, "busy_lane_steps": busy,
                       "active_lane_cycles": active,
                       "lane_steps": lanes * (cycles + wave + comp)}}


# Three calls in the program's memory; the window made the last two.
CALLS = [record(0, 9.0, 1.0, 1, 1, 1, 1, 1, 4, compiles=5),
         record(1, 2.0, 0.5, 10, 20, 10, 60, 30, 4, compiles=1),
         record(2, 3.0, 1.5, 20, 30, 10, 100, 60, 4, compiles=2)]


def ctx(driver="lanes", batches=2, cells=8, trace=True):
    tr = ({"devices": 1, "busy_s": 0.9, "window_s": 5.0, "idle_share": 0.82}
          if trace else None)
    return {"driver": driver, "config": {}, "trace": tr,
            "counters": {"cells": cells, "batches": batches, "window_s": 5.0},
            "device_kind": "TPU v5 lite"}


@pytest.fixture
def calls(monkeypatch):
    monkeypatch.setattr(evaluator, "lane_calls",
                        lambda n: [dict(c) for c in CALLS[-n:]] if n else [])


@pytest.mark.parametrize("name,want", [
    # (2.0 - 0.5) + (3.0 - 1.5) s host over 8 cells
    ("host_ms_per_cell.lanes", 1e3 * 3.0 / 8),
    # (10 + 20 + 10) + (20 + 30 + 10) steps over 2 populations
    ("steps_per_batch.lanes", 100.0 / 2),
    # 0.9 s busy over 100 steps
    ("device_us_per_step.lanes", 1e6 * 0.9 / 100),
    # (60 + 30 + 100 + 60) over 4 lanes x (40 + 60) steps
    ("lane_occupancy.lanes", 100.0 * 250 / 400),
    # 1 + 2 compilations in the window; the warm-up's 5 are not in it
    ("compiles_in_window.lanes", 3),
])
def test_reader_on_hand_made_records(calls, name, want):
    assert bench_file("metrics", f"{name}.py").read(ctx()) == \
        pytest.approx(want)


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("case", ["other driver", "no window", "too few",
                                  "no records"])
def test_reader_gives_nothing_without_its_records(calls, monkeypatch, name,
                                                  case):
    c = ctx()
    if case == "other driver":
        c = ctx(driver="predictive")
    elif case == "no window":
        c["counters"] = {}
    elif case == "too few":
        c = ctx(batches=4)
    else:                               # a program before lane_calls
        monkeypatch.delattr(evaluator, "lane_calls")
    assert bench_file("metrics", f"{name}.py").read(c) is None


@pytest.mark.parametrize("trace", [None, {"devices": 0, "busy_s": 0.0}])
def test_device_step_time_needs_a_device_plane(calls, trace):
    c = ctx()
    c["trace"] = trace
    assert bench_file("metrics", "device_us_per_step.lanes.py").read(c) \
        is None


def lane_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]
                if w["name"].startswith("paper-static19.")]


@pytest.mark.parametrize("workload", lane_cells())
@pytest.mark.parametrize("device_plane", [False, True])
def test_traced_rehearsal_reports_the_lane_metrics(workload, device_plane,
                                                   monkeypatch):
    """A traced CPU rehearsal reports the program-read metrics, positive,
    and no compilation in the window.
    A CPU trace has no device plane, so the step time appears only when
    the reduction is handed one (a stand-in busy time)."""
    run = bench_file("run.py")
    if device_plane:
        xp = bench_file("xplane.py")
        real = xp.reduce

        def with_device(planes, span, top=10):
            return {**real(planes, span, top), "devices": 1, "busy_s": 0.01}
        monkeypatch.setattr(xp, "reduce", with_device)
    result = run.run_cell(workload, 2**31 + 5, 0.2, True, require_tpu=False,
                          mix_overrides={"lanes": 4})
    assert result["correct"] is True
    got = result["metrics"]
    for name in PROGRAM:
        assert got[name]["value"] > 0, name
    # The warm-up population compiled every bucket the window ran.
    assert got["compiles_in_window.lanes"]["value"] == 0
    assert ("device_us_per_step.lanes" in got) is device_plane
    if device_plane:
        assert got["device_us_per_step.lanes"]["value"] > 0
        assert got["lane_occupancy.lanes"]["value"] <= 100.0
