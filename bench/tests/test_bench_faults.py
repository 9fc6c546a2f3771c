"""Each fault a cell can have, planted under the timed path, turns
``correct`` false in an otherwise whole run (CPU, tiny size).

Faults that a cell cannot have are not planted: no cell exchanges data
between chips.
"""
import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
from benchlib import bench_file, use_program  # noqa: E402

use_program()
TINY = {"lanes": {"lanes": 4}}


def cells_of(driver):
    run = bench_file("run.py")
    bench = run.load_benchmark()
    out = []
    for w in bench["workloads"]:
        _cell, entry = run.cell_of(bench, w["name"])
        with open(os.path.join(ROOT, entry["file"])) as f:
            if json.load(f)["driver"] == driver:
                out.append(w["name"])
    return out


def rehearse(workload, driver):
    run = bench_file("run.py")
    return run.run_cell(workload, 77, 0.2, False, require_tpu=False,
                        mix_overrides=TINY[driver])


def _state_unchanged(out):
    for key in ("bound", "done_committed", "completed", "done_is_cycle"):
        out[key][...] = False
    out["scale_outs"][...] = 0
    return out


def _half_left_out(out):
    half = out["completed"].shape[0] // 2
    for val in out.values():
        if isinstance(val, np.ndarray) and val.ndim >= 1:
            val[half:] = val[:1]
    return out


def _answer_altered(out):
    out["bind_cycle"][:, 0] += 1
    return out


def _last_lane_altered(out):
    out["bind_cycle"][-1, 0] += 1
    return out


@pytest.mark.parametrize("workload", cells_of("lanes"))
@pytest.mark.parametrize("fault", [_state_unchanged, _half_left_out,
                                   _answer_altered, _last_lane_altered])
@pytest.mark.parametrize("from_call", [0, 2])
def test_lane_faults_are_caught(workload, fault, from_call, monkeypatch):
    """``from_call`` 0 plants the fault in every call of the lane program;
    2 leaves set-up's call and the window's first whole, so only a later
    population of the window carries it."""
    from repro.manyworld import lanes
    real = lanes.run_lane_batch
    calls = []

    def planted(batch):
        out = {k: np.array(v) for k, v in real(batch).items()}
        calls.append(1)
        return fault(out) if len(calls) > from_call else out
    monkeypatch.setattr(lanes, "run_lane_batch", planted)
    result = rehearse(workload, "lanes")
    assert len(calls) > from_call
    assert result["correct"] is False
    assert result["checks"]["rows_differing"]["value"] > 0
