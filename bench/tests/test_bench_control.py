"""Each cell's control comes out as not correct through the harness's own
comparison, at a size a test run holds.  ``bench/tools/control.py`` reads
the same control at the cells' own sizes.

Lane cells: the control is the plain reference computed with float32
memory (the step below the deployment's float64), put in the program's
place under the timed path.  A whole run with it (set-up, window, check)
has to report ``rows_differing`` above its limit of 0.
"""
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
from benchlib import bench_file, use_program  # noqa: E402

use_program()
control = bench_file("tools", "control.py")


def lane_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("workload", lane_cells())
@pytest.mark.parametrize("seed", [9, 2**31 + 5])
def test_float32_memory_control_fails_the_run(workload, seed):
    result = control.run_control(workload, seed, lanes=8)
    assert result["correct"] is False
    assert result["checks"]["rows_differing"]["value"] > 0


@pytest.mark.parametrize("workload", lane_cells())
def test_float64_reference_in_the_programs_place_passes(workload):
    """The same substitution with the deployment's own precision is
    correct: the control fails for its precision, not for the swap."""
    result = control.run_control(workload, 9, lanes=8, mem_dtype=float)
    assert result["correct"] is True
    assert result["checks"]["rows_differing"]["value"] == 0
