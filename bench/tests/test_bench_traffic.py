"""The benchmark's copied traffic generator against the program's own
scenario builders, row for row, at each cell's parameters."""
import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
from benchlib import bench_file, use_program  # noqa: E402

use_program()
gen = bench_file("traffic", "generator.py")

MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic"))
               if f.endswith(".json"))
# Per-row columns; template ids are left out, as the program numbers its
# templates in order of first arrival and the copy in Table 2's order.
COLUMNS = ("arrival_time", "duration_s", "cpu_m", "mem_mb", "kind",
           "moveable", "checkpointable")


def program_trace(mix, seed):
    from repro.scenarios import build_scenario
    return build_scenario(f"paper-{mix['workload']}", seed=seed)


def row_types(trace):
    return [trace.templates[i].type_name for i in trace.template_id]


@pytest.mark.parametrize("mix_name", MIXES + ["paper-mixed"])
@pytest.mark.parametrize("lane", [0, 1, 5])
def test_copied_generator_matches_program(mix_name, lane):
    mix = (gen.load_mix(mix_name) if mix_name in MIXES else
           {"family": "paper", "workload": "mixed", "shape_seed": 3})
    ours = gen.to_trace(gen.skeleton(mix, lane=lane), "x")
    theirs = program_trace(mix, int(mix["shape_seed"]) + lane)
    assert ours.n == theirs.n == 50
    assert row_types(ours) == row_types(theirs)
    for col in COLUMNS:
        np.testing.assert_array_equal(getattr(ours, col),
                                      getattr(theirs, col), err_msg=col)


@pytest.mark.parametrize("mix_name", MIXES)
def test_seeds_deal_the_same_jobs_in_another_order(mix_name):
    mix = gen.load_mix(mix_name)
    sk = gen.skeleton(mix)
    a = gen.dealt(sk, 2**31 + 7)
    b = gen.dealt(sk, 12)
    np.testing.assert_array_equal(a.arrival_time, sk.arrival_time)
    assert not np.array_equal(a.template_id, b.template_id)
    assert sorted(a.template_id) == sorted(sk.template_id)
    if sk.duration_s is not None:
        assert sorted(a.duration_s) == sorted(sk.duration_s)
    again = gen.dealt(sk, 2**31 + 7)
    np.testing.assert_array_equal(a.template_id, again.template_id)


@pytest.mark.parametrize("mix_name", MIXES)
def test_reference_columns_match_program_input(mix_name):
    mix = gen.load_mix(mix_name)
    sk = gen.dealt(gen.skeleton(mix), 3)
    trace = gen.to_trace(sk, "x")
    cols = gen.job_columns(sk)
    np.testing.assert_array_equal(cols["arrival_t"], trace.arrival_time)
    np.testing.assert_array_equal(cols["cpu_m"], trace.cpu_m)
    np.testing.assert_array_equal(cols["mem_mb"], trace.mem_mb)
    batch = cols["is_batch"]
    np.testing.assert_array_equal(batch, trace.kind == 0)
    np.testing.assert_array_equal(cols["duration_s"][batch],
                                  trace.duration_s[batch])


def test_mix_files_name_known_families():
    for name in MIXES:
        with open(os.path.join(BENCH, "traffic", f"{name}.json")) as f:
            mix = json.load(f)
        assert mix["family"] in gen.FAMILIES, name
        assert "shape_seed" in mix, name
