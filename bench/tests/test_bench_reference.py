"""The configuration's plain reference agrees with the program's serial
engine (``run_cell``, the engine the lane rows are held to) on every
field of the row, on the cells' traces and on the paper's third workload.
"""
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
from benchlib import bench_file, use_program  # noqa: E402

use_program()
gen = bench_file("traffic", "generator.py")
ref = bench_file("configs", "paper-static19.py")


def config():
    with open(os.path.join(BENCH, "configs", "paper-static19.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("workload", ["bursty", "slow", "mixed"])
@pytest.mark.parametrize("nodes", [19, 6])
def test_reference_matches_serial_engine(workload, nodes):
    from repro.scenarios import register
    from repro.search.runner import CellSpec, run_cell
    cfg = dict(config(), nodes=nodes)
    mix = {"family": "paper", "workload": workload, "shape_seed": 40}
    skeletons = [gen.dealt(gen.skeleton(mix, lane=i), 2**31 + 3, lane=i)
                 for i in range(3)]
    name = f"bench.test.reference.{workload}"
    register(name, lambda lane, _n: gen.to_trace(skeletons[lane], name),
             overwrite=True)
    for lane, sk in enumerate(skeletons):
        row = run_cell(CellSpec(scenario=name, scheduler=cfg["scheduler"],
                                autoscaler=cfg["autoscaler"],
                                rescheduler=cfg["rescheduler"], seed=lane,
                                initial_workers=nodes))
        want = ref.simulate(gen.job_columns(sk), cfg)
        assert {f: row[f] for f in want} == want
