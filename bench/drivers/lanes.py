"""Driver for lane cells: one population of static-fleet cells, evaluated
again and again through the many-world lane program.

The timed entry is the program's ``run_cells(cells, workers="lanes")``,
as a policy search calls it.  The population is fixed for the run: cell
``i`` replays lane ``i`` of the mix (``bench/traffic/generator.py``),
dealt from ``--seed``.  The traces reach the program through its scenario
registry, under a name the benchmark owns.

Set-up builds the population and evaluates it once (that compiles the
lane program, or loads it from the cache, and fills the program's trace
memo).  The window evaluates whole populations until ``seconds`` have
passed.  The check replays every cell of the window's first population
with the configuration's plain reference, and holds every later
population's rows to the first's, on every field but ``wall_s``.
"""
from __future__ import annotations

import time

from benchlib import bench_file

SPANS = (("repro.manyworld.lanes", "stack_lanes", "bench.lanes.stack"),
         ("repro.manyworld.lanes", "run_lane_batch", "bench.lanes.device"),
         ("repro.manyworld.evaluator", "_lane_metrics", "bench.lanes.rebuild"))


def setup(ctx):
    gen = bench_file("traffic", "generator.py")
    ref = bench_file("configs", f"{ctx.config['name']}.py")
    from repro.cloud.adapter import NODE_TEMPLATES
    from repro.manyworld.evaluator import lane_eligible
    from repro.scenarios import register
    from repro.search.runner import CellSpec

    dep = ctx.config
    node = dep["node"]
    tpl = NODE_TEMPLATES[node["template"]]
    if (tpl.allocatable.cpu_m != node["allocatable_cpu_m"]
            or tpl.allocatable.mem_mb != node["allocatable_mem_mb"]
            or tpl.price_per_s != node["price_per_s"]):
        raise ValueError(f"the program's {node['template']} template is not "
                         f"the deployment's: {tpl}")
    mix = ctx.mix
    n_lanes = int(mix["lanes"])
    skeletons = [gen.dealt(gen.skeleton(mix, lane=i), ctx.seed, lane=i)
                 for i in range(n_lanes)]
    # The program memoizes traces per process by (scenario, seed, n_jobs):
    # the name carries everything the traces depend on.
    scenario = f"bench.{mix['name']}.{mix['shape_seed']}.{ctx.seed}"

    def build(lane, _n_jobs):
        return gen.to_trace(skeletons[lane], scenario)

    register(scenario, build, overwrite=True)
    cells = [CellSpec(scenario=scenario, scheduler=dep["scheduler"],
                      autoscaler=dep["autoscaler"],
                      rescheduler=dep["rescheduler"], seed=i,
                      initial_workers=int(dep["nodes"]))
             for i in range(n_lanes)]
    if not all(lane_eligible(c) for c in cells):
        raise ValueError("cells outside the lane envelope would run serially")
    state = {"ctx": ctx, "gen": gen, "ref": ref, "cells": cells,
             "skeletons": skeletons, "batches": []}
    _evaluate(state)                      # warm-up: compile + trace memo
    return state


def _evaluate(state):
    from repro.search.runner import run_cells
    return run_cells(state["cells"], workers="lanes")


def _with_spans():
    """Wrap the program's stack / device / rebuild calls in profiler
    spans for a traced run; returns the undo list."""
    import importlib

    import jax
    undo = []
    for mod_name, attr, span in SPANS:
        mod = importlib.import_module(mod_name)
        fn = getattr(mod, attr, None)
        if fn is None:
            continue

        def wrapped(*a, _fn=fn, _span=span, **k):
            with jax.profiler.TraceAnnotation(_span):
                return _fn(*a, **k)
        setattr(mod, attr, wrapped)
        undo.append((mod, attr, fn))
    return undo


def window(state, seconds: float, traced: bool = False) -> dict:
    undo = _with_spans() if traced else []
    try:
        batches = []
        t0 = time.perf_counter()
        while True:
            batches.append(_evaluate(state))
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
    finally:
        for mod, attr, fn in undo:
            setattr(mod, attr, fn)
    state["batches"] = batches
    n_cells = len(state["cells"]) * len(batches)
    return {"e2e": {"cells_per_s": n_cells / elapsed},
            "counters": {"cells": n_cells, "batches": len(batches),
                         "window_s": elapsed},
            "attempted": n_cells, "failed": 0}


def check(state) -> list:
    """Rows that differ, on any field but ``wall_s``: each cell of the
    window's first population against the plain reference, and each cell
    of a later population against the first's row.  A population with
    the wrong number of rows counts one more."""
    ctx, gen, ref = state["ctx"], state["gen"], state["ref"]
    batches = state.pop("batches")
    n_lanes = len(state["cells"])
    differing = sum(len(rows) != n_lanes for rows in batches)
    first = batches[0]
    for lane in range(min(n_lanes, len(first))):
        want = ref.simulate(gen.job_columns(state["skeletons"][lane]),
                            ctx.config)
        if any(first[lane].get(f) != v for f, v in want.items()):
            differing += 1
    for rows in batches[1:]:
        for got, row in zip(rows, first):
            if _fields(got) != _fields(row):
                differing += 1
    return [{"name": "rows_differing", "value": differing,
             "limit": ctx.config["correctness"]["rows_differing"]["limit"]}]


def _fields(row: dict) -> dict:
    return {k: v for k, v in row.items() if k != "wall_s"}
