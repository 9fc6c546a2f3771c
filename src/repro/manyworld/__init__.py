"""Many-world lanes: batched JAX evaluation of independent simulations.

An explicitly-flagged fast path that runs thousands of experiment
*lanes* (static fleets, or fleets grown by the binding autoscaler and
shrunk by Alg. 6) as one jit-compiled program — see
`repro.manyworld.lanes` for the engine, its relaxed-semantics contract
and its integer IEEE-754 float discipline, and
`repro.manyworld.evaluator` for the ``run_cells(..., workers="lanes")``
backend that reconstructs serial bit-identical result rows.  Importing this package does **not** import
JAX; the engine modules import it lazily on first use.
"""
from repro.manyworld.lanes import (LaneBatch, next_pow2, run_lane_batch,
                                   stack_lanes)

__all__ = ["LaneBatch", "next_pow2", "run_lane_batch", "stack_lanes",
           "lane_eligible", "run_cells_lanes"]


def __getattr__(name):
    # evaluator pulls in repro.search lazily; avoid import cycles at
    # package-import time.
    if name in ("lane_eligible", "run_cells_lanes"):
        from repro.manyworld import evaluator
        return getattr(evaluator, name)
    raise AttributeError(name)
