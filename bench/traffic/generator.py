"""The benchmark's one traffic generator: a mix file in, TraceStores out.

A traffic mix is a JSON file beside this module (``<mix>.json``) holding
the parameters of one arrival family.  The sampler below is a copy of the
paper's section 7.1 workloads (arXiv:1812.00300, Tables 1 and 2), so a
later change to the program's workload code cannot move the yardstick.
``bench/tests/test_bench_traffic.py`` holds it row for row against the
program's own builder (``repro.scenarios`` ``paper-<workload>``).

The paper's workloads.  Each trace is the 50 jobs of one Table 2 mix
(counts per Table 1 job type), in random order, with exponential
inter-arrival times: mean 10 s for ``bursty``, 60 s for ``slow``, and for
``mixed`` alternating bursty and slow periods of at least 10 jobs each,
the first chosen at random.  Batch jobs run for their type's fixed
duration (5, 10 or 15 minutes); services run until the end.

Seeds.  Each trace is first built from a fixed ``shape_seed`` (arrival
instants, and the job multiset).  ``--seed`` then draws a permutation that
deals those jobs to the arrival slots in another order, as the paper
itself deals its fixed multiset at random.  Every seed therefore offers
the same work with the same arrival instants; runs on different seeds
differ in order only.

Mix keys:

* ``family`` -- ``paper``;
* ``workload`` -- ``bursty`` | ``slow`` | ``mixed``;
* ``shape_seed`` -- the seed of the trace skeleton (lane ``i`` of a
  population uses ``shape_seed + i``);
* ``lanes`` -- population size, for lane cells.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# Paper Table 1: name -> (kind, cpu_m, mem in Gi, duration_s; services run
# until the end and carry duration 0).
JOB_TYPES = {
    "batch_small": ("batch", 100, 0.3, 300.0),
    "batch_med": ("batch", 200, 0.6, 600.0),
    "batch_large": ("batch", 300, 0.9, 900.0),
    "service_small": ("service", 100, 1.0, 0.0),
    "service_med": ("service", 200, 1.4, 0.0),
    "service_large": ("service", 300, 2.359, 0.0),
}
# Paper Table 2: jobs of each type in each workload (50 per workload).
WORKLOAD_MIXES = {
    "bursty": {"batch_small": 10, "batch_med": 8, "batch_large": 5,
               "service_small": 6, "service_med": 12, "service_large": 9},
    "slow": {"batch_small": 17, "batch_med": 11, "batch_large": 4,
             "service_small": 6, "service_med": 7, "service_large": 5},
    "mixed": {"batch_small": 6, "batch_med": 7, "batch_large": 9,
              "service_small": 7, "service_med": 11, "service_large": 10},
}
# Section 7.1: mean inter-arrival times, and the shortest mixed period.
BURSTY_MEAN_S = 10.0
SLOW_MEAN_S = 60.0
MIN_JOBS_PER_PERIOD = 10


def load_mix(name: str) -> dict:
    path = os.path.join(HERE, f"{name}.json")
    with open(path) as f:
        mix = json.load(f)
    mix["name"] = name
    return mix


@dataclasses.dataclass
class Skeleton:
    """One trace before it becomes a TraceStore: job type names (the
    template table), per-row template ids, arrival instants, and per-row
    durations (None: the template's)."""
    types: List[str]
    template_id: np.ndarray
    arrival_time: np.ndarray
    duration_s: Optional[np.ndarray]


def paper(seed, workload="bursty"):
    """One of the paper's section 7.1 workloads, drawn as the program's
    ``generate_workload`` draws it."""
    rng = np.random.default_rng(seed)
    counts = WORKLOAD_MIXES[workload]
    types = list(counts)
    jobs = np.repeat(np.arange(len(types), dtype=np.int32),
                     list(counts.values()))
    tid = jobs[rng.permutation(len(jobs))]
    times = []
    t = 0.0
    if workload == "mixed":
        bursty_first = bool(rng.integers(0, 2))
        period = 0
        while len(times) < len(tid):
            is_bursty = (period % 2 == 0) == bursty_first
            mean = BURSTY_MEAN_S if is_bursty else SLOW_MEAN_S
            remaining = len(tid) - len(times)
            if remaining <= 2 * MIN_JOBS_PER_PERIOD:
                n = remaining
            else:
                n = int(rng.integers(MIN_JOBS_PER_PERIOD,
                                     remaining - MIN_JOBS_PER_PERIOD + 1))
            for _ in range(n):
                t += float(rng.exponential(mean))
                times.append(t)
            period += 1
    else:
        mean = BURSTY_MEAN_S if workload == "bursty" else SLOW_MEAN_S
        for _ in range(len(tid)):
            t += float(rng.exponential(mean))
            times.append(t)
    return Skeleton(types, tid, np.asarray(times, np.float64), None)


FAMILIES = {"paper": paper}
_NOT_PARAMS = ("family", "name", "shape_seed", "lanes")


def skeleton(mix: dict, lane: int = 0) -> Skeleton:
    params = {k: v for k, v in mix.items() if k not in _NOT_PARAMS}
    return FAMILIES[mix["family"]](int(mix["shape_seed"]) + lane, **params)


def dealt(sk: Skeleton, seed: int, lane: int = 0) -> Skeleton:
    """The skeleton's jobs dealt to its arrival slots in the order that
    ``(seed, lane)`` draws: same instants, same multiset of jobs."""
    perm = np.random.default_rng([int(seed), int(lane)]).permutation(
        sk.arrival_time.size)
    return Skeleton(sk.types, sk.template_id[perm], sk.arrival_time,
                    None if sk.duration_s is None else sk.duration_s[perm])


def to_trace(sk: Skeleton, name: str):
    """The skeleton as the program's columnar input (``TraceStore``)."""
    from repro.core.pods import PodKind, PodSpec
    from repro.core.resources import Resources
    from repro.scenarios.trace import TraceStore
    specs = []
    for t in sk.types:
        kind, cpu_m, mem_gi, dur = JOB_TYPES[t]
        if kind == "batch":
            specs.append(PodSpec(t, PodKind.BATCH,
                                 Resources(cpu_m, mem_gi * 1024.0),
                                 duration_s=dur))
        else:
            specs.append(PodSpec(t, PodKind.SERVICE,
                                 Resources(cpu_m, mem_gi * 1024.0),
                                 moveable=True))
    return TraceStore(specs, sk.template_id, sk.arrival_time,
                      duration_s=sk.duration_s, name=name)


def job_columns(sk: Skeleton) -> dict:
    """Plain per-row columns for the references: arrival, cpu_m, mem_mb,
    duration (inf for services), is_batch -- no program type involved."""
    kind = np.array([JOB_TYPES[t][0] == "batch" for t in sk.types])
    cpu = np.array([JOB_TYPES[t][1] for t in sk.types], np.int64)
    mem = np.array([JOB_TYPES[t][2] * 1024.0 for t in sk.types])
    tdur = np.array([JOB_TYPES[t][3] for t in sk.types])
    tid = sk.template_id
    dur = tdur[tid] if sk.duration_s is None else np.asarray(sk.duration_s)
    return {"arrival_t": np.asarray(sk.arrival_time, np.float64),
            "cpu_m": cpu[tid], "mem_mb": mem[tid],
            "duration_s": np.where(kind[tid], dur, np.inf),
            "is_batch": kind[tid]}
