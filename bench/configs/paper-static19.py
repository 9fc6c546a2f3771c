"""Plain reference of the ``paper-static19`` deployment.

A static fleet of identical nodes, no autoscaler and no rescheduler,
best-fit placement (paper Alg. 2), replayed as a straightforward discrete-
event simulation with a heap.  It imports nothing of the program: it takes
the deployment's numbers from ``paper-static19.json`` and the jobs from
the benchmark's own generator columns.

Event semantics (the paper's Alg. 1 loop with a 10 s period):

* an arrival is handled before any other event at the same instant;
* other events at one instant run in the order they were scheduled:
  ``CYCLE(0)`` then ``SAMPLE(0)`` first, then each handler schedules its
  successors (a cycle schedules the completions of the batch jobs it
  bound, grouped by completion time in bind order, then the next cycle; a
  sample schedules the next sample 20 s later);
* a cycle walks the pending jobs in arrival order and places each on the
  feasible node with the least free memory (free = allocatable - used;
  CPU must fit exactly, memory with 1e-9 MB of slack); ties go to the
  lowest node rank.  A job that fits nowhere counts one scale-out request.
  A cycle that placed nothing while every job has arrived, no batch job
  runs and some job waits ends the cycles for good;
* a sample records the mean over nodes of used/allocatable memory and
  CPU (exact sums) and the bound jobs per node;
* the run ends after the first event at which every job has arrived,
  every batch job has finished and every service is bound, or at the
  48 h horizon.

Memory is accounted in float64 running sums, one job at a time, as the
deployment states.  ``mem_dtype=np.float32`` computes the same run with
float32 memory: the precision step below the stated one, which is the
control that the comparison has to reject.
"""
from __future__ import annotations

import heapq
import math
import statistics

_ARRIVAL, _CYCLE, _DONE, _SAMPLE = range(4)


def simulate(jobs: dict, dep: dict, mem_dtype=float) -> dict:
    """One static-fleet run of the deployment ``dep`` (the configuration
    file's keys); returns the result row's fields."""
    n_nodes = int(dep["nodes"])
    node = dep["node"]
    alloc_cpu = int(node["allocatable_cpu_m"])
    alloc_mem = mem_dtype(node["allocatable_mem_mb"])
    price = float(node["price_per_s"])
    cycle_s = float(dep["cycle_period_s"])
    sample_s = float(dep["sample_period_s"])
    horizon = float(dep["horizon_s"])

    arr = jobs["arrival_t"]
    cpu = [int(c) for c in jobs["cpu_m"]]
    mem = [mem_dtype(m) for m in jobs["mem_mb"]]
    dur = jobs["duration_s"].tolist()
    is_batch = jobs["is_batch"].tolist()
    n = len(cpu)
    n_batch = sum(is_batch)
    eps = mem_dtype(1e-9)

    # Nodes are identical, so index order is tie order.
    used_cpu = [0] * n_nodes
    used_mem = [mem_dtype(0.0)] * n_nodes
    pods = 0
    bind_t = [None] * n
    bind_node = [-1] * n
    pending = []            # job indices in arrival order, not yet bound
    n_arrived = 0
    batch_done = 0
    svc_bound = 0
    running_batch = 0
    scale_outs = 0
    last_done = None
    samples = []
    sample = None           # the last sample, while no bind or completion
                            # has changed the fleet since

    heap = []
    seq = 0

    def push(t, kind, payload=None):
        nonlocal seq
        heapq.heappush(heap, (t, seq, kind, payload))
        seq += 1

    push(0.0, _CYCLE)
    push(0.0, _SAMPLE)
    completed = False
    now = 0.0
    ai = 0
    while heap or ai < n:
        if ai < n and (not heap or arr[ai] <= heap[0][0]):
            t, kind = float(arr[ai]), _ARRIVAL
        else:
            t, _, kind, payload = heapq.heappop(heap)
        if t > horizon:
            break
        now = t
        if kind == _ARRIVAL:
            pending.append(ai)
            ai += 1
            n_arrived += 1
        elif kind == _CYCLE:
            placed = 0
            blocked_sizes = set()
            still = []
            bound_now = []
            for j in pending:
                size = (cpu[j], mem[j])
                best = -1
                if size not in blocked_sizes:
                    best_free = None
                    for r in range(n_nodes):
                        free_mem = alloc_mem - used_mem[r]
                        if (alloc_cpu - used_cpu[r] >= cpu[j]
                                and free_mem + eps >= mem[j]
                                and (best_free is None
                                     or free_mem < best_free)):
                            best, best_free = r, free_mem
                if best < 0:
                    blocked_sizes.add(size)
                    scale_outs += 1
                    still.append(j)
                    continue
                used_cpu[best] += cpu[j]
                used_mem[best] = used_mem[best] + mem[j]
                pods += 1
                sample = None
                bind_t[j] = t
                bind_node[j] = best
                placed += 1
                if is_batch[j]:
                    running_batch += 1
                    bound_now.append(j)
                else:
                    svc_bound += 1
            pending = still
            # Completions of this cycle's batch binds: one event per
            # completion instant, bind order within it.
            groups = {}
            for j in bound_now:
                groups.setdefault(t + dur[j], []).append(j)
            for td in sorted(groups):
                push(td, _DONE, groups[td])
            stuck = (n_arrived == n and placed == 0 and still
                     and running_batch == 0)
            if not stuck:
                push(t + cycle_s, _CYCLE)
        elif kind == _DONE:
            for j in payload:
                r = bind_node[j]
                used_cpu[r] -= cpu[j]
                used_mem[r] = used_mem[r] - mem[j]
                pods -= 1
                running_batch -= 1
                batch_done += 1
                sample = None
            last_done = t
        elif kind == _SAMPLE:
            if sample is None:
                ram = math.fsum(float(u / alloc_mem) for u in used_mem)
                cpu_r = math.fsum(u / max(alloc_cpu, 1) for u in used_cpu)
                sample = (ram / n_nodes, cpu_r / n_nodes,
                          float(pods) / n_nodes)
            samples.append(sample)
            push(t + sample_s, _SAMPLE)
        if (n_arrived == n and n > 0 and batch_done == n_batch
                and svc_bound == n - n_batch):
            completed = True
            break

    end = last_done if (completed and last_done) else now
    start = float(arr[0]) if n else 0.0
    secs = float(math.ceil(max(0.0, end)))
    cost = 0.0
    for _ in range(n_nodes):
        cost += secs * price
    pend = [bind_t[j] - float(arr[j]) for j in range(n)
            if bind_t[j] is not None]
    return {
        "completed": completed,
        "cost": cost,
        "duration_s": end - start,
        "mean_pending_s": statistics.fmean(pend) if pend else 0.0,
        "median_pending_s": statistics.median(pend) if pend else 0.0,
        "max_pending_s": max(pend) if pend else 0.0,
        "avg_ram_ratio": (statistics.fmean(s[0] for s in samples)
                          if samples else 0.0),
        "avg_cpu_ratio": (statistics.fmean(s[1] for s in samples)
                          if samples else 0.0),
        "avg_pods_per_node": (statistics.fmean(s[2] for s in samples)
                              if samples else 0.0),
        "max_nodes": n_nodes if samples else 0,
        "node_seconds": int(secs * n_nodes),
        "evictions": 0,
        "scale_outs": scale_outs,
        "scale_ins": 0,
        "failures_injected": 0,
        "preemption_notices": 0,
        "lost_work_s": 0.0,
        "n_jobs": n,
    }
