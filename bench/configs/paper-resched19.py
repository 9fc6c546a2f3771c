"""Plain reference of the ``paper-resched19`` deployment.

The paper's full chain (arXiv:1812.00300, sections 6.2 and 7.1, Table
4): a fleet that starts at one static worker, best-fit placement (Alg.
2), the non-binding rescheduler (Alg. 3) with its max pod age, the
binding autoscaler (Alg. 7) for scale-out and Alg. 6 for scale-in,
replayed as a straightforward discrete-event simulation with a heap.  It
imports nothing of the program: it takes the deployment's numbers from
``paper-resched19.json`` and the jobs from the benchmark's own generator
columns.  It is ``paper-bas19.py`` with Alg. 3 put back between a refused
job and the autoscaler.

Event semantics (the paper's Alg. 1 loop with a 10 s period):

* an arrival is handled before any other event at the same instant;
* other events at one instant run in the order they were scheduled:
  ``CYCLE(0)`` then ``SAMPLE(0)`` first, then each handler schedules its
  successors (a cycle schedules the boots of the nodes it launched, then
  the completions of the batch jobs it bound, grouped by completion time
  in bind order, then the next cycle; a sample the next sample 20 s
  later);
* nodes are named ``node-<n>`` in launch order from ``node-0``, the
  static worker; every tie between nodes goes to the name that sorts
  first as a string;
* a cycle walks the pending jobs by (pending since, arrival index) and
  places each on the READY node with the least free memory (free =
  allocatable - used; CPU must fit exactly, memory with 1e-9 MB of
  slack), else on such a TAINTED node; a job that fits nowhere goes to
  Alg. 3 first (below), and only when Alg. 3 fails counts one scale-out
  request and goes to the binding autoscaler: a job already
  promised to a booting node waits for it; else the first booting node
  (by name) whose planned room (allocatable less the jobs promised to it,
  subtracted one by one) holds the job takes the promise; else a node is
  launched for it, billed from now and READY after the provisioning
  delay, when its promises lapse;
* Alg. 3 for a refused job: a job pending for less than ``max_pod_age_s``
  waits (no scale-out).  Else the READY nodes with room for its CPU are
  tried as victims by (free memory, name), in the order
  ``candidate_order`` names (descending, the pseudocode's); a victim's
  moveable jobs, largest memory first (then latest arrival), are placed
  by best-fit on a copy of the free room of the other READY nodes, a job
  with no room skipped, until the memory freed reaches the job's
  shortfall on the victim (its memory less the victim's free memory)
  less 1e-9.  The first victim that frees that much with at least one
  mover is the plan: its movers are evicted in plan order and wait from
  now.  They rejoin the pending jobs from the next cycle on
  (``evictees_rejoin``: ``next_cycle``; ``same_wave`` would append them
  to the cycle's walk), while later jobs of the walk may take the freed
  room at once; the refused job waits too;
* after a cycle in which no job was refused, Alg. 6: remove every empty
  autoscaled READY or TAINTED node, then visit the autoscaled READY nodes
  that hold jobs, in launch order; a node whose jobs are all moveable,
  or that holds both moveable and batch jobs, is consolidated when each
  of its moveable jobs, largest memory first (then latest arrival), fits
  by best-fit on a copy of the free room of the other READY nodes; then
  its moveable jobs are evicted in the order they were bound there and
  wait again from now, and the node is removed (all moveable) or tainted
  (mixed).  Each removal or taint counts one scale-in;
* a cycle that placed nothing and carried out no Alg. 3 plan while every
  job has arrived, a job asked for a node, no batch job runs and no node
  boots ends the cycles for good;
* a sample records, over the READY and TAINTED nodes, their count, the
  mean of used/allocatable memory and CPU (exact sums) and the bound jobs
  per node;
* the run ends after the first event at which every job has arrived,
  every batch job has finished and every service is bound, or at the
  48 h horizon; nodes still up are billed to the end.

Memory is accounted in float64 running sums, one job at a time, as the
deployment states.  ``mem_dtype=np.float32`` computes the same run with
float32 memory: the precision step below the stated one, which is the
control that the comparison has to reject.
"""
from __future__ import annotations

import heapq
import math
import statistics

_ARRIVAL, _CYCLE, _DONE, _READY, _SAMPLE = range(5)
_BOOTING, _UP, _TAINTED = "booting", "ready", "tainted"


def simulate(jobs: dict, dep: dict, mem_dtype=float) -> dict:
    """One autoscaled run of the deployment ``dep`` (the configuration
    file's keys); returns the result row's fields."""
    node_cfg = dep["node"]
    alloc_cpu = int(node_cfg["allocatable_cpu_m"])
    alloc_mem = mem_dtype(node_cfg["allocatable_mem_mb"])
    price = float(node_cfg["price_per_s"])
    boot_s = float(node_cfg["provisioning_delay_s"])
    cycle_s = float(dep["cycle_period_s"])
    max_age = float(dep["max_pod_age_s"])
    descending = {"descending": True,
                  "ascending": False}[dep["candidate_order"]]
    same_wave = {"next_cycle": False,
                 "same_wave": True}[dep["evictees_rejoin"]]
    sample_s = float(dep["sample_period_s"])
    horizon = float(dep["horizon_s"])

    arr = jobs["arrival_t"]
    cpu = [int(c) for c in jobs["cpu_m"]]
    mem = [mem_dtype(m) for m in jobs["mem_mb"]]
    dur = jobs["duration_s"].tolist()
    is_batch = jobs["is_batch"].tolist()
    moveable = [not b for b in is_batch]      # services are moveable
    n = len(cpu)
    n_batch = sum(is_batch)
    eps = mem_dtype(1e-9)

    nodes = []        # every node ever launched, in launch order

    def new_node(state, autoscaled, t):
        node = {"name": f"node-{len(nodes)}", "state": state,
                "autoscaled": autoscaled, "used_cpu": 0,
                "used_mem": mem_dtype(0.0), "jobs": [], "start": t,
                "stop": None, "promised": []}
        nodes.append(node)
        return node

    for _ in range(int(dep["nodes"])):
        new_node(_UP, False, 0.0)
    retired = []      # nodes in the order their billing closed

    pending_since = [None] * n
    where = [None] * n        # the node a job is bound to
    promise = [None] * n      # the booting node a job is promised to
    n_arrived = 0
    batch_done = 0
    svc_bound = 0
    running_batch = 0
    scale_outs = 0
    scale_ins = 0
    evictions = 0
    intervals = []
    last_done = None
    samples = []

    heap = []
    seq = 0

    def push(t, kind, payload=None):
        nonlocal seq
        heapq.heappush(heap, (t, seq, kind, payload))
        seq += 1

    def free(node):
        return alloc_cpu - node["used_cpu"], alloc_mem - node["used_mem"]

    def fits(fc, fm, j):
        return fc >= cpu[j] and fm + eps >= mem[j]

    def best_fit(cands, j):
        """The fitting node with the least free memory, first name on
        ties; cands are (name, free cpu, free mem, node) tuples."""
        best = None
        for name, fc, fm, node in cands:
            if fits(fc, fm, j) and (best is None or (fm, name)
                                    < (best[2], best[0])):
                best = (name, fc, fm, node)
        return best

    def bind(node, j, t):
        nonlocal svc_bound, running_batch
        node["used_cpu"] += cpu[j]
        node["used_mem"] = node["used_mem"] + mem[j]
        node["jobs"].append(j)
        where[j] = node
        intervals.append(t - pending_since[j])
        pending_since[j] = None
        if is_batch[j]:
            running_batch += 1
        else:
            svc_bound += 1

    def unbind(node, j, t):
        nonlocal svc_bound, evictions
        node["used_cpu"] -= cpu[j]
        node["used_mem"] = node["used_mem"] - mem[j]
        node["jobs"].remove(j)
        where[j] = None
        pending_since[j] = t
        svc_bound -= 1
        evictions += 1

    def retire(node, t):
        node["state"] = None
        node["stop"] = t
        retired.append(node)

    def scale_out(j, t):
        if promise[j] is not None:
            return
        for node in sorted((x for x in nodes if x["state"] == _BOOTING),
                           key=lambda x: x["name"]):
            fc, fm = alloc_cpu, alloc_mem
            for k in node["promised"]:
                fc, fm = fc - cpu[k], fm - mem[k]
            if cpu[j] <= fc and mem[j] <= fm + eps:
                node["promised"].append(j)
                promise[j] = node
                return
        node = new_node(_BOOTING, True, t)
        node["promised"].append(j)
        promise[j] = node
        push(t + boot_s, _READY, node)

    def reschedule(j, t):
        """Alg. 3 for the refused job ``j``: "wait", "done" or "failed"."""
        if t - pending_since[j] < max_age:
            return "wait", []
        cands = [x for x in nodes if x["state"] == _UP
                 and free(x)[0] >= cpu[j]]
        cands.sort(key=lambda x: (free(x)[1], x["name"]), reverse=descending)
        for victim in cands:
            movers = [m for m in victim["jobs"] if moveable[m]]
            if not movers:
                continue
            movers.sort(key=lambda m: (mem[m], m), reverse=True)
            room = [[x["name"], *free(x)] for x in nodes
                    if x["state"] == _UP and x is not victim]
            needed = mem[j] - free(victim)[1]
            freed = mem_dtype(0.0)
            plan = []
            for m in movers:
                if freed >= needed - eps:
                    break
                best = None
                for spot in room:
                    if spot[1] >= cpu[m] and spot[2] + eps >= mem[m] and (
                            best is None or (spot[2], spot[0])
                            < (best[2], best[0])):
                        best = spot
                if best is None:
                    continue
                best[1] -= cpu[m]
                best[2] = best[2] - mem[m]
                plan.append(m)
                freed = freed + mem[m]
            if freed >= needed - eps and plan:
                for m in plan:
                    unbind(victim, m, t)
                return "done", plan
        return "failed", []

    def scale_in(t):
        nonlocal scale_ins
        for node in [x for x in nodes if x["autoscaled"] and not x["jobs"]
                     and x["state"] in (_UP, _TAINTED)]:
            retire(node, t)
            scale_ins += 1
        up = None             # free room of the READY nodes, while unchanged
        for node in [x for x in nodes if x["autoscaled"] and x["jobs"]
                     and x["state"] == _UP]:
            movers = [j for j in node["jobs"] if moveable[j]]
            if not movers:
                continue
            if up is None:
                up = [(x["name"], *free(x), x) for x in nodes
                      if x["state"] == _UP]
                most = max(fm for _, _, fm, _ in up)
            movers.sort(key=lambda j: (mem[j], j), reverse=True)
            if most + eps < mem[movers[0]]:
                continue      # the largest fits no READY node at all
            room = [[name, fc, fm] for name, fc, fm, x in up if x is not node]
            ok = True
            for j in movers:
                cj, mj = cpu[j], mem[j]
                best = None
                for spot in room:
                    if spot[1] >= cj and spot[2] + eps >= mj and (
                            best is None or (spot[2], spot[0])
                            < (best[2], best[0])):
                        best = spot
                if best is None:
                    ok = False
                    break
                best[1] -= cj
                best[2] = best[2] - mj
            if not ok:
                continue
            for j in [j for j in node["jobs"] if moveable[j]]:
                unbind(node, j, t)
            if node["jobs"]:
                node["state"] = _TAINTED
            else:
                retire(node, t)
            scale_ins += 1
            up = None

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
            pending_since[ai] = t
            ai += 1
            n_arrived += 1
        elif kind == _CYCLE:
            placed = 0
            refused = 0
            rescheduled = 0
            asked = 0
            bound_now = []
            walk = sorted((j for j in range(n_arrived)
                           if where[j] is None and pending_since[j]
                           is not None),
                          key=lambda j: (pending_since[j], j))
            for j in walk:
                for state in (_UP, _TAINTED):
                    best = best_fit([(x["name"], *free(x), x) for x in nodes
                                     if x["state"] == state], j)
                    if best is not None:
                        break
                if best is None:
                    refused += 1
                    outcome, evicted = reschedule(j, t)
                    if outcome == "done":
                        rescheduled += 1
                        if same_wave:
                            walk.extend(sorted(evicted))
                    elif outcome == "failed":
                        asked += 1
                        scale_outs += 1
                        scale_out(j, t)
                    continue
                bind(best[3], j, t)
                placed += 1
                if is_batch[j]:
                    bound_now.append(j)
            if refused == 0:
                scale_in(t)
            # Completions of this cycle's batch binds: one event per
            # completion instant, bind order within it.
            groups = {}
            for j in bound_now:
                groups.setdefault(t + dur[j], []).append(j)
            for td in sorted(groups):
                push(td, _DONE, groups[td])
            waiting = any(where[j] is None for j in range(n_arrived))
            stuck = (n_arrived == n and placed == 0 and rescheduled == 0
                     and asked > 0 and waiting and running_batch == 0
                     and not any(x["state"] == _BOOTING for x in nodes))
            if not stuck:
                push(t + cycle_s, _CYCLE)
        elif kind == _DONE:
            for j in payload:
                node = where[j]
                node["used_cpu"] -= cpu[j]
                node["used_mem"] = node["used_mem"] - mem[j]
                node["jobs"].remove(j)
                where[j] = None
                running_batch -= 1
                batch_done += 1
            last_done = t
        elif kind == _READY:
            payload["state"] = _UP
            for j in payload["promised"]:
                promise[j] = None
            payload["promised"] = []
        elif kind == _SAMPLE:
            up = [x for x in nodes if x["state"] in (_UP, _TAINTED)]
            k = len(up)
            if k:
                ram = math.fsum(float(x["used_mem"] / alloc_mem) for x in up)
                cpu_r = math.fsum(x["used_cpu"] / max(alloc_cpu, 1)
                                  for x in up)
                pods = sum(len(x["jobs"]) for x in up)
                samples.append((k, ram / k, cpu_r / k, float(pods) / k))
            else:
                samples.append((0, 0.0, 0.0, 0.0))
            push(t + sample_s, _SAMPLE)
        if (n_arrived == n and n > 0 and batch_done == n_batch
                and svc_bound == n - n_batch):
            completed = True
            break

    end = last_done if (completed and last_done) else now
    start = float(arr[0]) if n else 0.0
    cost = 0.0
    node_seconds = 0
    for node in retired + [x for x in nodes if x["state"] is not None]:
        stop = node["stop"] if node["stop"] is not None else end
        secs = float(math.ceil(max(0.0, stop - node["start"])))
        cost += secs * price
        node_seconds += int(secs)
    seen = [s for s in samples if s[0] > 0]
    return {
        "completed": completed,
        "cost": cost,
        "duration_s": end - start,
        "mean_pending_s": statistics.fmean(intervals) if intervals else 0.0,
        "median_pending_s": (statistics.median(intervals) if intervals
                             else 0.0),
        "max_pending_s": max(intervals) if intervals else 0.0,
        "avg_ram_ratio": (statistics.fmean(s[1] for s in seen)
                          if seen else 0.0),
        "avg_cpu_ratio": (statistics.fmean(s[2] for s in seen)
                          if seen else 0.0),
        "avg_pods_per_node": (statistics.fmean(s[3] for s in seen)
                              if seen else 0.0),
        "max_nodes": max((s[0] for s in samples), default=0),
        "node_seconds": node_seconds,
        "evictions": evictions,
        "scale_outs": scale_outs,
        "scale_ins": scale_ins,
        "failures_injected": 0,
        "preemption_notices": 0,
        "lost_work_s": 0.0,
        "n_jobs": n,
    }
