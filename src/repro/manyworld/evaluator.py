"""Lane-batched cell evaluator: ``run_cells`` rows from the lane engine.

``run_cells_lanes`` is the drop-in backend behind
``repro.search.runner.run_cells(..., workers="lanes")``: it takes the same
cell list and returns the same row dicts in the same order, but evaluates
every *lane-eligible* cell inside batched JAX programs
(`repro.manyworld.lanes`) instead of one serial simulation per cell.

**Eligibility** is the lane engine's relaxed-semantics envelope — a
static fleet, or the binding autoscaler with no rescheduler or with Alg.
3, the non-binding one (:func:`lane_eligible`).  Anything outside it (the
other autoscalers, Alg. 4, Alg. 3 on a static fleet, chaos, the object
engine) falls back to the serial ``run_cell`` transparently, so a mixed
cell list still returns one complete row list.

**Exactness.**  For eligible cells the rows are bit-identical to
``run_cell`` (except ``wall_s``, which is wall time and is reported as
the lane's share of its batch).  The lane program reproduces the bind
sequence exactly; this module reconstructs the remaining
``ExperimentResult`` metrics host-side by replaying the serial event
semantics over the lane outputs, with array work over a bucket's lanes
at once (:func:`_bucket_metrics`, in blocks of at most ``_REBUILD_BLOCK``
lane-pods; lanes with no pod take the same path, without the program):

* pending intervals are ``bind_time - submit_time`` per bound row in
  row order (the serial end-of-run column walk);
* the 20 s utilisation samples are replayed over the bind/completion
  events in serial processing order — the event order and the
  sample-tie rules (arrivals win ties; ``POD_DONE(t)`` precedes
  ``CYCLE(t)``; ``SAMPLE(t)`` ordering against both depends on push
  time) decide exactly which events each sample sees and which sample is
  the last one recorded before a completed run breaks.  Node usage is a
  sequential float64 accumulation in event order, as the serial engine
  keeps it, and each distinct sample state is kept once with the
  number of samples that record it;
* every sum the serial run rounds once is rounded once here: a sample's
  ``math.fsum`` of node ratios from exact integer limbs carried along
  the event walk (:func:`_limbs`, :func:`_round_limbs`), and
  ``statistics.fmean`` by :func:`_exact_sums`, a vectorised correctly
  rounded sum; each falls back to ``math.fsum`` wherever it cannot hold
  or prove its sum;
* cost/node-seconds use the serial CostModel formulas: one ``ceil``
  per node record, from launch (t=0 for a static node) to removal or the
  run's end, accumulated left-to-right in retirement order, then the
  nodes still up in launch order.

An autoscaled lane's replay adds its evictions (each closes an
incarnation: its bind and its pending interval are recorded too) and
its nodes' joins and removals, which change the set of nodes a sample
averages over.  Alg. 3 evicts in the middle of a cycle's binds: there
each eviction carries its place in the cycle's one sequence of binds
and evictions (``ev_seq``).

Buckets: lanes group by ``(scheduler, pod-pad, node-pad, autoscaled,
Alg. 3)`` with power-of-two pads, so the jit cache stays small while
mixed workloads share compilations; a lane's Alg. 3 age gate is its own
column, so the gates of one bucket may differ.

**Spans and counts.**  Each call is one ``lanes.call`` span of
``lanes.PROFILER`` (and of a JAX profiler trace, when one runs), with
children ``lanes.prepare`` (trace fetch, lane arrays, bucketing) and, per
bucket, ``lanes.stack``, ``lanes.dispatch``, ``lanes.wait``,
``lanes.fetch`` (those three in ``lanes.run_lane_batch``) and
``lanes.rebuild`` (the rows).  :func:`lane_calls` returns the records of
the last calls: self time per span name, the program's step counts
(``lanes.COUNTERS``) summed over buckets, the rebuild's own counts, and
the lane, bucket and lane-program compilation counts.
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import math
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.manyworld import lanes as _lanes
from repro.manyworld.lanes import (CYCLE_PERIOD_S, HORIZON_S, SCHEDULERS,
                                   next_pow2)

SAMPLE_PERIOD_S = 20.0

#: Records of the last calls of :func:`run_cells_lanes`, oldest first.
_CALLS: collections.deque = collections.deque(maxlen=1024)
_CALL_IDS = itertools.count()
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
#: Traces of the lane program in this process (a persistent-cache hit is
#: traced too), counted from the first call of :func:`run_cells_lanes`.
_compiles = 0
_listening = False


def _on_duration_event(event, _secs, fun_name="", **_kw) -> None:
    global _compiles
    if event == _TRACE_EVENT and fun_name.startswith("lane_program_"):
        _compiles += 1


def _count_compiles() -> None:
    global _listening
    if not _listening:
        import jax
        jax.monitoring.register_event_duration_secs_listener(
            _on_duration_event)
        _listening = True


def lane_calls(n: int) -> List[Dict]:
    """The records of the last ``n`` calls of :func:`run_cells_lanes` in
    this process, oldest first.  Each holds ``call`` (a monotone id),
    ``lanes`` (cells run on the device), ``buckets``, ``compiles`` (lane
    program traces in the call), ``wall_s`` (the ``lanes.call`` span),
    ``self_s`` (self time per span name: the root's is what no child
    covers) and ``counts``: ``lanes.COUNTERS`` summed over the buckets,
    ``lane_steps``, each bucket's lanes times its steps (outer cycles
    plus inner iterations), the most lanes that could have had work,
    ``sample_states``, the utilisation states the rebuild replayed over
    all lanes, ``rebuild_fallbacks``, its pending and average sums whose
    rounding the vectorised sum could not prove, and
    ``node_sum_fallbacks``, its states whose node sums the limbs could not
    hold (each summed by ``math.fsum``)."""
    if n <= 0:
        return []
    return [dict(rec, self_s=dict(rec["self_s"]), counts=dict(rec["counts"]))
            for rec in list(_CALLS)[-n:]]


def lane_eligible(cell) -> bool:
    """True when ``cell`` is inside the lane engine's relaxed envelope:
    no chaos, the array engine and a lane scheduler (``lanes.SCHEDULERS``:
    k8s-default and weighted run on the serial reference on every
    backend), on a static fleet (void autoscaler, no rescheduler) or an
    autoscaled one (the binding autoscaler with Alg. 6 ungated, on a
    template whose provisioning delay is a whole number of cycles longer
    than a sample period), with no rescheduler or with Alg. 3, the
    non-binding one.  The non-binding and predictive autoscalers, Alg. 4
    and Alg. 3 on a static fleet stay serial.  A weighted spec stays
    serial, so an invalid one raises the serial error."""
    if cell.chaos:
        return False
    if cell.rescheduler != "void" and not (
            cell.rescheduler == "non-binding"
            and cell.autoscaler == "binding"):
        return False
    if cell.autoscaler == "binding":
        if cell.scale_in_util_ceiling is not None:
            return False
        if _boot_cycles(cell) is None:
            return False
    elif cell.autoscaler != "void":
        return False
    if cell.engine not in (None, "array"):
        return False
    if cell.scheduler not in SCHEDULERS:
        return False
    if cell.initial_workers < 1:
        return False
    return cell.scheduler_weights is None


def _template_of(cell):
    from repro.cloud.adapter import M2_SMALL, NODE_TEMPLATES
    return (NODE_TEMPLATES[cell.template_name]
            if cell.template_name is not None else M2_SMALL)


def _boot_cycles(cell) -> Optional[int]:
    """The template's provisioning delay in cycles, or None where the lane
    program cannot hold it: an unknown template, a delay that is not a
    whole number of cycles, or one no longer than a sample period (a
    NODE_READY must fire before the SAMPLE at its instant)."""
    from repro.cloud.adapter import NODE_TEMPLATES
    if (cell.template_name is not None
            and cell.template_name not in NODE_TEMPLATES):
        return None
    delay = _template_of(cell).provisioning_delay_s
    cycles = delay / CYCLE_PERIOD_S
    if cycles != int(cycles) or delay <= SAMPLE_PERIOD_S:
        return None
    return int(cycles)


_CELL_FIELDS: tuple = ()


def _cell_dict(cell) -> dict:
    """`dataclasses.asdict(cell)` minus the recursive deepcopy walk —
    every `CellSpec` field is a primitive or a flat tuple, for which
    `asdict` returns the value unchanged, so a getattr sweep builds an
    `==`-identical dict at a fraction of the cost (the serial `run_cell`
    row this must match bit-for-bit uses `asdict`)."""
    global _CELL_FIELDS
    if not _CELL_FIELDS:
        _CELL_FIELDS = tuple(f.name for f in dataclasses.fields(cell))
    return {name: getattr(cell, name) for name in _CELL_FIELDS}


def _base_row(cell, trace, infeasible: bool) -> dict:
    from repro.search.runner import _RESULT_FIELDS
    row = {"label": cell.label, "cell": _cell_dict(cell),
           "n_jobs": trace.n, "infeasible": infeasible}
    if infeasible:
        for field in _RESULT_FIELDS:
            row[field] = False if field == "completed" else 0
        row["wall_s"] = 0.0
    return row


#: Columns summed at a time by :func:`_exact_sums`: a block's working
#: vectors stay in cache through its passes.
_SUM_BLOCK = 1 << 14
#: Lanes times pod pad rebuilt at a time by :func:`_rebuild`: the
#: rebuild's event and state arrays grow with both, so a bucket of long
#: traces is rebuilt in blocks of lanes and its memory stays bounded.
_REBUILD_BLOCK = 1 << 18


def _exact_sums(x: np.ndarray) -> Tuple[np.ndarray, int]:
    """``math.fsum(x[:, j])`` for every column ``j`` of the finite float64
    array ``x``: the exact sum rounded once, ties to even.  Returns the
    sums and how many columns the vectorised passes could not prove.

    The rows are added with two passes of error-free additions (TwoSum),
    so that the exact sum is ``s + r + f``: ``s`` the plain sum, ``r`` the
    sum of its rounding errors, ``f`` the rounding errors of ``r``.  Where
    ``f`` is all zero the exact sum is ``s + r``, and one IEEE addition
    rounds it correctly.  Elsewhere ``s + r`` is kept only where its own
    rounding error plus twice ``sum |f|`` stays clearly inside half the
    smaller gap to a neighbouring float, which proves that no rounding
    boundary lies between; every other column is summed by ``math.fsum``.
    """
    res = np.empty(x.shape[1])
    fallbacks = 0
    for c in range(0, x.shape[1], _SUM_BLOCK):
        xb = x[:, c:c + _SUM_BLOCK]
        s = xb[0].copy()
        r = np.zeros_like(s)
        f_abs = np.zeros_like(s)
        t, z, w, e = (np.empty_like(s) for _ in range(4))
        for b in xb[1:]:
            np.add(s, b, out=t)                    # TwoSum(s, b) -> t, e
            np.subtract(t, s, out=z)
            np.subtract(s, np.subtract(t, z, out=w), out=w)
            np.add(w, np.subtract(b, z, out=e), out=e)
            s, t = t, s
            np.add(r, e, out=t)                    # TwoSum(r, e) -> t, f
            np.subtract(t, r, out=z)
            np.subtract(r, np.subtract(t, z, out=w), out=w)
            np.add(w, np.subtract(e, z, out=z), out=w)
            f_abs += np.abs(w, out=w)
            r, t = t, r
        sum_b = s + r
        z = sum_b - s
        err = (s - (sum_b - z)) + (r - z)
        a = np.abs(sum_b)
        gap = np.minimum(np.nextafter(a, np.inf) - a,
                         a - np.nextafter(a, 0.0))
        proven = (f_abs == 0.0) | ((a >= 2.0 ** -900) & (
            np.abs(err) + 2.0 * f_abs < 0.5 * gap * (1.0 - 2.0 ** -20)))
        slow = np.flatnonzero(~proven)
        if slow.size:
            sum_b[slow] = [math.fsum(col)
                           for col in xb[:, slow].T.tolist()]
            fallbacks += slow.size
        res[c:c + _SUM_BLOCK] = sum_b
    return res, fallbacks


#: The most nodes a state's limb sums may count: each limb is below
#: 2**53, so the sums of 1,023 stay inside int64 (:func:`_limbs`).
_LIMB_NODES = 1023


def _limbs(r: np.ndarray, out: Optional[np.ndarray] = None):
    """``r`` as three int64 limbs, ``r == a * 2**-32 + b * 2**-85 + c *
    2**-138`` exactly, each with the sign of ``r`` and below 2**53 in
    magnitude, in ``out`` (shape ``(3, r.size)``, made if not given); and
    ``held``, where they hold ``r``.

    ``x = r * 2**32`` is exact, and so is each step after it: ``a`` is
    its whole part, the fraction ``x - a`` is a multiple of ``x``'s ulp
    below one, and scaling it by 2**53 gives ``b`` as its whole part and
    the next fraction, of which ``c`` is the whole part.  ``c`` takes
    the rest exactly where ``r`` is a multiple of 2**-138, which every
    magnitude from 2**-86 (an ulp of 2**-138) is.  ``held`` also asks
    ``|r| < 2**11``, so that the sums of :data:`_LIMB_NODES` ratios keep
    ``a`` below 2**53 (:func:`_round_limbs`).  Where ``held`` is false
    the limbs are some integers."""
    x = r * 2.0 ** 32
    held = np.abs(x) < 2.0 ** 43
    np.copyto(x, 0.0, where=~held)
    whole = np.trunc(x)
    if out is None:
        out = np.empty((3, r.size), np.int64)
    out[0] = whole
    np.subtract(x, whole, out=x)
    np.multiply(x, 2.0 ** 53, out=x)
    np.trunc(x, out=whole)
    out[1] = whole
    np.subtract(x, whole, out=x)
    np.multiply(x, 2.0 ** 53, out=x)
    out[2] = x
    held &= out[2] == x
    return out, held


def _carry(a, b, c):
    """Limb sums with the carries moved up: ``b`` and ``c`` in
    [0, 2**53), the sum unchanged."""
    q = c >> 53
    b = b + q
    c = c - (q << 53)
    q = b >> 53
    return a + q, b - (q << 53), c


def _round_limbs(a: np.ndarray, b: np.ndarray, c: np.ndarray):
    """The exact ``a * 2**-32 + b * 2**-85 + c * 2**-138`` of int64 limb
    sums, rounded once to float64, ties to even: ``math.fsum``'s bits for
    the values the limbs were summed from, where the carried ``a`` stays
    below 2**53 (sums of at most :data:`_LIMB_NODES` held limbs).

    After the carries (:func:`_carry`) a negative sum is rounded as its
    negation, so ``a >= 0``.  Without ``c`` the sum is two exact doubles,
    and one IEEE addition rounds it.  With ``c`` and ``a`` zero it is the
    same of ``b`` and ``c`` parts.  With both, the sum is at least 2**-32,
    so its neighbouring doubles lie at least 2**-84 apart and every
    rounding midpoint is a multiple of 2**-85, as the ``a`` and ``b``
    parts are: the ``c`` part, below 2**-85, moves the rounding of their
    sum only where that sum is itself a midpoint rounded down, which it
    then rounds up."""
    a, b, c = _carry(a, b, c)
    neg = np.flatnonzero(a < 0)
    a[neg], b[neg], c[neg] = _carry(-a[neg], -b[neg], -c[neg])
    res = a * 2.0 ** -32 + b * 2.0 ** -85
    low = np.flatnonzero(c)
    hi, mid = a[low] * 2.0 ** -32, b[low] * 2.0 ** -85
    s = res[low]
    e = mid - (s - hi)                        # Fast2Sum: hi >= mid, or 0
    up = np.nextafter(s, np.inf)
    res[low] = np.where(a[low] == 0, mid + c[low] * 2.0 ** -138,
                        np.where((e > 0) & (2.0 * e == up - s), up, s))
    res[neg] = -res[neg]
    return res


def _split(v: np.ndarray):
    """Veltkamp's split: ``v == hi + lo`` exactly, each half with at most
    26 significant bits, so ``m * hi`` and ``m * lo`` are exact for any
    integer ``m < 2**27``."""
    p = v * 134217729.0                    # 2**27 + 1
    hi = p - (p - v)
    return hi, v - hi


def _cycle_k(tc):
    """First sample grid index that sees an effect of the cycle at ``tc``
    (a bind, an eviction, a node removal): the next grid point after
    ``tc`` (SAMPLE(t) runs before CYCLE(t) for t > 0), except cycle 0,
    whose effects sample at t = 0 (run() pushes CYCLE(0) before
    SAMPLE(0))."""
    return np.where(tc == 0.0, 0.0, np.floor(tc / SAMPLE_PERIOD_S) + 1.0)


def _bucket_metrics(entries: list, batch, out: dict):
    """One bucket's ExperimentResult fields, rebuilt from its lane outputs
    with array work over the whole bucket.

    ``batch`` is the bucket's :class:`~repro.manyworld.lanes.LaneBatch`
    (pod columns and cluster scalars), ``out`` the program's per-pod
    ``bound`` / ``bind_node`` / ``bind_seq`` / ``bind_cycle`` / ``done_t``
    / ``done_committed`` and per-lane ``completed`` / ``done_time`` /
    ``done_is_cycle`` / ``scale_outs``; an autoscaled bucket's also hold
    its node records, evictions and ``pend`` (``lanes.run_lane_batch``).
    Returns one field dict per lane, the sample states replayed, the
    sums :func:`_exact_sums` summed by ``math.fsum`` and the states whose
    node sums were.  Every formula is the serial one, applied in the
    serial order, and every float sum that the serial run rounds once
    (``fsum``, ``fmean``) is rounded once here.
    """
    SP = SAMPLE_PERIOD_S
    L, P = batch.valid.shape
    lanes = np.arange(L)
    valid = batch.valid
    fleet = batch.autoscale
    n_nodes = batch.n_nodes.astype(np.int64)
    N = batch.n_pad if fleet else int(n_nodes.max())
    acpu = np.maximum(batch.alloc_cpu.astype(np.float64), 1.0)
    price = np.array([float(e[3].price_per_s) for e in entries])

    bound = out["bound"] & valid
    done = out["done_committed"] & valid
    bind_t = out["bind_cycle"].astype(np.float64) * CYCLE_PERIOD_S
    done_t, seq = out["done_t"], out["bind_seq"]
    completed, te = out["completed"], out["done_time"]
    if fleet:
        # The closed incarnations of evicted pods: bound at ev_bind_t,
        # pending since ev_pend, evicted at ev_t.
        X = out["ev_pod"].shape[1]
        logged = np.arange(X) < out["ev_n"][:, None]
        ev_bind_t = out["ev_bind_cycle"].astype(np.float64) * CYCLE_PERIOD_S
        ev_t = out["ev_cycle"].astype(np.float64) * CYCLE_PERIOD_S
        seq_of_index = _lanes.node_layout(N)[0]

    # -- end of run (simulation.run: last_batch_done wins when truthy) --
    lbd_raw = np.where(done, done_t, -np.inf).max(axis=1)
    lbd = np.where(done.any(axis=1), lbd_raw, 0.0)
    end = np.where(completed, np.where(lbd != 0.0, lbd, te), HORIZON_S)
    a0 = batch.arrival_t[:, 0]
    start = np.where(valid[:, 0] & (a0 <= HORIZON_S), a0, 0.0)

    # -- pending intervals (store.pending_intervals_all: one per bind, of
    # every incarnation) ------------------------------------------------
    if fleet:
        pend = np.concatenate([bind_t - out["pend"],
                               ev_bind_t - out["ev_pend"]], axis=1)
        pend_ok = np.concatenate([bound, logged], axis=1)
    else:
        pend, pend_ok = bind_t - batch.arrival_t, bound
    n_pend = pend_ok.sum(axis=1)
    pend_sum, fallbacks = _exact_sums(np.where(pend_ok, pend, 0.0).T.copy())
    srt = np.sort(np.where(pend_ok, pend, np.inf), axis=1)
    half = n_pend // 2
    median = np.where(n_pend % 2 == 1, srt[lanes, half],
                      (srt[lanes, np.maximum(half - 1, 0)]
                       + srt[lanes, half]) / 2)
    top = srt[lanes, np.maximum(n_pend - 1, 0)]

    # -- utilisation sample replay --------------------------------------
    # Each lane's events in serial processing order, (time, kind, seq):
    # NODE_READY (-1, pushed a provisioning delay before, so before every
    # other event of its instant that a sample can tell apart), POD_DONE
    # (0) before the cycle's binds (1), its evictions (2) and node
    # removals (3) at equal times; equal-time completions fire in
    # scheduling-push order == ascending bind_seq, and a node's evictions
    # in bind order.  With Alg. 3 (``ev_seq`` in ``out``) evictions happen
    # among the binds: they are of kind 1 there, and the cycle's one
    # sequence of binds and evictions orders them.  Padding and missing
    # events sort last.  Each event
    # carries the first sample (grid index k, time 20 k) that can see it:
    # * a cycle's effect at tc: :func:`_cycle_k`;
    # * a completion at td is visible from td itself when td is on-grid
    #   and its POD_DONE was pushed (at its bind cycle tc) before
    #   SAMPLE(td) was (at td-20) — i.e. tc < td-20, or the cycle-0
    #   corner tc==0, td==20 — else from the next grid point after td;
    # * a node's NODE_READY at tr, pushed at its launch, more than a
    #   sample period before: from the first grid point at or after tr.
    # Events carry (node, signed memory, signed cpu): node events their
    # node and no usage.
    td = np.where(done, done_t, 0.0)
    early = ((np.fmod(td, SP) == 0.0)
             & ((bind_t < td - SP) | ((bind_t == 0.0) & (td == SP))))
    kd = np.where(early, td / SP, np.floor(td / SP) + 1.0)
    node = out["bind_node"]
    mem_p = batch.mem_mb
    cpu_p = batch.cpu_m.astype(np.float64)
    groups = [  # time, kind, seq, live, k, node, sign, mem, cpu
        (done_t, 0, seq, done, kd, node, -1, mem_p, cpu_p),
        (bind_t, 1, seq, bound, _cycle_k(bind_t), node, 1, mem_p, cpu_p)]
    if fleet:
        ev_pod = out["ev_pod"]
        ev_mem = np.take_along_axis(mem_p, ev_pod, axis=1)
        ev_cpu = np.take_along_axis(cpu_p, ev_pod, axis=1)
        ev_seq, ev_node = out["ev_bind_seq"], out["ev_node"]
        nstate = out["nstate"]
        autoscaled = seq_of_index[None, :] >= n_nodes[:, None]
        launched = autoscaled & (nstate != _lanes.NODE_NONE)
        ready_t = (out["launch_k"] + batch.boot_cycles[:, None]).astype(
            np.float64) * CYCLE_PERIOD_S
        gone = out["gone_k"] >= 0
        gone_t = out["gone_k"].astype(np.float64) * CYCLE_PERIOD_S
        zero_n = np.zeros((L, N), np.int64)
        zero_f = np.zeros((L, N))
        node_n = np.broadcast_to(np.arange(N), (L, N))
        ev_kind, ev_order = ((1, out["ev_seq"]) if "ev_seq" in out
                             else (2, ev_seq))
        groups += [
            (ev_bind_t, 1, ev_seq, logged, _cycle_k(ev_bind_t), ev_node, 1,
             ev_mem, ev_cpu),
            (ev_t, ev_kind, ev_order, logged, _cycle_k(ev_t), ev_node, -1,
             ev_mem, ev_cpu),
            (ready_t, -1, zero_n, launched, np.ceil(ready_t / SP), node_n,
             0, zero_f, zero_f),
            (gone_t, 3, zero_n, gone, _cycle_k(gone_t), node_n, 0, zero_f,
             zero_f)]
    widths = [g[3].shape[1] for g in groups]
    E = sum(widths)
    kinds = np.concatenate([np.full(w, g[1], np.int8)
                            for g, w in zip(groups, widths)])
    order = np.lexsort((
        np.concatenate([g[2] for g in groups], axis=1),
        np.broadcast_to(kinds, (L, E)),
        np.concatenate([np.where(g[3], g[0], np.inf) for g in groups],
                       axis=1)), axis=1)

    def at_events(field, cols=E):
        """One field of each lane's first ``cols`` events, in row order."""
        return np.take_along_axis(np.concatenate(
            [np.broadcast_to(g[field], (L, w))
             for g, w in zip(groups, widths)], axis=1), order[:, :cols],
            axis=1)

    live = at_events(3)
    # A sample applies the events in order up to the first it cannot see
    # yet, so an event is applied at the first sample at or after every
    # visibility time up to it: a running maximum along the lane.
    ev_k = np.maximum.accumulate(np.where(live, at_events(4), 0.0),
                                 axis=1).astype(np.int64)

    # The last sample recorded.  Non-completed lanes sample the whole
    # horizon.  A completed lane breaks on its trigger event at te: every
    # grid point strictly before te is in; the grid point *at* te is in
    # iff the trigger ran after SAMPLE(te) — for a CYCLE trigger that is
    # every te>0, for a POD_DONE trigger it is the complement of the
    # completion-visibility push rule above, judged on the trigger pod
    # (the last committed: latest done_t, then highest bind_seq).
    trig = np.argmax(np.where(done & (done_t == lbd_raw[:, None]), seq, -1),
                     axis=1)
    tc = bind_t[lanes, trig]
    pod_done_first = (tc < te - SP) | ((tc == 0.0) & (te == SP))
    last_k = np.where(
        ~completed, HORIZON_S / SP,
        np.where((np.fmod(te, SP) == 0.0) & (te > 0.0),
                 np.where(out["done_is_cycle"] | ~pod_done_first,
                          te / SP, te / SP - 1.0),
                 np.ceil(te / SP) - 1.0)).astype(np.int64)

    # Sample states: a lane holds one state from grid point 0 and a new
    # one from each later visibility time up to its last sample, each
    # recorded for ``m`` samples.  States are numbered lane by lane.
    applied = live & (ev_k <= last_k[:, None])
    same_k = np.zeros_like(applied)
    same_k[:, 1:] = ev_k[:, 1:] == ev_k[:, :-1]
    new = applied & (ev_k > 0) & ~same_k
    per_lane = np.where(last_k >= 0, 1 + new.sum(axis=1), 0)
    first = np.cumsum(per_lane) - per_lane
    n_seg = int(per_lane.sum())
    seg = first[:, None] + np.cumsum(new, axis=1)
    seg_lane = np.repeat(lanes, per_lane)
    seg_k = np.zeros(n_seg, np.int64)
    seg_k[seg[new]] = ev_k[new]
    m = np.append(seg_k[1:] - seg_k[:-1], 0)
    has_seg = per_lane > 0
    ends = (first + per_lane - 1)[has_seg]
    m[ends] = last_k[has_seg] - seg_k[ends] + 1

    # The walk: one event column at a time for every lane at once.  Each
    # event applies its node's usage, ``used[node] += delta``, a
    # sequential float64 accumulation in event order (the serial engine's
    # own), and its node's membership: a node counts in its lane's
    # samples from the start (a static node) or from its NODE_READY, to
    # its removal.  Where the node counts, the event adds to its lane's
    # sums the limbs of the node's ratio after it and takes away those
    # before it (:func:`_limbs`), so a state's node sums are the lane's
    # running limb sums at its closing event, exact, and are rounded once
    # (:func:`_round_limbs`).  A state with a ratio the limbs cannot hold,
    # or more nodes than their sums can, is summed there by ``math.fsum``
    # over its lane's counted nodes, and counted.
    closes = applied.copy()
    closes[:, :-1] &= ~(applied[:, 1:] & same_k[:, 1:])
    n_cols = int(applied.any(axis=0).sum())
    act = applied[:, :n_cols]
    sign = at_events(6, n_cols)
    flat = (np.where(act, at_events(5, n_cols), 0) * L
            + lanes[:, None]).T.copy()
    deltas = [np.where(act, sign * at_events(f, n_cols), 0.0).T.copy()
              for f in (7, 8)]
    kind = at_events(1, n_cols)
    marks = np.where(act, (kind == -1) + 2 * (kind == 3), 0).T.astype(
        np.int8)                   # 1: NODE_READY, 2: removal
    # Membership bits, (node, lane): 1 once READY (a static node from the
    # start), 2 once removed; a node counts while it reads 1.
    seq = seq_of_index if fleet else np.arange(N)
    member = (seq[:, None] < n_nodes[None, :]).astype(np.int8).ravel()
    count = member.reshape(N, L).sum(axis=0)
    used = [np.zeros(N * L) for _ in deltas]
    allocs = (batch.alloc_mem, acpu)
    ratio = np.empty((2, 2, L))             # (before, after) x (mem, cpu)
    limbs = np.empty((2, 3, 2 * L), np.int64)
    limb_sums = np.zeros((3, 2 * L), np.int64)
    unheld = np.zeros(2 * L, np.int64)
    col, lane_at = np.nonzero(closes[:, :n_cols].T)
    cuts = np.cumsum(np.bincount(col, minlength=n_cols))
    state = seg[lane_at, col]
    state_sums = np.zeros((3, 2, state.size), np.int64)
    nn = count[seg_lane]
    by_fsum = []
    c0 = 0
    for j in range(n_cols):
        f = flat[j]
        m_b = member.take(f)
        m_a = m_b | marks[j]
        member[f] = m_a
        for r, (u, d, alloc) in enumerate(zip(used, deltas, allocs)):
            b = u.take(f)
            np.divide(b, alloc, out=ratio[0, r])
            b += d[j]
            u[f] = b
            np.divide(b, alloc, out=ratio[1, r])
        c_b, c_a = m_b == 1, m_a == 1
        ratio[0] *= c_b
        ratio[1] *= c_a
        for phase, add in ((1, np.add), (0, np.subtract)):
            _, held = _limbs(ratio[phase].ravel(), out=limbs[phase])
            add(limb_sums, limbs[phase], out=limb_sums)
            if not held.all():
                add(unheld, ~held, out=unheld)
        count += c_a
        count -= c_b
        c1 = cuts[j]
        cl = lane_at[c0:c1]
        state_sums[:, 0, c0:c1] = limb_sums[:, cl]
        state_sums[:, 1, c0:c1] = limb_sums[:, L + cl]
        nn[state[c0:c1]] = count[cl]
        if unheld.any() or count.max() > _LIMB_NODES:
            for i in c0 + np.flatnonzero((unheld[cl] + unheld[L + cl] > 0)
                                         | (count[cl] > _LIMB_NODES)):
                ln = lane_at[i]
                on = member[ln::L] == 1
                by_fsum.append((state[i], *(
                    math.fsum(u[ln::L][on] / alloc[ln])
                    for u, alloc in zip(used, allocs))))
        c0 = c1
    ram, cpu_r = np.zeros(n_seg), np.zeros(n_seg)
    ram[state], cpu_r[state] = (_round_limbs(*state_sums[:, r])
                                for r in (0, 1))
    for i, mem_sum, cpu_sum in by_fsum:
        ram[i], cpu_r[i] = mem_sum, cpu_sum
    pods = np.zeros(n_seg, np.int64)
    pods[seg[closes]] = np.cumsum(act * sign, axis=1)[closes[:, :n_cols]]
    node_fallbacks = len(by_fsum)

    # Each lane's average is fmean over its samples, m copies of each
    # state: m * v splits into two exact products (:func:`_split`).
    # (Static nodes are never removed, so every sample sees one.)
    hi, lo = _split(np.stack([ram / nn, cpu_r / nn,
                              pods.astype(np.float64) / nn], axis=1))
    pos = np.arange(n_seg) - first[seg_lane]
    terms = np.zeros((2 * max(int(per_lane.max()), 1), L, 3))
    terms[2 * pos, seg_lane] = m[:, None] * hi
    terms[2 * pos + 1, seg_lane] = m[:, None] * lo
    sums, n = _exact_sums(terms.reshape(terms.shape[0], 3 * L))
    fallbacks += n
    n_samples = np.bincount(seg_lane, m, minlength=L).astype(np.int64)
    avgs = np.where((n_samples > 0)[:, None],
                    sums.reshape(L, 3) / np.maximum(n_samples, 1)[:, None],
                    0.0)

    # -- cost (CostModel: per node record ceil(max(0, stop - start)) *
    # price, summed left-to-right in record order: the records closed by
    # Alg. 6 as they retired, then the open ones in provision order) ----
    if fleet:
        exists = nstate != _lanes.NODE_NONE
        start_n = out["launch_k"].astype(np.float64) * CYCLE_PERIOD_S
        stop_n = np.where(gone, gone_t, end[:, None])
        secs_n = np.where(exists, np.ceil(np.maximum(0.0, stop_n - start_n)),
                          0.0)
        rec = np.lexsort((np.broadcast_to(seq_of_index, (L, N)),
                          out["gone_step"], np.where(gone, out["gone_k"], 0),
                          ~gone, ~exists), axis=1)
        term = np.take_along_axis(secs_n, rec, axis=1) * price[:, None]
        real = np.take_along_axis(exists, rec, axis=1)
        cost = np.zeros(L)
        for k in range(N):
            cost = np.where(real[:, k], cost + term[:, k], cost)
        node_secs = secs_n.sum(axis=1).astype(np.int64)
    else:
        secs = np.ceil(np.maximum(0.0, end))
        term = secs * price
        cost = np.zeros(L)
        for k in range(N):
            cost = np.where(k < n_nodes, cost + term, cost)
        node_secs = (secs * n_nodes).astype(np.int64)
    max_nodes = np.zeros(L, np.int64)
    np.maximum.at(max_nodes, seg_lane, nn)

    has = n_pend > 0
    zeros = np.zeros(L, np.int64)
    cols = {
        "completed": completed.astype(bool),
        "cost": cost,
        "duration_s": end - start,
        "mean_pending_s": np.where(has, pend_sum / np.maximum(n_pend, 1),
                                   0.0),
        "median_pending_s": np.where(has, median, 0.0),
        "max_pending_s": np.where(has, top, 0.0),
        "avg_ram_ratio": avgs[:, 0],
        "avg_cpu_ratio": avgs[:, 1],
        "avg_pods_per_node": avgs[:, 2],
        "max_nodes": max_nodes,
        "node_seconds": node_secs,
        "evictions": out["ev_n"].astype(np.int64) if fleet else zeros,
        "scale_outs": out["scale_outs"].astype(np.int64),
        "scale_ins": out["scale_ins"].astype(np.int64) if fleet else zeros,
        "failures_injected": zeros,
        "preemption_notices": zeros,
        "lost_work_s": np.zeros(L),
    }
    names = list(cols)
    metrics = [dict(zip(names, vals))
               for vals in zip(*(cols[k].tolist() for k in names))]
    return metrics, n_seg, fallbacks, node_fallbacks


def _no_steps(batch) -> dict:
    """The lane outputs of a program that takes no step, as for lanes
    with no pod: nothing bound or committed, never completed."""
    L, P = batch.valid.shape
    return {"bound": np.zeros((L, P), bool),
            "done_committed": np.zeros((L, P), bool),
            "bind_node": np.full((L, P), -1, np.int32),
            "bind_seq": np.full((L, P), -1, np.int32),
            "bind_cycle": np.full((L, P), -1, np.int32),
            "done_t": np.full((L, P), np.inf),
            "completed": np.zeros(L, bool),
            "done_time": np.full(L, HORIZON_S),
            "done_is_cycle": np.zeros(L, bool),
            "scale_outs": np.zeros(L, np.int32)}


def run_cells_lanes(cells: Sequence) -> List[dict]:
    """Evaluate ``cells`` with the lane engine; serial-identical rows in
    submission order.  Ineligible cells run through the serial
    ``run_cell`` unchanged."""
    cells = list(cells)
    rows: List[Optional[dict]] = [None] * len(cells)
    _count_compiles()
    compiles0 = _compiles
    span = _lanes.PROFILER.span
    program_counts = (_lanes.COUNTERS + _lanes.FLEET_COUNTERS
                      + _lanes.RESCHED_COUNTERS)
    counts = dict.fromkeys(program_counts + (
        "lane_steps", "node_cycles", "sample_states", "rebuild_fallbacks",
        "node_sum_fallbacks", "lane_fallbacks"), 0)
    n_lanes = 0
    with span("lanes.call") as root:
        with span("lanes.prepare"):
            buckets, idle = _prepare(cells, rows)
        for (sched, p_pad, n_pad, fleet, resched), entries in buckets.items():
            t0 = time.perf_counter()
            with span("lanes.stack"):
                batch = _lanes.stack_lanes([e[4] for e in entries], sched,
                                           p_pad=p_pad,
                                           node_pad=n_pad if fleet else None,
                                           resched=resched)
            out = _lanes.run_lane_batch(batch)
            share = (time.perf_counter() - t0) / len(entries)
            with span("lanes.rebuild"):
                got = {key: int(out.pop(key)) for key in program_counts
                       if key in out}
                for key, val in got.items():
                    counts[key] += val
                counts["lane_steps"] += len(entries) * (
                    got["n_cycles"] + got["wave_steps"]
                    + got["completion_steps"])
                if fleet:
                    counts["node_cycles"] += (len(entries) * got["n_cycles"]
                                              * n_pad)
                n_lanes += len(entries)
                _rebuild(entries, batch, out, share, rows, counts)
        if idle:
            # Lanes with no pod take no program step: their rows come
            # from the same rebuild, without JAX.
            with span("lanes.rebuild"):
                t0 = time.perf_counter()
                batch = _lanes.stack_lanes([e[4] for e in idle],
                                           idle[0][1].scheduler)
                _rebuild(idle, batch, _no_steps(batch), 0.0, rows, counts)
                share = (time.perf_counter() - t0) / len(idle)
                for e in idle:
                    rows[e[0]]["wall_s"] = share
    self_s = _lanes.PROFILER.self_times(root)
    _CALLS.append({"call": next(_CALL_IDS), "lanes": n_lanes,
                   "buckets": len(buckets),
                   "compiles": _compiles - compiles0,
                   "wall_s": sum(self_s.values()), "self_s": self_s,
                   "counts": counts})
    assert all(r is not None for r in rows)
    return rows


def _prepare(cells: list, rows: list):
    """Fill ``rows`` for the cells that need no lane, and bucket the rest:
    ``(scheduler, pod-pad, node-pad, autoscaled, Alg. 3) -> [(idx, cell,
    trace, template, lane arrays)]``; lanes with no pod go apart, to the
    second list."""
    from repro.search.runner import (CellError, _get_trace, _infeasible,
                                     run_cell)
    buckets, idle = {}, []
    for idx, cell in enumerate(cells):
        try:
            if not lane_eligible(cell):
                rows[idx] = run_cell(cell)
                continue
            trace = _get_trace(cell.scenario, cell.seed, cell.n_jobs)
            template = _template_of(cell)
            if _infeasible(cell, trace):
                rows[idx] = _base_row(cell, trace, infeasible=True)
                continue
            lane = trace.to_lane_arrays()
            lane["n_nodes"] = cell.initial_workers
            lane["alloc_cpu"] = template.allocatable.cpu_m
            lane["alloc_mem"] = float(template.allocatable.mem_mb)
            entry = (idx, cell, trace, template, lane)
            if trace.n == 0:
                # No pod: no launch either, so the static rebuild holds.
                idle.append(entry)
                continue
            if cell.autoscaler == "binding":
                lane["boot_cycles"] = _boot_cycles(cell)
                lane["max_pod_age_s"] = float(cell.max_pod_age_s)
                key = (cell.scheduler, next_pow2(trace.n),
                       _node_records(cell, trace), True,
                       cell.rescheduler == "non-binding")
            else:
                key = (cell.scheduler, next_pow2(trace.n),
                       next_pow2(cell.initial_workers), False, False)
            buckets.setdefault(key, []).append(entry)
        except CellError:
            raise
        except Exception as exc:
            raise CellError(f"cell {cell.label} failed: {exc!r}") from exc
    return buckets, idle


def _node_records(cell, trace) -> int:
    """Node records of an autoscaled lane: its static nodes plus one
    launch per pod, to a power of two.  Nothing bounds a lane's launches
    (a pod may ask again each time its node boots without it), so a lane
    that needs more records stops and runs serially (:func:`_rebuild`):
    the bound sets the program's width, never a row."""
    return next_pow2(cell.initial_workers + trace.n)


def _rebuild(entries: list, batch, out: dict, share: float, rows: list,
             counts: dict) -> None:
    """One bucket's rows from its lane outputs (counters taken out), in
    blocks of ``_REBUILD_BLOCK`` lane-pods; adds the sample states and
    ``math.fsum`` fallbacks to ``counts``.  A lane that ran past its node
    or eviction records (``overflow``) runs through the serial
    ``run_cell`` instead, counted in ``lane_fallbacks``."""
    from repro.search.runner import CellError, run_cell
    step = max(1, _REBUILD_BLOCK // batch.p_pad)
    arrays = [f.name for f in dataclasses.fields(batch)
              if isinstance(getattr(batch, f.name), np.ndarray)]
    over = out.pop("overflow", np.zeros(len(entries), bool))
    for i in np.flatnonzero(over):
        idx, cell = entries[i][:2]
        try:
            rows[idx] = run_cell(cell)
        except Exception as exc:
            raise CellError(f"cell {cell.label} failed: {exc!r}") from exc
    counts["lane_fallbacks"] += int(over.sum())
    keep = np.flatnonzero(~over) if over.any() else None
    n_keep = len(entries) if keep is None else keep.size
    for lo in range(0, n_keep, step):
        lanes = (slice(lo, lo + step) if keep is None
                 else keep[lo:lo + step])
        block = (entries[lanes] if keep is None
                 else [entries[i] for i in lanes])
        sub = dataclasses.replace(
            batch, **{name: getattr(batch, name)[lanes] for name in arrays})
        try:
            metrics, states, fallbacks, node_fallbacks = _bucket_metrics(
                block, sub, {key: val[lanes] for key, val in out.items()})
        except Exception as exc:
            raise CellError(f"{len(block)} lane cells from "
                            f"{block[0][1].label} failed: {exc!r}") from exc
        for (idx, cell, trace, _template, _lane), fields in zip(block,
                                                              metrics):
            row = _base_row(cell, trace, infeasible=False)
            row.update(fields)
            row["wall_s"] = share
            rows[idx] = row
        counts["sample_states"] += states
        counts["rebuild_fallbacks"] += fallbacks
        counts["node_sum_fallbacks"] += node_fallbacks
