"""Many-world lane engine: thousands of simulations as one JAX program.

One *lane* is one full static-cluster experiment — trace, scheduler,
fleet size — and a batch of lanes runs as a single jit-compiled program
over stacked ``(lane, node)`` / ``(lane, pod)`` arrays.  The program is
the cycle hot path of the serial engine lowered to fixed shapes:

* an outer ``lax.while_loop`` advances the 10 s scheduling cycle for all
  lanes in lockstep, bailing out as soon as every lane is finished
  (completed, stuck, or quiescent) or the 48 h horizon is reached;
* a completion inner loop commits due batch completions **one pod per
  lane per step** in ``(done_time, bind_seq)`` order — the serial event
  order — so the per-node ``used_*`` running floats stay bit-identical
  (summation order matters; a segment-sum would not);
* a bind inner loop walks the pending snapshot in FIFO (row) order, one
  pod per lane per step: feasibility mask, scheduler score, first-extremum
  select (:func:`masked_argmin`), then the serial accounting ops
  ``used += req`` / ``free = alloc - used``.

**Names and counts.**  The jitted program is named after its scheduler
(``lane_program_best_fit``: the ``XLA Modules`` line of a profiler
trace), and its loop bodies run under the named scopes ``completions``,
``wave`` and ``cycle_end``.  Beside its outputs it returns the steps it
took (:data:`COUNTERS`): the outer cycles, the iterations of each inner
loop, and how many lanes had work in them.  The counts only describe the
run; nothing reads them back into a decision.  :func:`run_lane_batch`
times its dispatch, wait and copy to the host as spans of
:data:`PROFILER`, the lane path's process-wide span recorder.

**Relaxed-semantics envelope.**  Lanes model the void/void static-cluster
regime only: no autoscaler, no rescheduler, no chaos, homogeneous READY
fleet billed from t=0, speed factor 1.  Everything else — event ordering,
tie-breaks, stuck detection, blocked-pod scale-out counting — follows the
serial engine exactly; ``repro.manyworld.evaluator`` reconstructs full
``ExperimentResult`` rows host-side from the lane outputs.  See
ARCHITECTURE.md "Many-world lanes" for the contract and the enumerated
divergences.

**Float discipline.**  The serial engine's float64 values — arrival and
completion times, memory requests and the per-node ``used_mem`` running
sums — enter the program as their IEEE-754 bit patterns in int64, and
the program computes on the patterns: :func:`f64_add` is IEEE-754
binary64 addition (round to nearest, ties to even) in integer ops, and
comparisons are integer comparisons of :func:`_order_key`.  No float64
operation runs on the device.  XLA:TPU emulates float64 with pairs of
float32, which neither holds nor adds a float64 as IEEE-754 does, and the
lane rows then drift from the serial rows (ROADMAP item 1.3).  CPU
requests are whole milli-cores and stay integers.  Policies whose scores
need float multiply and divide (k8s-default, weighted) are outside the
lane envelope.  The program needs ``jax.enable_x64`` for its int64
columns only.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import numpy as np

from repro.obs.profiler import PhaseProfiler

CYCLE_PERIOD_S = 10.0
HORIZON_S = 48 * 3600.0          # SimConfig.max_sim_time_s default
MAX_CYCLES = int(HORIZON_S / CYCLE_PERIOD_S)   # cycle at t == horizon runs

SCHEDULERS = ("best-fit", "worst-fit", "first-fit")

#: Scalar step counts the program returns beside its lane outputs:
#: ``n_cycles`` outer cycles; ``wave_steps`` / ``completion_steps``
#: iterations of the bind and completion loops over the whole run;
#: ``busy_lane_steps`` lanes with a pending candidate or a due completion,
#: summed over those iterations; ``active_lane_cycles`` active lanes,
#: summed over the outer cycles.  The two lane sums are int64: lanes times
#: steps can pass 2**31 at policy-search sizes.
COUNTERS = ("n_cycles", "wave_steps", "completion_steps", "busy_lane_steps",
            "active_lane_cycles")

#: The lane path's spans (``repro.manyworld.evaluator`` opens the rest).
PROFILER = PhaseProfiler(max_spans=1 << 14)

# bind_seq fill for "no completion candidate" (any value > every real seq).
_SEQ_INF = np.int32(2**31 - 1)

# IEEE-754 binary64 fields of an int64 bit pattern.
_SIGN = np.int64(-2**63)
_MAG = np.int64(2**63 - 1)
_FRAC = np.int64(2**52 - 1)
_IMPL = np.int64(2**52)
_KEY_MAX = np.int64(2**63 - 1)          # masked_argmin fill: above every key


def f64_bits(x) -> np.ndarray:
    """float64 values -> their IEEE-754 bit patterns as int64."""
    return np.ascontiguousarray(x, np.float64).view(np.int64)


_INF_BITS = f64_bits(np.inf)[()]
_EPS_BITS = f64_bits(1e-9)[()]           # serial fits slack on memory
_HORIZON_BITS = f64_bits(HORIZON_S)[()]
# Cycle start times k * 10.0, k = 0 .. MAX_CYCLES, as the serial clock
# computes them.
_T_BITS = f64_bits(np.arange(MAX_CYCLES + 1) * CYCLE_PERIOD_S)


def next_pow2(n: int) -> int:
    """Smallest power of two >= max(n, 1) — the padding quantum that keeps
    the jit cache small (one compile per (scheduler, N, P) bucket)."""
    p = 1
    while p < max(n, 1):
        p *= 2
    return p


@dataclasses.dataclass
class LaneBatch:
    """Stacked fixed-shape inputs for one compiled many-world program.

    Pod axis is padded to ``p_pad`` (``valid`` masks real rows), node axis
    to ``n_pad`` (``n_nodes`` masks real nodes); every lane in a batch
    shares one scheduler.  Build via :func:`stack_lanes`.
    """

    scheduler: str
    arrival_t: np.ndarray     # (L, P) f64, +inf padded
    cpu_m: np.ndarray         # (L, P) i64
    mem_mb: np.ndarray        # (L, P) f64
    duration_s: np.ndarray    # (L, P) f64
    is_batch: np.ndarray      # (L, P) bool
    valid: np.ndarray         # (L, P) bool
    n_nodes: np.ndarray       # (L,)  i32
    alloc_cpu: np.ndarray     # (L,)  i64
    alloc_mem: np.ndarray     # (L,)  f64

    @property
    def n_lanes(self) -> int:
        return self.arrival_t.shape[0]

    @property
    def p_pad(self) -> int:
        return self.arrival_t.shape[1]

    @property
    def n_pad(self) -> int:
        return next_pow2(int(self.n_nodes.max()) if self.n_nodes.size else 1)


def stack_lanes(lanes, scheduler: str, p_pad: Optional[int] = None
                ) -> LaneBatch:
    """Stack per-lane dicts (``TraceStore.to_lane_arrays`` output plus
    cluster scalars ``n_nodes`` / ``alloc_cpu`` / ``alloc_mem``) into one
    padded :class:`LaneBatch`.  CPU requests and allocatable must be whole
    milli-cores, as ``Resources.cpu_m`` is."""
    if scheduler not in SCHEDULERS:
        raise ValueError(f"unsupported lane scheduler {scheduler!r}")
    n_max = max((int(d["arrival_t"].size) for d in lanes), default=0)
    P = p_pad if p_pad is not None else next_pow2(n_max)
    if n_max > P:
        raise ValueError(f"p_pad={P} < largest lane ({n_max} pods)")
    L = len(lanes)
    arr = np.full((L, P), np.inf)
    cpu = np.zeros((L, P), np.int64)
    mem = np.zeros((L, P))
    dur = np.zeros((L, P))
    isb = np.zeros((L, P), bool)
    val = np.zeros((L, P), bool)
    n_nodes = np.zeros(L, np.int32)
    a_cpu = np.zeros(L, np.int64)
    a_mem = np.zeros(L)
    for i, d in enumerate(lanes):
        n = int(d["arrival_t"].size)
        c = np.append(np.asarray(d["cpu_m"], np.float64), d["alloc_cpu"])
        if not np.array_equal(c, np.floor(c)):
            raise ValueError("lane CPU requests and allocatable must be "
                             "whole milli-cores")
        arr[i, :n] = d["arrival_t"]
        cpu[i, :n] = c[:n]
        mem[i, :n] = d["mem_mb"]
        dur[i, :n] = d["duration_s"]
        isb[i, :n] = d["is_batch"]
        val[i, :n] = True
        n_nodes[i] = d["n_nodes"]
        a_cpu[i] = d["alloc_cpu"]
        a_mem[i] = d["alloc_mem"]
    return LaneBatch(scheduler, arr, cpu, mem, dur, isb, val,
                     n_nodes, a_cpu, a_mem)


def f64_add(a, b):
    """IEEE-754 binary64 ``a + b`` on int64 bit patterns, in integer ops.

    Round to nearest, ties to even; exact for every pair of finite
    inputs, subnormals and signed zeros included.  Infinities and NaNs are
    outside its domain: the lane program never adds one, and none of its
    sums overflows.  Significands carry three guard bits (guard, round,
    sticky), which is enough for a correctly rounded sum.
    """
    import jax.numpy as jnp
    from jax import lax
    swap = (b & _MAG) > (a & _MAG)
    x = jnp.where(swap, b, a)                       # larger magnitude
    y = jnp.where(swap, a, b)
    sx, sy = x < 0, y < 0
    ex = (x >> 52) & 0x7FF
    ey = (y >> 52) & 0x7FF
    # A subnormal has no implicit bit and the scale of exponent 1.
    mx = jnp.where(ex > 0, (x & _FRAC) | _IMPL, x & _FRAC) << 3
    my = jnp.where(ey > 0, (y & _FRAC) | _IMPL, y & _FRAC) << 3
    ex = jnp.maximum(ex, 1)
    d = jnp.minimum(ex - jnp.maximum(ey, 1), 63)
    my_al = my >> d
    my_al = my_al | ((my_al << d) != my).astype(my.dtype)    # sticky
    m = jnp.where(sx == sy, mx + my_al, mx - my_al)
    # Carry out of the top: one step right, keeping the sticky bit.
    carry = m >= (1 << 56)
    m = jnp.where(carry, (m >> 1) | (m & 1), m)
    e = jnp.where(carry, ex + 1, ex)
    # Cancellation: left until the leading bit is bit 55, but no further
    # than the subnormal scale.
    sh = jnp.clip(lax.clz(m) - 8, 0, e - 1)
    m = m << sh
    e = e - sh
    g = m & 7
    m = m >> 3
    m = m + ((g > 4) | ((g == 4) & ((m & 1) == 1))).astype(m.dtype)
    ovf = m >= (1 << 53)
    m = jnp.where(ovf, m >> 1, m)
    e = jnp.where(ovf, e + 1, e)
    bits = (jnp.where(m >= _IMPL, e, 0) << 52) | (m & _FRAC)
    # An exact zero is +0, except (-0) + (-0).
    neg = jnp.where(m == 0, sx & sy, sx)
    return jnp.where(neg, bits | _SIGN, bits)


def _order_key(bits):
    """int64 key that orders float64 bit patterns as their values order
    (``+0`` and ``-0`` share the key 0).  Non-negative patterns are their
    own keys."""
    import jax.numpy as jnp
    return jnp.where(bits < 0, -(bits & _MAG), bits)


def masked_argmin(keys, mask):
    """First index of the masked minimum, per lane.

    ``keys`` is ``(L, N)`` int64, ``mask`` ``(L, N)`` bool; returns
    ``(L,)`` int32.  Infeasible nodes are filled with the largest key and
    ``jnp.argmin`` breaks ties to the first occurrence, like the serial
    NumPy ``argmin`` over its ``+inf``-filled buffer.  Rows whose mask is
    all-False return an arbitrary index: callers gate on
    ``mask.any(axis=1)``, as the serial path gates on ``buf[i] == fill``.
    """
    import jax.numpy as jnp
    buf = jnp.where(mask, keys, _KEY_MAX)
    return jnp.argmin(buf, axis=1).astype(jnp.int32)


def _wave_keys(sched: str, free_mem):
    """Per-node score keys for one pod per lane, **negated for max-mode**
    so one masked-argmin select serves every policy: the serial
    ``Scheduler.wave_scores`` of best-fit (min free memory), worst-fit
    (max free memory) and first-fit (first feasible rank)."""
    import jax.numpy as jnp
    if sched == "best-fit":
        return _order_key(free_mem)
    if sched == "worst-fit":
        return -_order_key(free_mem)
    return jnp.zeros_like(free_mem)


def _program_factory(sched: str, n_pad: int):
    """Build the jitted many-world program for one (scheduler, padded node
    count); XLA retraces per (L, P) bucket.  ``arr_t`` / ``mem`` / ``dur``
    / ``alloc_mem`` and the float outputs are float64 bit patterns
    (module docstring, "Float discipline"); all times are non-negative, so
    their patterns compare like their values."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def run(arr_t, cpu, mem, dur, isb, valid, n_nodes, alloc_cpu, alloc_mem):
        L, P = arr_t.shape
        t_of = jnp.asarray(_T_BITS)
        li = jnp.arange(L)
        node_active = (jnp.arange(n_pad, dtype=jnp.int32)[None, :]
                       < n_nodes[:, None])                    # (L, N)
        ac = alloc_cpu[:, None]
        am = alloc_mem[:, None]

        def completions(t, st, steps, busy):
            """Commit due batch completions one pod per lane per step, in
            (done_time, bind_seq) order — the serial POD_DONE event order
            (heap pops ascending time; push order == bind order within a
            timestamp).  ``steps`` and ``busy`` count the iterations and
            the lanes that committed in them."""
            def due_of(c):
                done_c, done_t, bound, active = c[3], c[4], c[5], c[9]
                return (valid & isb & bound & ~done_c
                        & (done_t <= t) & active[:, None])

            def cond(c):
                return due_of(c).any()

            def body(c):
                (used_cpu, used_mem, pcount, done_c, done_t, bound,
                 bind_node, bind_seq, bind_cycle, active, completed,
                 done_time, done_is_cycle, steps, busy) = c
                due = due_of(c)
                has = due.any(axis=1)
                # Two-stage extremum: earliest done_time, then lowest
                # bind_seq among its ties (seq is unique per lane).
                t1 = jnp.where(due, done_t, _INF_BITS)
                tmin = t1.min(axis=1, keepdims=True)
                s1 = jnp.where(due & (t1 == tmin), bind_seq, _SEQ_INF)
                p = jnp.argmin(s1, axis=1)
                node = jnp.where(has, bind_node[li, p], 0)
                # serial: node._used_* -= req, one pod at a time.
                old_c, old_m = used_cpu[li, node], used_mem[li, node]
                used_cpu = used_cpu.at[li, node].set(
                    jnp.where(has, old_c - cpu[li, p], old_c))
                used_mem = used_mem.at[li, node].set(
                    jnp.where(has, f64_add(old_m, mem[li, p] ^ _SIGN), old_m))
                pcount = pcount.at[li, node].add(-has.astype(jnp.int32))
                done_c = done_c.at[li, p].set(done_c[li, p] | has)
                # _done() check after this POD_DONE event: all arrived at
                # the *event's* time, every batch row committed, every
                # service bound.
                td = jnp.where(has, done_t[li, p], _INF_BITS)
                arrived_td = (~valid | (arr_t <= td[:, None])).all(axis=1)
                batch_done = (~valid | ~isb | done_c).all(axis=1)
                svc_bound = (~valid | isb | bound).all(axis=1)
                now_done = has & active & arrived_td & batch_done & svc_bound
                completed = completed | now_done
                done_time = jnp.where(now_done, td, done_time)
                active = active & ~now_done
                return (used_cpu, used_mem, pcount, done_c, done_t, bound,
                        bind_node, bind_seq, bind_cycle, active, completed,
                        done_time, done_is_cycle, steps + 1,
                        busy + has.sum(dtype=busy.dtype))

            with jax.named_scope("completions"):
                out = lax.while_loop(cond, body, st + (steps, busy))
            return out[:13], out[13], out[14]

        def wave(t, k, st, steps, busy):
            """One scheduling cycle's wave: walk the pending snapshot in
            row (FIFO) order, one pod per lane per step.  Blocked pods are
            counted (the serial void/void fallback bumps one scale-out
            request per blocked pod) and skipped — decision-identical to
            the serial blocked_keys latch, which only memoizes the same
            outcome (working frees never grow inside a cycle).  ``steps``
            and ``busy`` count the iterations and the lanes that attempted
            a pod in them."""
            (used_cpu, used_mem, pcount, done_c, done_t, bound,
             bind_node, bind_seq, bind_cycle, active, completed,
             done_time, done_is_cycle, seq_ctr, scale_outs) = st
            arrived = valid & (arr_t <= t)

            def cand_of(c):
                bound, attempted = c[2], c[8]
                return arrived & ~bound & ~attempted & active[:, None]

            def cond(c):
                return cand_of(c).any()

            def body(c):
                (used_cpu, used_mem, bound, bind_node, bind_seq,
                 bind_cycle, done_t, pcount, attempted, placed, blocked,
                 seq_ctr, steps, busy) = c
                cand = cand_of(c)
                has = cand.any(axis=1)
                p = jnp.argmax(cand, axis=1)       # first pending row
                pc = cpu[li, p][:, None]
                pm = mem[li, p][:, None]
                # serial WavePlacer: free = alloc - used (elementwise);
                # fits = (free_cpu >= cpu) & (free_mem + 1e-9 >= mem).
                free_cpu = ac - used_cpu
                free_mem = f64_add(am, used_mem ^ _SIGN)
                mem_fits = (_order_key(f64_add(free_mem, _EPS_BITS))
                            >= _order_key(pm))
                mask = (free_cpu >= pc) & mem_fits & node_active
                r = masked_argmin(_wave_keys(sched, free_mem), mask)
                feas = mask.any(axis=1)
                do = has & feas
                blk = has & ~feas
                r_g = jnp.where(do, r, 0).astype(jnp.int32)
                old_c, old_m = used_cpu[li, r_g], used_mem[li, r_g]
                used_cpu = used_cpu.at[li, r_g].set(
                    jnp.where(do, old_c + pc[:, 0], old_c))
                used_mem = used_mem.at[li, r_g].set(
                    jnp.where(do, f64_add(old_m, pm[:, 0]), old_m))
                pcount = pcount.at[li, r_g].add(do.astype(jnp.int32))
                bound = bound.at[li, p].set(bound[li, p] | do)
                bind_node = bind_node.at[li, p].set(
                    jnp.where(do, r_g, bind_node[li, p]))
                bind_seq = bind_seq.at[li, p].set(
                    jnp.where(do, seq_ctr, bind_seq[li, p]))
                bind_cycle = bind_cycle.at[li, p].set(
                    jnp.where(do, k, bind_cycle[li, p]))
                # Completion timestamp: now + duration (speed factor 1);
                # services never complete (+inf).
                td = jnp.where(do & isb[li, p], f64_add(t, dur[li, p]),
                               _INF_BITS)
                done_t = done_t.at[li, p].set(
                    jnp.where(do, td, done_t[li, p]))
                seq_ctr = seq_ctr + do.astype(jnp.int32)
                placed = placed + do.astype(jnp.int32)
                blocked = blocked + blk.astype(jnp.int32)
                attempted = attempted.at[li, p].set(attempted[li, p] | has)
                return (used_cpu, used_mem, bound, bind_node, bind_seq,
                        bind_cycle, done_t, pcount, attempted, placed,
                        blocked, seq_ctr, steps + 1,
                        busy + has.sum(dtype=busy.dtype))

            zeros_i = jnp.zeros(L, jnp.int32)
            with jax.named_scope("wave"):
                (used_cpu, used_mem, bound, bind_node, bind_seq, bind_cycle,
                 done_t, pcount, _att, placed, blocked, seq_ctr, steps, busy
                 ) = lax.while_loop(
                    cond, body,
                    (used_cpu, used_mem, bound, bind_node, bind_seq,
                     bind_cycle, done_t, pcount, jnp.zeros_like(bound),
                     zeros_i, zeros_i, seq_ctr, steps, busy))
            scale_outs = scale_outs + blocked

            # -- post-cycle bookkeeping (serial order: wave stats, the
            # _done() check after the CYCLE event, then stuck detection).
            with jax.named_scope("cycle_end"):
                all_arrived = (~valid | (arr_t <= t)).all(axis=1)
                pending_after = (arrived & ~bound).any(axis=1)
                running_batch = (valid & isb & bound & ~done_c).any(axis=1)
                batch_done = (~valid | ~isb | done_c).all(axis=1)
                svc_bound = (~valid | isb | bound).all(axis=1)
                has_pods = valid.any(axis=1)
                done_b = (active & has_pods & all_arrived & batch_done
                          & svc_bound)
                completed = completed | done_b
                done_time = jnp.where(done_b, t, done_time)
                done_is_cycle = done_is_cycle | done_b
                active = active & ~done_b
                # _permanently_stuck: static cluster, everything arrived,
                # nothing placed, something blocked, nothing running.
                stuck_now = (active & all_arrived & (placed == 0)
                             & (blocked > 0) & ~running_batch & pending_after)
                active = active & ~stuck_now
                # Quiescent: all arrived, nothing pending, nothing running,
                # not done (zero-pod lanes) — state can never change again;
                # the lane just samples to the horizon (host-side).
                quies = active & all_arrived & ~pending_after & ~running_batch
                active = active & ~quies
            return (used_cpu, used_mem, pcount, done_c, done_t, bound,
                    bind_node, bind_seq, bind_cycle, active, completed,
                    done_time, done_is_cycle, seq_ctr, scale_outs,
                    steps, busy)

        def cycle_body(st):
            k, state = st[0], st[1:16]
            wave_steps, completion_steps, busy, active_cycles = st[16:]
            active_cycles += state[9].sum(dtype=active_cycles.dtype)
            t = t_of[k]
            # POD_DONE events at times <= t all fire before CYCLE(t).
            mid, completion_steps, busy = completions(
                t, state[:13], completion_steps, busy)
            *state, wave_steps, busy = wave(t, k, mid + state[13:],
                                            wave_steps, busy)
            return (k + 1, *state, wave_steps, completion_steps, busy,
                    active_cycles)

        def cycle_cond(st):
            k, active = st[0], st[10]
            return active.any() & (k <= MAX_CYCLES)

        init = (
            jnp.zeros((), jnp.int32),                      # k
            jnp.zeros((L, n_pad), cpu.dtype),              # used_cpu
            jnp.zeros((L, n_pad), mem.dtype),              # used_mem (+0.0)
            jnp.zeros((L, n_pad), jnp.int32),              # pcount
            jnp.zeros((L, P), bool),                       # done_c
            jnp.full((L, P), _INF_BITS),                   # done_t
            jnp.zeros((L, P), bool),                       # bound
            jnp.full((L, P), -1, jnp.int32),               # bind_node
            jnp.full((L, P), -1, jnp.int32),               # bind_seq
            jnp.full((L, P), -1, jnp.int32),               # bind_cycle
            valid.any(axis=1),                             # active
            jnp.zeros(L, bool),                            # completed
            jnp.full(L, _HORIZON_BITS),                    # done_time
            jnp.zeros(L, bool),                            # done_is_cycle
            jnp.zeros(L, jnp.int32),                       # seq_ctr
            jnp.zeros(L, jnp.int32),                       # scale_outs
            jnp.zeros((), jnp.int32),                      # wave_steps
            jnp.zeros((), jnp.int32),                      # completion_steps
            jnp.zeros((), jnp.int64),                      # busy_lane_steps
            jnp.zeros((), jnp.int64),                      # active_lane_cycles
        )
        (k, used_cpu, used_mem, pcount, done_c, done_t, bound,
         bind_node, bind_seq, bind_cycle, active, completed, done_time,
         done_is_cycle, seq_ctr, scale_outs, wave_steps, completion_steps,
         busy, active_cycles) = lax.while_loop(cycle_cond, cycle_body, init)
        return {
            "bound": bound, "done_committed": done_c,
            "bind_node": bind_node, "bind_seq": bind_seq,
            "bind_cycle": bind_cycle, "done_t": done_t,
            "completed": completed, "done_time": done_time,
            "done_is_cycle": done_is_cycle, "scale_outs": scale_outs,
            "n_cycles": k, "wave_steps": wave_steps,
            "completion_steps": completion_steps, "busy_lane_steps": busy,
            "active_lane_cycles": active_cycles,
            "used_cpu": used_cpu, "used_mem": used_mem, "pcount": pcount,
        }

    run.__name__ = run.__qualname__ = "lane_program_" + sched.replace("-", "_")
    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _jit_cache(sched: str, n_pad: int):
    return _program_factory(sched, n_pad)


def program_args(batch: LaneBatch) -> tuple:
    """The program's host arguments for ``batch``, floats as bit patterns."""
    return (f64_bits(batch.arrival_t), batch.cpu_m, f64_bits(batch.mem_mb),
            f64_bits(batch.duration_s), batch.is_batch, batch.valid,
            batch.n_nodes, batch.alloc_cpu, f64_bits(batch.alloc_mem))


def run_lane_batch(batch: LaneBatch) -> dict:
    """Execute one :class:`LaneBatch`; returns numpy lane outputs.

    Per lane: ``completed`` / ``done_time`` / ``done_is_cycle`` /
    ``scale_outs``; per pod: ``bound``, ``bind_node`` (node *rank* —
    serial parity maps ``node_slot`` through ``ClusterArrays.id_rank``),
    ``bind_seq`` (per-lane bind order), ``bind_cycle`` (bind time is
    exactly ``bind_cycle * 10.0``), ``done_t`` and ``done_committed``.
    Per batch: the scalar step counts of :data:`COUNTERS`.

    Spans of :data:`PROFILER`: ``lanes.dispatch`` (arguments and the jit
    call), ``lanes.wait`` (until the device is done), ``lanes.fetch`` (the
    copies to the host).
    """
    import jax
    span = PROFILER.span
    with jax.enable_x64(True):
        with span("lanes.dispatch"):
            run = _jit_cache(batch.scheduler, batch.n_pad)
            dev = run(*program_args(batch))
        with span("lanes.wait"):
            jax.block_until_ready(dev)
        with span("lanes.fetch"):
            out = {key: np.asarray(v) for key, v in dev.items()}
            for key in ("done_t", "done_time", "used_mem"):
                out[key] = out[key].view(np.float64)
    return out
