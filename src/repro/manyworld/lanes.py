"""Many-world lane engine: thousands of simulations as one JAX program.

One *lane* is one full experiment — trace, scheduler, fleet — and a
batch of lanes runs as a single jit-compiled program
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
(``lane_program_best_fit``, ``..._autoscaled`` for an autoscaled fleet,
``..._autoscaled_resched`` with Alg. 3: the ``XLA Modules`` line of a
profiler trace), and its loop bodies run under the named scopes
``completions``, ``wave``, ``reschedule``, ``scale_in`` and
``cycle_end``.  Beside its outputs it returns the steps it took
(:data:`COUNTERS`, :data:`FLEET_COUNTERS` when autoscaled and
:data:`RESCHED_COUNTERS` with Alg. 3): the outer cycles, the iterations
of each inner loop, how many lanes had work in them, the nodes launched,
removed and live, and Alg. 3's outcomes.  The counts only describe the
run; nothing reads them back into a decision.  :func:`run_lane_batch`
times its dispatch, wait and copy to the host as spans of
:data:`PROFILER`, the lane path's process-wide span recorder.

**Relaxed-semantics envelope.**  Lanes model no chaos, a homogeneous
fleet and speed factor 1, on a static fleet (void autoscaler: READY
nodes billed from t=0) or an autoscaled one; the one rescheduler they
model is Alg. 3 (non-binding) on an autoscaled fleet.  Everything else
— event ordering, tie-breaks, stuck detection, blocked-pod scale-out
counting — follows the serial engine exactly;
``repro.manyworld.evaluator`` reconstructs full ``ExperimentResult``
rows host-side from the lane outputs.  See ARCHITECTURE.md "Many-world
lanes" for the contract and the enumerated divergences.

**Autoscaled lanes.**  The binding autoscaler (paper Alg. 7) and Alg. 6
scale-in run inside the cycle.  Each lane holds ``n_pad`` node records,
its static nodes and every node it launches, never reused, laid out in
node_id order (:func:`node_layout`) so the first index of a masked
extremum is the serial lowest-id tie-break; each record has a state
(:data:`NODE_STATES`), its launch and removal cycles, and the planned
free room of its booting tracker.  A cycle first lets the nodes whose
provisioning delay has passed join (their trackers' pods lose their
association), commits completions, then places: READY nodes first,
TAINTED as the last resort, and a blocked pod asks the autoscaler, which
associates it with a booting node or launches one.  After a cycle with
no blocked pod, Alg. 6 removes empty autoscaled nodes and visits the
others in launch order, evicting moveable pods whose shadow best-fit
succeeds; evicted pods re-pend at the cycle's instant, so the wave picks
by (pending since, row).  Each eviction records the incarnation it
closes, for the host's rebuild.  A lane that runs past its node or
eviction records stops (``overflow``) and is rerun serially.

**Alg. 3 lanes.**  With the non-binding rescheduler a blocked pod first
meets Alg. 3 inside the wave: younger than its lane's ``max_pod_age_s``
it waits (no scale-out); else the READY nodes with room for its CPU that
hold moveable pods are tried as victims, most free memory first, and a
victim's moveable pods, largest first, are placed by the shadow
best-fit that Alg. 6 uses, until they free the memory the pod lacks.
A plan evicts its movers (they re-pend now and sit out the rest of this
wave, as the serial engine walks a snapshot) and the wave goes on; only
a failed plan asks the autoscaler.  Binds and evictions then share one
sequence counter (``ev_seq``), which orders them within a cycle for the
rebuild.

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
#: The autoscaled program's further counts: ``scale_out_nodes`` nodes
#: launched and ``scale_in_nodes`` nodes removed by Alg. 6 (steps 1 and
#: 2), over all lanes; ``active_node_cycles`` live nodes (booting, ready
#: or tainted) of the active lanes, summed over the outer cycles;
#: ``scale_in_steps`` iterations of Alg. 6's candidate, shadow-placement
#: and eviction loops.
FLEET_COUNTERS = ("scale_out_nodes", "scale_in_nodes", "active_node_cycles",
                  "scale_in_steps")
#: The Alg. 3 program's further counts, over all lanes: calls that waited
#: out the age gate (``resched_waits``), calls past it that searched for a
#: plan (``resched_plans``), plans found and carried out
#: (``resched_done``), pods they evicted (``resched_evictions``), and
#: ``resched_steps`` iterations of the plan and eviction loops.
RESCHED_COUNTERS = ("resched_waits", "resched_plans", "resched_done",
                    "resched_evictions", "resched_steps")

#: Node record states of an autoscaled lane: no node yet, booting
#: (PROVISIONING, with the binding autoscaler's tracker), READY, TAINTED
#: (Alg. 6 step 3) and removed (Alg. 6 steps 1 and 2).
NODE_STATES = tuple(range(5))
NODE_NONE, NODE_BOOTING, NODE_READY, NODE_TAINTED, NODE_GONE = NODE_STATES

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
_KEY_MIN = np.int64(-2**63)             # masked max fill: below every key


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
    to ``n_pad``; every lane in a batch shares one scheduler.  A static
    batch (``node_pad`` None) holds ``n_nodes`` READY nodes per lane, at
    node indices ``0 .. n_nodes - 1``.  An autoscaled batch (the binding
    autoscaler, :data:`NODE_STATES`) gives each lane ``node_pad`` node
    records: its ``n_nodes`` static nodes and every node it launches,
    in node_id order (:func:`node_layout`); ``boot_cycles`` is the
    provisioning delay in cycles and ``moveable`` marks the pods Alg. 6
    may move.  ``max_age`` (float64 per lane, autoscaled batches only)
    makes the batch run Alg. 3 with that age gate.  Build via
    :func:`stack_lanes`.
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
    moveable: np.ndarray      # (L, P) bool
    boot_cycles: np.ndarray   # (L,)  i32
    node_pad: Optional[int] = None
    max_age: Optional[np.ndarray] = None    # (L,) f64

    @property
    def n_lanes(self) -> int:
        return self.arrival_t.shape[0]

    @property
    def p_pad(self) -> int:
        return self.arrival_t.shape[1]

    @property
    def autoscale(self) -> bool:
        return self.node_pad is not None

    @property
    def resched(self) -> bool:
        return self.max_age is not None

    @property
    def n_pad(self) -> int:
        if self.node_pad is not None:
            return self.node_pad
        return next_pow2(int(self.n_nodes.max()) if self.n_nodes.size else 1)


def stack_lanes(lanes, scheduler: str, p_pad: Optional[int] = None,
                node_pad: Optional[int] = None,
                resched: bool = False) -> LaneBatch:
    """Stack per-lane dicts (``TraceStore.to_lane_arrays`` output plus
    cluster scalars ``n_nodes`` / ``alloc_cpu`` / ``alloc_mem``) into one
    padded :class:`LaneBatch`.  CPU requests and allocatable must be whole
    milli-cores, as ``Resources.cpu_m`` is.  ``node_pad`` makes the batch
    autoscaled: each lane dict then also holds ``boot_cycles``, and
    ``node_pad`` bounds the node records of every lane.  ``resched`` (an
    autoscaled batch only) adds Alg. 3, with each lane's gate from its
    ``max_pod_age_s``."""
    if scheduler not in SCHEDULERS:
        raise ValueError(f"unsupported lane scheduler {scheduler!r}")
    if resched and node_pad is None:
        raise ValueError("Alg. 3 lanes need an autoscaled batch")
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
    mov = np.zeros((L, P), bool)
    n_nodes = np.zeros(L, np.int32)
    a_cpu = np.zeros(L, np.int64)
    a_mem = np.zeros(L)
    boot = np.zeros(L, np.int32)
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
        if node_pad is not None:
            mov[i, :n] = d["moveable"]
            boot[i] = d["boot_cycles"]
    if node_pad is not None and L and int(n_nodes.max()) > node_pad:
        raise ValueError(f"node_pad={node_pad} < largest static fleet "
                         f"({int(n_nodes.max())} nodes)")
    ages = (np.array([float(d["max_pod_age_s"]) for d in lanes])
            if resched else None)
    return LaneBatch(scheduler, arr, cpu, mem, dur, isb, val,
                     n_nodes, a_cpu, a_mem, mov, boot, node_pad, ages)


def node_layout(n_pad: int):
    """``(seq_of_index, index_of_seq)`` of an autoscaled batch's node axis.

    A lane's nodes are ``node-<seq>``, numbered in launch order from 0 (its
    static nodes first), and node ids order *lexicographically*: ties in
    every placement go to the lowest id, and the binding autoscaler visits
    its booting nodes in id order.  So node ``seq`` sits at index
    ``index_of_seq[seq]``, the rank of its id among ``node-0 ..
    node-<n_pad - 1>``, and the first index of a masked extremum is the
    serial tie-break; ``seq_of_index`` is the inverse, the launch
    (insertion) order that Alg. 6 walks."""
    seq_of_index = np.array(sorted(range(n_pad), key=lambda s: f"node-{s}"),
                            np.int32)
    index_of_seq = np.empty(n_pad, np.int32)
    index_of_seq[seq_of_index] = np.arange(n_pad, dtype=np.int32)
    return seq_of_index, index_of_seq


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


# Per-lane row access inside the step loops.  A TPU scatter or gather
# with one index per lane runs serially over the lanes (about 0.1 ms a
# call at 4,096 lanes on a TPU v5e), while a select over a whole (lane,
# row) array of a few dozen columns is one vector pass; so every read and
# write of one element per lane is a one-hot select over the row axis.
# A select moves bits, so the values are those of the indexed forms.

def _row_hit(width: int, i):
    """``(L, width)`` bool, True at column ``i[l]`` of lane ``l``."""
    import jax.numpy as jnp
    return jnp.arange(width, dtype=jnp.int32)[None, :] == i[:, None]


def _row_put(x, i, v, m):
    """``x`` with ``x[l, i[l]] = v[l]`` (or the scalar ``v``) in the lanes
    where ``m[l]``: ``x.at[li, i].set(jnp.where(m, v, x[li, i]))``."""
    import jax.numpy as jnp
    v = jnp.asarray(v, x.dtype)
    return jnp.where(_row_hit(x.shape[1], i) & m[:, None],
                     v[:, None] if v.ndim else v, x)


def _row_add(x, i, d, m):
    """``x`` with the scalar ``d`` added at ``x[l, i[l]]`` in the lanes
    where ``m[l]``: ``x.at[li, i].add(jnp.where(m, d, 0))``."""
    import jax.numpy as jnp
    return x + jnp.where(_row_hit(x.shape[1], i) & m[:, None],
                         jnp.asarray(d, x.dtype), 0)


def _row_pick(x, i):
    """``x[l, i[l]]`` per lane (``x`` may be one row, ``(1, N)``): the one
    selected element survives a max (an ``any`` for bool) whose fill is
    the dtype's least value, so the result is exact."""
    import jax.numpy as jnp
    hit = _row_hit(x.shape[1], i)
    if x.dtype == jnp.bool_:
        return (hit & x).any(axis=1)
    return jnp.where(hit, x, jnp.iinfo(x.dtype).min).max(axis=1)


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


def _while(cond, body, state: dict, keys):
    """``lax.while_loop`` over the entries ``keys`` of ``state``, carried
    as a tuple in that order (the carry's order sets the compiled loop's
    buffers); ``cond`` and ``body`` see, and ``body`` returns, dicts."""
    from jax import lax

    def as_dict(c):
        return dict(zip(keys, c))

    def step(c):
        new = body(as_dict(c))
        return tuple(new[k] for k in keys)

    out = lax.while_loop(lambda c: cond(as_dict(c)), step,
                         tuple(state[k] for k in keys))
    return {**state, **as_dict(out)}


def _program_factory(sched: str, n_pad: int, autoscale: bool = False,
                     resched: bool = False):
    """Build the jitted many-world program for one (scheduler, padded node
    count, fleet kind); XLA retraces per (L, P) bucket.  ``arr_t`` /
    ``mem`` / ``dur`` / ``alloc_mem`` and the float outputs are float64
    bit patterns (module docstring, "Float discipline"); all times are
    non-negative, so their patterns compare like their values.

    ``autoscale`` adds the binding autoscaler and Alg. 6 (module
    docstring, "Autoscaled lanes") and its outputs; without it the program
    is the static-fleet one, which traces none of that code.  ``resched``
    (with ``autoscale``) adds Alg. 3 in the wave (module docstring, "Alg. 3
    lanes"), its per-lane age gate ``max_age`` as a further argument, the
    eviction sequence ``ev_seq`` and :data:`RESCHED_COUNTERS`."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    seq_of_index, index_of_seq = node_layout(n_pad)

    def run(arr_t, cpu, mem, dur, isb, valid, n_nodes, alloc_cpu, alloc_mem,
            *fleet):
        L, P = arr_t.shape
        t_of = jnp.asarray(_T_BITS)
        ac = alloc_cpu[:, None]
        am = alloc_mem[:, None]
        if autoscale:
            moveable, boot = fleet[:2]
            max_age = fleet[2] if resched else None
            node_seq = jnp.asarray(seq_of_index)[None, :]         # (1, N)
            index_of = jnp.asarray(index_of_seq)
            autoscaled = node_seq >= n_nodes[:, None]             # (L, N)
            X = P                      # eviction records per lane
        else:
            node_active = (jnp.arange(n_pad, dtype=jnp.int32)[None, :]
                           < n_nodes[:, None])                    # (L, N)

        def completions(t, S):
            """Commit due batch completions one pod per lane per step, in
            (done_time, bind_seq) order — the serial POD_DONE event order
            (heap pops ascending time; push order == bind order within a
            timestamp).  ``completion_steps`` and ``busy`` count the
            iterations and the lanes that committed in them."""
            keys = ["used_cpu", "used_mem", "pcount", "done_c", "done_t",
                    "bound", "bind_node", "bind_seq", "bind_cycle", "active",
                    "completed", "done_time", "done_is_cycle",
                    "completion_steps", "busy"]
            if autoscale:
                keys.append("nbatch")

            def due_of(c):
                return (valid & isb & c["bound"] & ~c["done_c"]
                        & (c["done_t"] <= t) & c["active"][:, None])

            def cond(c):
                return due_of(c).any()

            def body(c):
                c = dict(c)
                done_t, bound = c["done_t"], c["bound"]
                bind_node, bind_seq = c["bind_node"], c["bind_seq"]
                due = due_of(c)
                has = due.any(axis=1)
                # Two-stage extremum: earliest done_time, then lowest
                # bind_seq among its ties (seq is unique per lane).
                t1 = jnp.where(due, done_t, _INF_BITS)
                tmin = t1.min(axis=1, keepdims=True)
                s1 = jnp.where(due & (t1 == tmin), bind_seq, _SEQ_INF)
                p = jnp.argmin(s1, axis=1)
                node = jnp.where(has, _row_pick(bind_node, p), 0)
                # serial: node._used_* -= req, one pod at a time.
                old_c = _row_pick(c["used_cpu"], node)
                old_m = _row_pick(c["used_mem"], node)
                c["used_cpu"] = _row_put(c["used_cpu"], node,
                                         old_c - _row_pick(cpu, p), has)
                c["used_mem"] = _row_put(
                    c["used_mem"], node,
                    f64_add(old_m, _row_pick(mem, p) ^ _SIGN), has)
                c["pcount"] = _row_add(c["pcount"], node, -1, has)
                if autoscale:
                    c["nbatch"] = _row_add(c["nbatch"], node, -1, has)
                done_c = _row_put(c["done_c"], p, True, has)
                c["done_c"] = done_c
                # _done() check after this POD_DONE event: all arrived at
                # the *event's* time, every batch row committed, every
                # service bound.  done_t[p] is tmin, bit for bit.
                td = jnp.where(has, tmin[:, 0], _INF_BITS)
                arrived_td = (~valid | (arr_t <= td[:, None])).all(axis=1)
                batch_done = (~valid | ~isb | done_c).all(axis=1)
                svc_bound = (~valid | isb | bound).all(axis=1)
                now_done = (has & c["active"] & arrived_td & batch_done
                            & svc_bound)
                c["completed"] = c["completed"] | now_done
                c["done_time"] = jnp.where(now_done, td, c["done_time"])
                c["active"] = c["active"] & ~now_done
                c["completion_steps"] = c["completion_steps"] + 1
                c["busy"] = c["busy"] + has.sum(dtype=c["busy"].dtype)
                return c

            with jax.named_scope("completions"):
                return _while(cond, body, S, keys)

        def scale_out(k, p, pc, pm, blk, c):
            """The binding autoscaler (Alg. 7) for each lane's blocked pod:
            a pod already associated with a booting node asks for nothing;
            else the first booting node in id order whose planned free
            room holds it absorbs it; else a new node is launched for it,
            billed from now and READY ``boot`` cycles later.  Planned free
            room is ``allocatable - req - req ...``, one subtraction per
            absorbed pod, as the serial tracker sums it."""
            need = blk & (_row_pick(c["assoc"], p) < 0)
            pf_cpu, pf_mem = c["pf_cpu"], c["pf_mem"]
            room = ((c["nstate"] == NODE_BOOTING) & (pf_cpu >= pc)
                    & (_order_key(f64_add(pf_mem, _EPS_BITS))
                       >= _order_key(pm)))
            absorb = need & room.any(axis=1)
            launch = need & ~absorb
            seq = c["n_launched"]
            fits_pad = seq < n_pad
            go = launch & fits_pad
            c["overflow"] = c["overflow"] | (launch & ~fits_pad)
            tgt = jnp.where(absorb, jnp.argmax(room, axis=1),
                            _row_pick(index_of[None, :],
                                      jnp.minimum(seq, n_pad - 1))
                            ).astype(jnp.int32)
            upd = absorb | go
            base_c = jnp.where(absorb, _row_pick(pf_cpu, tgt), alloc_cpu)
            base_m = jnp.where(absorb, _row_pick(pf_mem, tgt), alloc_mem)
            c["pf_cpu"] = _row_put(pf_cpu, tgt, base_c - pc[:, 0], upd)
            c["pf_mem"] = _row_put(pf_mem, tgt,
                                   f64_add(base_m, pm[:, 0] ^ _SIGN), upd)
            c["nstate"] = _row_put(c["nstate"], tgt, NODE_BOOTING, go)
            c["launch_k"] = _row_put(c["launch_k"], tgt, k, go)
            c["assoc"] = _row_put(c["assoc"], p, tgt, upd)
            c["n_launched"] = seq + go.astype(jnp.int32)
            c["scale_out_nodes"] = (c["scale_out_nodes"]
                                    + go.sum(dtype=jnp.int64))
            return c

        def wave(t, k, S):
            """One scheduling cycle's wave: walk the pending snapshot in
            FIFO order, one pod per lane per step.  Blocked pods are
            counted (one scale-out request per blocked pod, as the serial
            engine counts them) and skipped — decision-identical to the
            serial blocked_keys latch, which only memoizes the same outcome
            between changes of the cluster (working frees grow inside a
            cycle only by an Alg. 3 eviction, after which the serial engine
            places on fresh arrays).  With Alg. 3 a blocked pod first meets
            :func:`reschedule`, and only a failed plan asks for a node.
            ``wave_steps`` and ``busy`` count the iterations and the lanes
            that attempted a pod in them."""
            arrived = valid & (arr_t <= t)
            active = S["active"]
            keys = ["used_cpu", "used_mem", "bound", "bind_node", "bind_seq",
                    "bind_cycle", "done_t", "pcount", "attempted", "placed",
                    "blocked", "seq_ctr", "wave_steps", "busy"]
            if autoscale:
                keys += ["nmove", "nbatch", "assoc", "nstate", "launch_k",
                         "pf_cpu", "pf_mem", "n_launched", "overflow",
                         "scale_out_nodes"]
                pend = S["pend"]
            if resched:
                keys += ["pend", "ev_pod", "ev_node", "ev_bind_cycle",
                         "ev_bind_seq", "ev_pend", "ev_cycle", "ev_seq",
                         "ev_n", "failed", "moved", *RESCHED_COUNTERS]

            def cand_of(c):
                return (arrived & ~c["bound"] & ~c["attempted"]
                        & active[:, None])

            def cond(c):
                return cand_of(c).any()

            def body(c):
                c = dict(c)
                cand = cand_of(c)
                has = cand.any(axis=1)
                if autoscale:
                    # FIFO by (pending_since, row): evicted pods re-pend
                    # at their eviction instant.
                    p = jnp.argmin(jnp.where(cand, pend, _KEY_MAX), axis=1)
                else:
                    p = jnp.argmax(cand, axis=1)       # first pending row
                pc = _row_pick(cpu, p)[:, None]
                pm = _row_pick(mem, p)[:, None]
                used_cpu, used_mem = c["used_cpu"], c["used_mem"]
                # serial WavePlacer: free = alloc - used (elementwise);
                # fits = (free_cpu >= cpu) & (free_mem + 1e-9 >= mem).
                free_cpu = ac - used_cpu
                free_mem = f64_add(am, used_mem ^ _SIGN)
                mem_fits = (_order_key(f64_add(free_mem, _EPS_BITS))
                            >= _order_key(pm))
                fits = (free_cpu >= pc) & mem_fits
                keys_n = _wave_keys(sched, free_mem)
                if autoscale:
                    # READY nodes first; TAINTED ones only when no READY
                    # node fits (the serial last-resort fallback).
                    m_r = fits & (c["nstate"] == NODE_READY)
                    m_t = fits & (c["nstate"] == NODE_TAINTED)
                    f_r = m_r.any(axis=1)
                    r = jnp.where(f_r, masked_argmin(keys_n, m_r),
                                  masked_argmin(keys_n, m_t))
                    feas = f_r | m_t.any(axis=1)
                else:
                    mask = fits & node_active
                    r = masked_argmin(keys_n, mask)
                    feas = mask.any(axis=1)
                do = has & feas
                blk = has & ~feas
                r_g = jnp.where(do, r, 0).astype(jnp.int32)
                old_c = _row_pick(used_cpu, r_g)
                old_m = _row_pick(used_mem, r_g)
                c["used_cpu"] = _row_put(used_cpu, r_g, old_c + pc[:, 0], do)
                c["used_mem"] = _row_put(used_mem, r_g,
                                         f64_add(old_m, pm[:, 0]), do)
                c["pcount"] = _row_add(c["pcount"], r_g, 1, do)
                is_b = _row_pick(isb, p)
                if autoscale:
                    c["nmove"] = _row_add(c["nmove"], r_g, 1,
                                          do & _row_pick(moveable, p))
                    c["nbatch"] = _row_add(c["nbatch"], r_g, 1, do & is_b)
                c["bound"] = _row_put(c["bound"], p, True, do)
                c["bind_node"] = _row_put(c["bind_node"], p, r_g, do)
                c["bind_seq"] = _row_put(c["bind_seq"], p, c["seq_ctr"], do)
                c["bind_cycle"] = _row_put(c["bind_cycle"], p, k, do)
                # Completion timestamp: now + duration (speed factor 1);
                # services never complete (+inf).
                td = jnp.where(do & is_b, f64_add(t, _row_pick(dur, p)),
                               _INF_BITS)
                c["done_t"] = _row_put(c["done_t"], p, td, do)
                if autoscale:
                    failed = blk
                    if resched:
                        with jax.named_scope("reschedule"):
                            c, failed, moved = reschedule(t, k, p, pc, pm,
                                                          blk, c)
                        c["failed"] = c["failed"] + failed.astype(jnp.int32)
                        c["moved"] = c["moved"] + moved.astype(jnp.int32)
                    c = scale_out(k, p, pc, pm, failed, c)
                c["seq_ctr"] = c["seq_ctr"] + do.astype(jnp.int32)
                c["placed"] = c["placed"] + do.astype(jnp.int32)
                c["blocked"] = c["blocked"] + blk.astype(jnp.int32)
                c["attempted"] = _row_put(c["attempted"], p, True, has)
                c["wave_steps"] = c["wave_steps"] + 1
                c["busy"] = c["busy"] + has.sum(dtype=c["busy"].dtype)
                return c

            zeros_i = jnp.zeros(L, jnp.int32)
            tally = {"placed": zeros_i, "blocked": zeros_i}
            if resched:
                tally.update(failed=zeros_i, moved=zeros_i)
            with jax.named_scope("wave"):
                S = _while(cond, body, {**S, "attempted": jnp.zeros_like(
                    S["bound"]), **tally}, keys)
            placed, blocked = S.pop("placed"), S.pop("blocked")
            S.pop("attempted")
            # Scale-out requests: the blocked pods, or with Alg. 3 those
            # whose plan failed.
            failed = S.pop("failed") if resched else blocked
            S["scale_outs"] = S["scale_outs"] + failed
            if autoscale:
                with jax.named_scope("scale_in"):
                    S = scale_in(t, k, S, active & (blocked == 0))

            # -- post-cycle bookkeeping (serial order: wave stats, scale-in,
            # the _done() check after the CYCLE event, then stuck
            # detection).
            with jax.named_scope("cycle_end"):
                bound, done_c = S["bound"], S["done_c"]
                all_arrived = (~valid | (arr_t <= t)).all(axis=1)
                pending_after = (arrived & ~bound).any(axis=1)
                running_batch = (valid & isb & bound & ~done_c).any(axis=1)
                batch_done = (~valid | ~isb | done_c).all(axis=1)
                svc_bound = (~valid | isb | bound).all(axis=1)
                has_pods = valid.any(axis=1)
                done_b = (active & has_pods & all_arrived & batch_done
                          & svc_bound)
                S["completed"] = S["completed"] | done_b
                S["done_time"] = jnp.where(done_b, t, S["done_time"])
                S["done_is_cycle"] = S["done_is_cycle"] | done_b
                active = active & ~done_b
                # _permanently_stuck: everything arrived, nothing placed or
                # rescheduled, a scale-out asked for, nothing running,
                # nothing booting.
                stuck_now = (active & all_arrived & (placed == 0)
                             & (failed > 0) & ~running_batch
                             & pending_after)
                if resched:
                    stuck_now = stuck_now & (S.pop("moved") == 0)
                if autoscale:
                    stuck_now = stuck_now & ~(
                        S["nstate"] == NODE_BOOTING).any(axis=1)
                active = active & ~stuck_now
                # Quiescent: all arrived, nothing pending, nothing running,
                # not done (zero-pod lanes) — state can never change again;
                # the lane just samples to the horizon (host-side).
                quies = (active & all_arrived & ~pending_after
                         & ~running_batch)
                active = active & ~quies
                if autoscale:
                    # A lane past its node or eviction records stops here;
                    # the host runs it serially.
                    active = active & ~S["overflow"]
                S["active"] = active
            return S

        def largest(rem, mem_key):
            """Each lane's pod of ``rem`` with the most memory, ties to the
            later row: the serial sort of movers by (memory, uid),
            descending."""
            mk = jnp.where(rem, mem_key, -1)
            top = rem & (mk == mk.max(axis=1, keepdims=True))
            return P - 1 - jnp.argmax(top[:, ::-1], axis=1)

        def shadow_fit(others, sh_cpu, sh_mem, q, act):
            """Shadow best-fit of each lane's pod ``q`` (in the lanes
            ``act``) onto the free room ``sh_cpu`` / ``sh_mem`` of the
            nodes ``others``: the fitting node with the least free memory,
            the first on ties, loses the pod's request.  Returns where the
            pod found room, its memory and the shadow after it.  Alg. 3 and
            Alg. 6 place their movers by it."""
            qc, qm = _row_pick(cpu, q), _row_pick(mem, q)
            f = (others & (sh_cpu >= qc[:, None])
                 & (_order_key(f64_add(sh_mem, _EPS_BITS))
                    >= _order_key(qm)[:, None]))
            b = masked_argmin(_order_key(sh_mem), f)
            put = act & f.any(axis=1)
            sc, sm = _row_pick(sh_cpu, b), _row_pick(sh_mem, b)
            sh_cpu = _row_put(sh_cpu, b, sc - qc, put)
            sh_mem = _row_put(sh_mem, b, f64_add(sm, qm ^ _SIGN), put)
            return put, qm, sh_cpu, sh_mem

        def evict(t, k, c, node, out, pick, bind_seq, bind_cycle, steps):
            """Evict ``out`` (pods on ``node``), one a lane a step in the
            order ``pick(out)`` gives: the node's usage drops one pod at a
            time, and each pod re-pends now, its closed incarnation
            recorded; with Alg. 3 each eviction also takes the next number
            of the bind sequence (``ev_seq``).  ``steps`` names the counter
            of the iterations."""
            def cond(s):
                return s[0].any()

            def body(s):
                out, c = s
                c = dict(c)
                act = out.any(axis=1)
                q = pick(out)
                old_c = _row_pick(c["used_cpu"], node)
                old_m = _row_pick(c["used_mem"], node)
                c["used_cpu"] = _row_put(c["used_cpu"], node,
                                         old_c - _row_pick(cpu, q), act)
                c["used_mem"] = _row_put(
                    c["used_mem"], node,
                    f64_add(old_m, _row_pick(mem, q) ^ _SIGN), act)
                dec = act.astype(jnp.int32)
                c["pcount"] = _row_add(c["pcount"], node, -1, act)
                c["nmove"] = _row_add(c["nmove"], node, -1, act)
                slot = c["ev_n"]
                rec = act & (slot < X)
                sl = jnp.minimum(slot, X - 1)
                recs = (("ev_pod", q), ("ev_node", node),
                        ("ev_bind_cycle", _row_pick(bind_cycle, q)),
                        ("ev_bind_seq", _row_pick(bind_seq, q)),
                        ("ev_pend", _row_pick(c["pend"], q)),
                        ("ev_cycle", k))
                if resched:
                    recs += (("ev_seq", c["seq_ctr"]),)
                for key, val in recs:
                    c[key] = _row_put(c[key], sl, val, rec)
                c["overflow"] = c["overflow"] | (act & (slot >= X))
                c["ev_n"] = slot + dec
                if resched:
                    c["seq_ctr"] = c["seq_ctr"] + dec
                c["bound"] = _row_put(c["bound"], q, False, act)
                c["pend"] = _row_put(c["pend"], q, t, act)
                c[steps] = c[steps] + 1
                return _row_put(out, q, False, act), c

            return lax.while_loop(cond, body, (out, c))[1]

        def reschedule(t, k, p, pc, pm, blk, c):
            """Alg. 3, the non-binding rescheduler, for each lane's blocked
            pod ``p`` (in the lanes ``blk``).  A pod pending for less than
            its lane's ``max_age`` waits.  Else the candidate victims are
            the READY nodes with room for its CPU that hold moveable pods,
            most free memory first, ties to the highest id rank (the
            pseudocode's descending order).  A victim's moveable pods are
            tried largest first, each placed by :func:`shadow_fit` on the
            other READY nodes from the call's free room, or skipped where
            none holds it, until they free ``needed = mem - free_mem`` of
            the victim, less 1e-9.  The first victim that frees that much
            with at least one mover is the plan: its movers are evicted in
            plan order and sit out the rest of the wave.  One loop walks
            victims and movers alike, one mover a lane a step.  Returns
            ``c``, the lanes whose call FAILED (they ask the autoscaler)
            and those that carried out a plan."""
            ready = c["nstate"] == NODE_READY
            age = f64_add(t, _row_pick(c["pend"], p) ^ _SIGN)
            gated = blk & (_order_key(age) >= _order_key(max_age))
            free_cpu = ac - c["used_cpu"]
            free_mem = f64_add(am, c["used_mem"] ^ _SIGN)
            cands = (gated[:, None] & ready & (free_cpu >= pc)
                     & (c["nmove"] > 0))
            movers = c["bound"] & valid & moveable
            bind_node = c["bind_node"]
            free_key = _order_key(free_mem)
            mem_key = _order_key(mem)
            node_ix = jnp.arange(n_pad, dtype=jnp.int32)[None, :]
            zero = jnp.zeros(L, mem.dtype)
            s = {"live": gated & cands.any(axis=1),
                 "open": jnp.zeros(L, bool), "found": jnp.zeros(L, bool),
                 "cands": cands, "victim": jnp.zeros(L, jnp.int32),
                 "rem": jnp.zeros_like(movers), "sel": jnp.zeros_like(movers),
                 "sh_cpu": free_cpu, "sh_mem": free_mem, "freed": zero,
                 "lim": zero, "steps": c["resched_steps"]}

            def cond(s):
                return s["live"].any()

            def body(s):
                s = dict(s)
                live, cands = s["live"], s["cands"]
                # A lane with no victim open opens its next candidate, on
                # a fresh shadow, with nothing freed yet.
                opening = live & ~s["open"]
                ck = jnp.where(cands, free_key, _KEY_MIN)
                top = cands & (ck == ck.max(axis=1, keepdims=True))
                v = (n_pad - 1 - jnp.argmax(top[:, ::-1], axis=1)).astype(
                    jnp.int32)
                s["cands"] = _row_put(cands, v, False, opening)
                o2 = opening[:, None]
                victim = jnp.where(opening, v, s["victim"])
                rem = jnp.where(o2, movers & (bind_node == v[:, None]),
                                s["rem"])
                sel = s["sel"] & ~o2
                sh_cpu = jnp.where(o2, free_cpu, s["sh_cpu"])
                sh_mem = jnp.where(o2, free_mem, s["sh_mem"])
                needed = f64_add(pm[:, 0], _row_pick(free_mem, v) ^ _SIGN)
                freed = jnp.where(opening, 0, s["freed"])
                lim = jnp.where(opening, f64_add(needed, _EPS_BITS ^ _SIGN),
                                s["lim"])
                # One mover while the victim frees too little; a mover
                # with no room elsewhere is skipped.
                act = (live & (_order_key(freed) < _order_key(lim))
                       & rem.any(axis=1))
                q = largest(rem, mem_key)
                others = ready & (node_ix != victim[:, None])
                put, qm, sh_cpu, sh_mem = shadow_fit(others, sh_cpu, sh_mem,
                                                     q, act)
                sel = _row_put(sel, q, True, put)
                freed = jnp.where(put, f64_add(freed, qm), freed)
                rem = _row_put(rem, q, False, act)
                enough = _order_key(freed) >= _order_key(lim)
                close = live & (enough | ~rem.any(axis=1))
                ok = close & enough & sel.any(axis=1)
                s.update(found=s["found"] | ok, open=live & ~close,
                         live=live & ~ok & (~close | s["cands"].any(axis=1)),
                         victim=victim, rem=rem, sel=sel, sh_cpu=sh_cpu,
                         sh_mem=sh_mem, freed=freed, lim=lim,
                         steps=s["steps"] + 1)
                return s

            s = _while(cond, body, s, list(s))
            found = s["found"]
            out = s["sel"] & found[:, None]
            c["resched_steps"] = s["steps"]
            keys = ["used_cpu", "used_mem", "pcount", "nmove", "bound", "pend",
                    "ev_pod", "ev_node", "ev_bind_cycle", "ev_bind_seq",
                    "ev_pend", "ev_cycle", "ev_seq", "ev_n", "overflow",
                    "seq_ctr", "resched_steps"]
            c.update(evict(t, k, {key: c[key] for key in keys}, s["victim"],
                           out, lambda o: largest(o, mem_key), c["bind_seq"],
                           c["bind_cycle"], "resched_steps"))
            c["attempted"] = c["attempted"] | out
            for key, n in (("resched_waits", blk & ~gated),
                           ("resched_plans", gated), ("resched_done", found),
                           ("resched_evictions", out)):
                c[key] = c[key] + n.sum(dtype=jnp.int64)
            return c, gated & ~found, found

        def scale_in(t, k, S, go):
            """Alg. 6 after a fully successful cycle (``go``): remove the
            empty autoscaled READY/TAINTED nodes, then visit the non-empty
            autoscaled READY nodes in launch order.  A node whose pods are
            all moveable, or whose moveable pods share it with batch pods,
            is consolidated when every moveable pod fits elsewhere by
            :func:`shadow_fit` on a shadow of the other READY nodes (pods by
            memory, then row, descending): its moveable pods are evicted in
            bind order and re-pend now; the first kind of node is removed,
            the second tainted.  Later candidates see the earlier
            changes."""
            nstate = S["nstate"]
            step1 = (go[:, None] & autoscaled & (S["pcount"] == 0)
                     & ((nstate == NODE_READY) | (nstate == NODE_TAINTED)))
            S["nstate"] = jnp.where(step1, NODE_GONE, nstate)
            S["gone_k"] = jnp.where(step1, k, S["gone_k"])
            S["gone_step"] = jnp.where(step1, 1, S["gone_step"])
            n1 = step1.sum(axis=1, dtype=jnp.int32)
            S["scale_ins"] = S["scale_ins"] + n1
            S["scale_in_nodes"] = S["scale_in_nodes"] + n1.sum(
                dtype=jnp.int64)
            # Only nodes holding moveable pods can change, and a node's
            # own pods change only when it is visited: the others are
            # skipped without a visit.
            n_mv, n_b, n_pods = S["nmove"], S["nbatch"], S["pcount"]
            cands = (go[:, None] & autoscaled & (n_pods > 0)
                     & (S["nstate"] == NODE_READY) & (n_mv > 0)
                     & ((n_mv == n_pods) | ((n_b > 0)
                                            & (n_mv + n_b == n_pods))))
            keys = ["used_cpu", "used_mem", "pcount", "nmove", "nstate",
                    "gone_k", "gone_step", "bound", "pend", "ev_pod",
                    "ev_node", "ev_bind_cycle", "ev_bind_seq", "ev_pend",
                    "ev_cycle", "ev_n", "overflow", "scale_ins",
                    "scale_in_nodes", "scale_in_steps"]
            if resched:
                keys += ["ev_seq", "seq_ctr"]
            bind_node, bind_seq = S["bind_node"], S["bind_seq"]
            bind_cycle, nbatch = S["bind_cycle"], S["nbatch"]
            mem_key = _order_key(mem)

            def placeable(c, node, movers, ok):
                """Shadow best-fit of ``movers`` (one node's moveable pods)
                onto the other READY nodes, largest first; ``ok`` stays
                True where every mover found room."""
                others = ((c["nstate"] == NODE_READY)
                          & (jnp.arange(n_pad)[None, :] != node[:, None]))

                def cond(s):
                    return (s[0].any(axis=1) & s[3]).any()

                def body(s):
                    rem, sh_cpu, sh_mem, ok, steps = s
                    act = rem.any(axis=1) & ok
                    q = largest(rem, mem_key)
                    put, _, sh_cpu, sh_mem = shadow_fit(others, sh_cpu,
                                                        sh_mem, q, act)
                    ok = ok & ~(act & ~put)
                    rem = _row_put(rem, q, False, act)
                    return rem, sh_cpu, sh_mem, ok, steps + 1

                s = (movers, ac - c["used_cpu"],
                     f64_add(am, c["used_mem"] ^ _SIGN), ok,
                     c["scale_in_steps"])
                s = lax.while_loop(cond, body, s)
                c["scale_in_steps"] = s[4]
                return s[3]

            def in_bind_order(out):
                return jnp.argmin(jnp.where(out, bind_seq, _SEQ_INF), axis=1)

            def cond(s):
                return s[0].any()

            def body(s):
                cands, c = s
                c = dict(c)
                has = cands.any(axis=1)
                node = jnp.argmin(jnp.where(cands, node_seq, n_pad),
                                  axis=1).astype(jnp.int32)
                cands = _row_put(cands, node, False, has)
                only = has & (_row_pick(c["nmove"], node)
                              == _row_pick(c["pcount"], node))
                mixed = has & ~only
                movers = ((only | mixed)[:, None] & c["bound"] & valid
                          & moveable & (bind_node == node[:, None]))
                ok = placeable(c, node, movers, only | mixed)
                c = evict(t, k, c, node, movers & ok[:, None], in_bind_order,
                          bind_seq, bind_cycle, "scale_in_steps")
                # ``ok`` only stays True in lanes with a candidate.
                c["nstate"] = _row_put(
                    c["nstate"], node,
                    jnp.where(only, NODE_GONE, NODE_TAINTED), ok)
                gone = ok & only
                c["gone_k"] = _row_put(c["gone_k"], node, k, gone)
                c["gone_step"] = _row_put(c["gone_step"], node, 2, gone)
                c["scale_ins"] = c["scale_ins"] + ok.astype(jnp.int32)
                c["scale_in_nodes"] = c["scale_in_nodes"] + gone.sum(
                    dtype=jnp.int64)
                c["scale_in_steps"] = c["scale_in_steps"] + 1
                return cands, c

            c = lax.while_loop(cond, body,
                               (cands, {key: S[key] for key in keys}))[1]
            return {**S, **c}

        def cycle_body(S):
            S = dict(S)
            k = S["k"]
            t = t_of[k]
            active = S["active"]
            S["active_lane_cycles"] = S["active_lane_cycles"] + active.sum(
                dtype=jnp.int64)
            if autoscale:
                # NODE_READY events due by t fire before CYCLE(t): the node
                # joins, and its tracker's pods lose their association.
                nstate = S["nstate"]
                ready = (active[:, None] & (nstate == NODE_BOOTING)
                         & (S["launch_k"] + boot[:, None] <= k))
                S["nstate"] = jnp.where(ready, NODE_READY, nstate)
                assoc = S["assoc"]
                # Each pod's node, by a one-hot over the node axis (no
                # association, -1, matches none).
                node_ax = jnp.arange(n_pad)[None, None, :]
                freed = ((assoc[:, :, None] == node_ax)
                         & ready[:, None, :]).any(axis=2)
                S["assoc"] = jnp.where(freed, -1, assoc)
                live = ((S["nstate"] != NODE_NONE)
                        & (S["nstate"] != NODE_GONE))
                S["active_node_cycles"] = S["active_node_cycles"] + (
                    live & active[:, None]).sum(dtype=jnp.int64)
            # POD_DONE events at times <= t all fire before CYCLE(t).
            S = completions(t, S)
            S = wave(t, k, S)
            S["k"] = k + 1
            return S

        def cycle_cond(S):
            return S["active"].any() & (S["k"] <= MAX_CYCLES)

        S = {
            "k": jnp.zeros((), jnp.int32),
            "used_cpu": jnp.zeros((L, n_pad), cpu.dtype),
            "used_mem": jnp.zeros((L, n_pad), mem.dtype),      # +0.0
            "pcount": jnp.zeros((L, n_pad), jnp.int32),
            "done_c": jnp.zeros((L, P), bool),
            "done_t": jnp.full((L, P), _INF_BITS),
            "bound": jnp.zeros((L, P), bool),
            "bind_node": jnp.full((L, P), -1, jnp.int32),
            "bind_seq": jnp.full((L, P), -1, jnp.int32),
            "bind_cycle": jnp.full((L, P), -1, jnp.int32),
            "active": valid.any(axis=1),
            "completed": jnp.zeros(L, bool),
            "done_time": jnp.full(L, _HORIZON_BITS),
            "done_is_cycle": jnp.zeros(L, bool),
            "seq_ctr": jnp.zeros(L, jnp.int32),
            "scale_outs": jnp.zeros(L, jnp.int32),
            "wave_steps": jnp.zeros((), jnp.int32),
            "completion_steps": jnp.zeros((), jnp.int32),
            "busy": jnp.zeros((), jnp.int64),
            "active_lane_cycles": jnp.zeros((), jnp.int64),
        }
        if autoscale:
            zl = jnp.zeros(L, jnp.int32)
            S.update(
                nstate=jnp.where(autoscaled, NODE_NONE,
                                 NODE_READY).astype(jnp.int32),
                launch_k=jnp.zeros((L, n_pad), jnp.int32),
                gone_k=jnp.full((L, n_pad), -1, jnp.int32),
                gone_step=jnp.zeros((L, n_pad), jnp.int32),
                nmove=jnp.zeros((L, n_pad), jnp.int32),
                nbatch=jnp.zeros((L, n_pad), jnp.int32),
                pf_cpu=jnp.zeros((L, n_pad), cpu.dtype),
                pf_mem=jnp.zeros((L, n_pad), mem.dtype),
                n_launched=n_nodes.astype(jnp.int32),
                assoc=jnp.full((L, P), -1, jnp.int32),
                pend=arr_t,
                ev_pod=jnp.zeros((L, X), jnp.int32),
                ev_node=jnp.zeros((L, X), jnp.int32),
                ev_bind_cycle=jnp.zeros((L, X), jnp.int32),
                ev_bind_seq=jnp.zeros((L, X), jnp.int32),
                ev_pend=jnp.zeros((L, X), arr_t.dtype),
                ev_cycle=jnp.zeros((L, X), jnp.int32),
                ev_n=zl, overflow=jnp.zeros(L, bool), scale_ins=zl,
                scale_out_nodes=jnp.zeros((), jnp.int64),
                scale_in_nodes=jnp.zeros((), jnp.int64),
                active_node_cycles=jnp.zeros((), jnp.int64),
                scale_in_steps=jnp.zeros((), jnp.int64))
        if resched:
            S["ev_seq"] = jnp.zeros((L, X), jnp.int32)
            S.update({key: jnp.zeros((), jnp.int64)
                      for key in RESCHED_COUNTERS})
        S = _while(cycle_cond, cycle_body, S, list(S))
        out = {
            "bound": S["bound"], "done_committed": S["done_c"],
            "bind_node": S["bind_node"], "bind_seq": S["bind_seq"],
            "bind_cycle": S["bind_cycle"], "done_t": S["done_t"],
            "completed": S["completed"], "done_time": S["done_time"],
            "done_is_cycle": S["done_is_cycle"],
            "scale_outs": S["scale_outs"],
            "n_cycles": S["k"], "wave_steps": S["wave_steps"],
            "completion_steps": S["completion_steps"],
            "busy_lane_steps": S["busy"],
            "active_lane_cycles": S["active_lane_cycles"],
            "used_cpu": S["used_cpu"], "used_mem": S["used_mem"],
            "pcount": S["pcount"],
        }
        if autoscale:
            for key in ("nstate", "launch_k", "gone_k", "gone_step", "pend",
                        "ev_pod", "ev_node", "ev_bind_cycle", "ev_bind_seq",
                        "ev_pend", "ev_cycle", "ev_n", "overflow",
                        "scale_ins") + FLEET_COUNTERS:
                out[key] = S[key]
        if resched:
            for key in ("ev_seq",) + RESCHED_COUNTERS:
                out[key] = S[key]
        return out

    name = "lane_program_" + sched.replace("-", "_")
    if autoscale:
        name += "_autoscaled"
    if resched:
        name += "_resched"
    run.__name__ = run.__qualname__ = name
    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _jit_cache(sched: str, n_pad: int, autoscale: bool = False,
               resched: bool = False):
    return _program_factory(sched, n_pad, autoscale, resched)


def program_args(batch: LaneBatch) -> tuple:
    """The program's host arguments for ``batch``, floats as bit patterns."""
    args = (f64_bits(batch.arrival_t), batch.cpu_m, f64_bits(batch.mem_mb),
            f64_bits(batch.duration_s), batch.is_batch, batch.valid,
            batch.n_nodes, batch.alloc_cpu, f64_bits(batch.alloc_mem))
    if batch.autoscale:
        args += (batch.moveable, batch.boot_cycles)
    if batch.resched:
        args += (f64_bits(batch.max_age),)
    return args


def run_lane_batch(batch: LaneBatch) -> dict:
    """Execute one :class:`LaneBatch`; returns numpy lane outputs.

    Per lane: ``completed`` / ``done_time`` / ``done_is_cycle`` /
    ``scale_outs``; per pod: ``bound``, ``bind_node`` (node *rank* —
    serial parity maps ``node_slot`` through ``ClusterArrays.id_rank``),
    ``bind_seq`` (per-lane bind order), ``bind_cycle`` (bind time is
    exactly ``bind_cycle * 10.0``), ``done_t`` and ``done_committed``.
    Per batch: the scalar step counts of :data:`COUNTERS`.  An autoscaled
    batch adds, per node record (index as :func:`node_layout`):
    ``nstate`` (:data:`NODE_STATES`), ``launch_k`` (launch cycle, 0 for
    a static node), ``gone_k`` / ``gone_step`` (the cycle and Alg. 6 step
    that removed it, or -1 / 0); per pod ``pend`` (pending since); per
    eviction, in order, ``ev_pod`` / ``ev_node`` / ``ev_bind_cycle`` /
    ``ev_bind_seq`` / ``ev_pend`` (the closed incarnation) and
    ``ev_cycle``, ``ev_n`` of them; per lane ``scale_ins`` and
    ``overflow`` (past its node or eviction records: its outputs are not
    a run); and the counts of :data:`FLEET_COUNTERS`.

    Spans of :data:`PROFILER`: ``lanes.dispatch`` (arguments and the jit
    call), ``lanes.wait`` (until the device is done), ``lanes.fetch`` (the
    copies to the host).
    """
    import jax
    span = PROFILER.span
    with jax.enable_x64(True):
        with span("lanes.dispatch"):
            run = _jit_cache(batch.scheduler, batch.n_pad, batch.autoscale,
                             batch.resched)
            dev = run(*program_args(batch))
        with span("lanes.wait"):
            jax.block_until_ready(dev)
        with span("lanes.fetch"):
            out = {key: np.asarray(v) for key, v in dev.items()}
            for key in ("done_t", "done_time", "used_mem", "pend",
                        "ev_pend"):
                if key in out:
                    out[key] = out[key].view(np.float64)
    return out
