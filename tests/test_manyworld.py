"""Many-world lane engine (repro.manyworld): lane-vs-serial parity and
padded-shape/masking edge cases.

The parity suite is the engine's contract: inside the relaxed envelope
(void/void static cluster) every lane reproduces the serial engine's bind
sequence **bit-identically** — same rows bound, to the same nodes (rank ==
lexicographic node_id order), at the same cycle times, in the same order —
and the evaluator reconstructs `run_cells` rows whose 17 metric fields are
bitwise equal to the serial runner's.  The edge battery pins the padding
and masking behaviors (zero-pod lanes, all-infeasible lanes, non-pow2 lane
counts, mixed lane sizes in one bucket) and the integer IEEE-754 add the
program does its float arithmetic with; hand-built corner lanes and
bitwise ``math.fsum`` cases pin the host's bucket-wide rebuild.
"""
import dataclasses
import math

import numpy as np
import pytest

jax = pytest.importorskip("jax")   # lane engine is JAX-gated by design

from repro.cloud.adapter import M2_SMALL
from repro.core import build_simulation, reset_id_counters
from repro.core.pods import PodKind, PodSpec
from repro.core.resources import Resources
from repro.manyworld import evaluator as ev
from repro.manyworld import lanes as ml
from repro.manyworld.evaluator import (_exact_sums, _limbs, _round_limbs,
                                      _split, lane_calls, lane_eligible,
                                      run_cells_lanes)
from repro.scenarios import register
from repro.scenarios.trace import KIND_BATCH, TraceStore
from repro.search.runner import _RESULT_FIELDS, CellSpec, _get_trace, run_cells

ALLOC_CPU = float(M2_SMALL.allocatable.cpu_m)
ALLOC_MEM = float(M2_SMALL.allocatable.mem_mb)


def _lane_of(trace, n_nodes):
    d = trace.to_lane_arrays()
    d["n_nodes"] = n_nodes
    d["alloc_cpu"] = ALLOC_CPU
    d["alloc_mem"] = ALLOC_MEM
    return d


def _serial_bind_columns(cell, trace):
    """(bound, rank, bind_t) columns from a serial array-engine run, with
    node slots mapped through ``id_rank`` into the lane engine's rank
    space (lexicographic node_id order)."""
    reset_id_counters()
    sim = build_simulation(cell.to_experiment_spec(trace))
    res = sim.run()
    store, arr = sim.orch.store, sim.orch.cluster.arrays
    n = trace.n
    bound = np.array([store.node_slot[i] >= 0 for i in range(n)])
    rank = np.array([arr.id_rank[store.node_slot[i]]
                     if store.node_slot[i] >= 0 else -1 for i in range(n)])
    bind_t = np.array([store.bound_time[i] if store.bound_time[i] is not None
                       else np.nan for i in range(n)])
    return res, bound, rank, bind_t


CASES = [
    # (scenario, scheduler, n_nodes): batch-only completing lanes,
    # service lanes that run to the horizon, a saturated 1-node lane, and
    # >10-node fleets (node-ids sort lexicographically: rank permutation).
    ("heavy-tail", "best-fit", 4),
    ("heavy-tail", "worst-fit", 1),
    ("heavy-tail", "first-fit", 3),
    ("heavy-tail", "best-fit", 12),
    ("heavy-tail", "worst-fit", 12),
    ("capacity-crunch", "best-fit", 2),
    ("diurnal", "first-fit", 3),
    ("mix-ramp", "worst-fit", 12),
]


class TestLaneParity:
    @pytest.mark.parametrize("scen,sched,nw", CASES)
    def test_bind_sequence_bitwise(self, scen, sched, nw):
        """Lane bind sequence == serial bind sequence: same rows, nodes,
        times, order; same completion flag, time, and scale-out count."""
        trace = _get_trace(scen, 0, 40)
        out = ml.run_lane_batch(ml.stack_lanes([_lane_of(trace, nw)], sched))
        cell = CellSpec(scenario=scen, scheduler=sched, autoscaler="void",
                        rescheduler="void", seed=0, n_jobs=40, engine="array",
                        initial_workers=nw)
        res, bound_s, rank_s, bt_s = _serial_bind_columns(cell, trace)
        n = trace.n
        bl = out["bound"][0, :n]
        assert np.array_equal(bound_s, bl)
        assert np.array_equal(rank_s[bl], out["bind_node"][0, :n][bl])
        assert np.array_equal(bt_s[bl], out["bind_cycle"][0, :n][bl] * 10.0)
        assert res.completed == bool(out["completed"][0])
        assert res.scale_outs == int(out["scale_outs"][0])
        # Bind *order*: lane seq sorts rows exactly like serial
        # (bound_time, row) — waves walk the FIFO snapshot in row order.
        seq = out["bind_seq"][0, :n]
        lane_order = sorted(np.nonzero(bl)[0], key=lambda i: seq[i])
        serial_order = sorted(np.nonzero(bound_s)[0],
                              key=lambda i: (bt_s[i], i))
        assert lane_order == serial_order

    def test_many_lanes_one_batch(self):
        """Stacked lanes don't interfere: each lane of a mixed batch
        (different seeds/sizes/fleets, one scheduler) equals its own
        single-lane run."""
        specs = [(0, 40, 4), (1, 40, 2), (2, 24, 3), (3, 40, 1), (4, 32, 5)]
        lanes = []
        for seed, nj, nw in specs:
            lanes.append(_lane_of(_get_trace("heavy-tail", seed, nj), nw))
        batch_out = ml.run_lane_batch(ml.stack_lanes(lanes, "best-fit"))
        for li, lane in enumerate(lanes):
            solo = ml.run_lane_batch(ml.stack_lanes([lane], "best-fit"))
            for key in ("bound", "bind_node", "bind_seq", "bind_cycle"):
                p = lane["arrival_t"].size
                assert np.array_equal(batch_out[key][li, :p],
                                      solo[key][0, :p]), (key, li)
            assert batch_out["completed"][li] == solo["completed"][0]
            assert batch_out["done_time"][li] == solo["done_time"][0]


class TestEvaluatorRows:
    def test_rows_bitwise_equal_serial(self):
        """workers='lanes' rows == serial rows on every metric field,
        including ineligible-cell fallback and the infeasible
        short-circuit, in submission order."""
        cells = [
            CellSpec(scenario="heavy-tail", scheduler="best-fit",
                     autoscaler="void", rescheduler="void", seed=0,
                     n_jobs=40, engine="array", initial_workers=4),
            CellSpec(scenario="diurnal", scheduler="first-fit",
                     autoscaler="void", rescheduler="void", seed=0,
                     n_jobs=24, engine="array", initial_workers=3),
            CellSpec(scenario="mix-ramp", scheduler="worst-fit",
                     autoscaler="void", rescheduler="void", seed=2,
                     n_jobs=40, engine="array", initial_workers=5),
            # outside the lane schedulers -> serial on every backend
            CellSpec(scenario="diurnal", scheduler="k8s-default",
                     autoscaler="void", rescheduler="void", seed=0,
                     n_jobs=24, engine="array", initial_workers=3),
            CellSpec(scenario="heavy-tail", scheduler="weighted",
                     autoscaler="void", rescheduler="void", seed=1,
                     n_jobs=40, engine="array", initial_workers=5,
                     scheduler_weights=(0.2, 0.5, 0.3)),
            # the binding autoscaler: an autoscaled lane
            CellSpec(scenario="heavy-tail", scheduler="best-fit",
                     autoscaler="binding", seed=0, n_jobs=16,
                     engine="array"),
            # ineligible: the non-binding autoscaler -> serial fallback
            CellSpec(scenario="heavy-tail", scheduler="best-fit",
                     autoscaler="non-binding", seed=0, n_jobs=16,
                     engine="array"),
            # infeasible short-circuit: heavy-tail pods exceed m2.tiny
            CellSpec(scenario="heavy-tail", scheduler="best-fit",
                     autoscaler="void", rescheduler="void", seed=0,
                     n_jobs=40, engine="array", initial_workers=2,
                     template_name="m2.tiny"),
        ]
        serial = run_cells(cells, workers=1)
        rows = run_cells(cells, workers="lanes")
        assert [r["label"] for r in rows] == [r["label"] for r in serial]
        for s, l in zip(serial, rows):
            for field in _RESULT_FIELDS:
                assert s[field] == l[field], (s["label"], field)
            assert s["infeasible"] == l["infeasible"]
            assert s["n_jobs"] == l["n_jobs"]

    def test_eligibility_gate(self):
        base = dict(scenario="heavy-tail", scheduler="best-fit",
                    autoscaler="void", rescheduler="void", engine="array")
        assert lane_eligible(CellSpec(**base))
        # Float multiply/divide scores stay on the serial reference.
        assert not lane_eligible(CellSpec(**{**base, "scheduler": "k8s-default"}))
        assert not lane_eligible(CellSpec(**{**base, "scheduler": "weighted"}))
        assert lane_eligible(CellSpec(**{**base, "engine": None}))
        # The binding autoscaler runs as lanes; the other autoscalers, an
        # Alg. 6 gate and a delay the program cannot hold stay serial.
        assert lane_eligible(CellSpec(**{**base, "autoscaler": "binding"}))
        assert lane_eligible(CellSpec(**{**base, "autoscaler": "binding",
                                         "template_name": "tpu-v5e-host"}))
        assert not lane_eligible(CellSpec(**{**base,
                                             "autoscaler": "non-binding"}))
        assert not lane_eligible(CellSpec(**{**base,
                                             "autoscaler": "predictive"}))
        assert not lane_eligible(CellSpec(**{**base, "autoscaler": "binding",
                                             "scale_in_util_ceiling": 0.5}))
        assert not lane_eligible(CellSpec(**{**base, "autoscaler": "binding",
                                             "template_name": "no-such"}))
        assert not lane_eligible(CellSpec(**{**base, "autoscaler": "binding",
                                             "rescheduler": "binding"}))
        assert not lane_eligible(CellSpec(**{**base, "rescheduler": "non-binding"}))
        # Alg. 3 runs as lanes under the binding autoscaler.
        assert lane_eligible(CellSpec(**{**base, "autoscaler": "binding",
                                         "rescheduler": "non-binding"}))
        assert not lane_eligible(CellSpec(**{**base, "engine": "object"}))
        assert not lane_eligible(
            CellSpec(**{**base, "scenario": "zone-outage", "chaos": True}))
        assert not lane_eligible(   # weights demand the weighted scheduler
            CellSpec(**{**base, "scheduler_weights": (1.0, 0.0, 0.0)}))


class TestPaddingAndMasking:
    def test_zero_pod_lane(self):
        """An empty trace never completes: the lane runs (host-side) to
        the horizon with a flat-zero utilisation series — and a zero-pod
        lane stacked with real lanes doesn't disturb them."""
        trace = _get_trace("heavy-tail", 0, 40)
        empty = trace.slice(0, 0)
        cells = [CellSpec(scenario="heavy-tail", scheduler="best-fit",
                          autoscaler="void", rescheduler="void", seed=0,
                          n_jobs=nj, engine="array", initial_workers=2)
                 for nj in (0, 40)]
        serial = run_cells(cells, workers=1)
        rows = run_cells(cells, workers="lanes")
        for s, l in zip(serial, rows):
            for field in _RESULT_FIELDS:
                assert s[field] == l[field], (s["label"], field)
        assert rows[0]["completed"] is False
        assert rows[0]["max_nodes"] == 2
        assert empty.n == 0 and empty.to_lane_arrays()["arrival_t"].size == 0

    def test_all_infeasible_lane_blocks_forever(self):
        """A lane none of whose pods ever fit (requests larger than the
        whole node) binds nothing, counts every attempt as a scale-out
        request, and goes permanently stuck — without perturbing a
        feasible neighbor lane in the same batch."""
        big = {"arrival_t": np.array([0.0, 5.0]),
               "cpu_m": np.array([2000.0, 2000.0]),       # > 940 alloc
               "mem_mb": np.array([100.0, 100.0]),
               "duration_s": np.array([60.0, 60.0]),
               "is_batch": np.array([True, True]),
               "n_nodes": 3, "alloc_cpu": ALLOC_CPU, "alloc_mem": ALLOC_MEM}
        ok = _lane_of(_get_trace("heavy-tail", 0, 24), 3)
        out = ml.run_lane_batch(ml.stack_lanes([big, ok], "best-fit"))
        assert not out["bound"][0].any()
        assert not out["completed"][0]
        # Stuck on the first cycle with both pods arrived: the engine
        # stops cycling that lane; by then each pending pod was counted
        # once per cycle it was attempted.
        assert int(out["scale_outs"][0]) >= 2
        solo = ml.run_lane_batch(ml.stack_lanes([ok], "best-fit"))
        p = ok["arrival_t"].size
        assert np.array_equal(out["bound"][1, :p], solo["bound"][0, :p])

    def test_non_pow2_lane_counts(self):
        """3 and 5 lanes (not a multiple of any tile) give the same
        per-lane outputs as 1-lane batches."""
        lanes = [_lane_of(_get_trace("heavy-tail", s, 24), 2)
                 for s in range(5)]
        for cnt in (3, 5):
            out = ml.run_lane_batch(ml.stack_lanes(lanes[:cnt], "best-fit"))
            for li in range(cnt):
                solo = ml.run_lane_batch(ml.stack_lanes([lanes[li]],
                                                        "best-fit"))
                p = lanes[li]["arrival_t"].size
                assert np.array_equal(out["bind_seq"][li, :p],
                                      solo["bind_seq"][0, :p])

    def test_pad_rejects_oversized_lane(self):
        lane = _lane_of(_get_trace("heavy-tail", 0, 40), 2)
        with pytest.raises(ValueError, match="p_pad"):
            ml.stack_lanes([lane], "best-fit", p_pad=16)
        with pytest.raises(ValueError, match="scheduler"):
            ml.stack_lanes([lane], "k8s-default")
        with pytest.raises(ValueError, match="milli-cores"):
            ml.stack_lanes([{**lane, "cpu_m": lane["cpu_m"] + 0.5}],
                           "best-fit")

    def test_next_pow2(self):
        assert [ml.next_pow2(n) for n in (0, 1, 2, 3, 40, 64, 65)] \
            == [1, 1, 2, 4, 64, 64, 128]


class TestSelectKernels:
    def test_backends_agree_with_numpy(self):
        """The lane select is NumPy's first-occurrence masked argmin over
        int64 score keys, including tie rows and all-masked rows (callers
        gate on mask.any — the index just has to be in range)."""
        rng = np.random.default_rng(7)
        keys = rng.integers(-2**62, 2**62, (17, 13), dtype=np.int64)
        keys[3, 4] = keys[3, 9] = keys[3].min() - 1            # exact tie
        mask = rng.random((17, 13)) < 0.6
        mask[5] = False                                        # all masked
        mask[3, 4] = mask[3, 9] = True
        from jax import enable_x64
        with enable_x64(True):
            got = np.asarray(ml.masked_argmin(keys, mask))
        buf = np.where(mask, keys, np.iinfo(np.int64).max)
        ref = buf.argmin(axis=1)
        rows = mask.any(axis=1)
        assert np.array_equal(got[rows], ref[rows])
        assert got[3] == 4                                     # first tie
        assert 0 <= got[5] < 13

    @pytest.mark.parametrize("dtype", ["int64", "int32", "bool"])
    @pytest.mark.parametrize("n_lanes", [1, 4096])
    def test_row_helpers_equal_indexed_forms(self, dtype, n_lanes):
        """The step loops' one-hot row reads and writes equal the
        ``.at[]`` and fancy-index forms bit for bit: float64 bit patterns
        (negative, -0.0, +inf, the key fill) and counters, masked-off
        lanes, the first and last column, one lane and 4,096."""
        import jax.numpy as jnp
        rng = np.random.default_rng(11)
        L, N = n_lanes, 37
        special = np.concatenate([
            ml.f64_bits([np.inf, -0.0, -2415.616]),
            np.array([ml._KEY_MAX, ml._SIGN, -1, 0], np.int64)])
        if dtype == "bool":
            x, v = rng.random((L, N)) < 0.5, rng.random(L) < 0.5
        else:
            info = np.iinfo(dtype)
            x = rng.integers(info.min, info.max, (L, N), dtype=dtype,
                             endpoint=True)
            v = rng.integers(info.min, info.max, L, dtype=dtype,
                             endpoint=True)
            if dtype == "int64":
                x.flat[rng.integers(0, x.size, x.size // 3)] = rng.choice(
                    special, x.size // 3)
                v[::2] = rng.choice(special, v[::2].size)
        li = np.arange(L)
        m0 = rng.random(L) < 0.5
        with jax.enable_x64(True):
            put, add, pick = (jax.jit(ml._row_put), jax.jit(ml._row_add),
                              jax.jit(ml._row_pick))
            for i in (np.zeros(L, np.int32), np.full(L, N - 1, np.int32),
                      rng.integers(0, N, L).astype(np.int32)):
                for m in (m0, ~m0):
                    xj = jnp.asarray(x)
                    ref = xj.at[li, i].set(jnp.where(m, v, xj[li, i]))
                    assert np.array_equal(np.asarray(put(x, i, v, m)),
                                          np.asarray(ref))
                    ref = xj.at[li, i].set(jnp.where(m, v[0], xj[li, i]))
                    assert np.array_equal(np.asarray(put(x, i, v[0], m)),
                                          np.asarray(ref))
                    for d in ((-1, 2) if dtype != "bool" else ()):
                        ref = xj.at[li, i].add(jnp.where(m, d, 0))
                        assert np.array_equal(np.asarray(add(x, i, d, m)),
                                              np.asarray(ref))
                assert np.array_equal(np.asarray(pick(x, i)), x[li, i])
                assert np.array_equal(np.asarray(pick(x[:1], i)), x[0, i])


def _f64_cases(name, rng, n=4096):
    """Operand pairs for one family of float64 additions."""
    if name == "random-bits":       # every finite exponent, both signs
        bits = rng.integers(0, 2**63 - 1, n, dtype=np.int64)
        finite = ((bits >> 52) & 0x7FF) != 0x7FF
        bits = np.where(finite, bits, bits & ~(np.int64(1) << 62))
        neg = rng.random(n) < 0.5
        a = np.where(neg, bits | np.int64(-2**63), bits).view(np.float64)
        return a, np.roll(a, 1)
    e = rng.integers(-60, 60, n)
    a = rng.standard_normal(n) * 2.0 ** e
    if name == "cancellation":      # near-equal magnitudes, opposite signs
        return a, -a * (1 + rng.integers(-4, 5, n) * 2.0**-52)
    if name == "mixed-scale":       # alignment shifts up to and past 53
        return a, rng.standard_normal(n) * 2.0 ** (e + rng.integers(-60, 60, n))
    if name == "subnormal":
        sub = rng.integers(0, 2**52, n, dtype=np.int64).view(np.float64)
        sub = sub * rng.choice([1.0, -1.0], n)
        return sub, np.roll(sub, 3)
    if name == "lane-values":       # pod memory sizes, slack, signed zeros
        vals = np.array([307.2, 614.4, 921.6, 1024.0, 1433.6, 2415.616,
                         3584.0, 1e-9, 0.0, -0.0])
        pick = lambda: (vals[rng.integers(0, vals.size, n)]  # noqa: E731
                        * rng.choice([1.0, -1.0], n))
        return pick(), pick()
    assert name == "times"          # cycle start + batch duration
    return (rng.integers(0, ml.MAX_CYCLES + 1, n) * 10.0,
            np.clip(rng.lognormal(np.log(120.0), 1.0, n), 1.0, 7200.0))


class TestF64Add:
    @pytest.mark.parametrize("family", [
        "random-bits", "cancellation", "mixed-scale", "subnormal",
        "lane-values", "times"])
    def test_matches_numpy_bits(self, family):
        """The integer IEEE-754 add equals NumPy's float64 add bit for bit
        on every finite sum (round to nearest, ties to even; subnormals
        and signed zeros included)."""
        a, b = _f64_cases(family, np.random.default_rng(3))
        with np.errstate(over="ignore"):
            ref = a + b
        ok = np.isfinite(ref)
        from jax import enable_x64
        with enable_x64(True):
            got = np.asarray(jax.jit(ml.f64_add)(ml.f64_bits(a),
                                                  ml.f64_bits(b)))
        assert np.array_equal(got[ok], ml.f64_bits(ref)[ok])

    def test_order_key_orders_like_floats(self):
        rng = np.random.default_rng(5)
        x = np.concatenate([rng.standard_normal(4096) * 1e3,
                            [0.0, -0.0, 5e-324, -5e-324, np.inf]])
        from jax import enable_x64
        with enable_x64(True):
            k = np.asarray(ml._order_key(ml.f64_bits(x)))
        i, j = rng.integers(0, x.size, (2, 20000))
        assert np.array_equal(k[i] < k[j], x[i] < x[j])
        assert np.array_equal(k[i] == k[j], x[i] == x[j])


class TestLaneExports:
    def test_trace_to_lane_arrays(self):
        trace = _get_trace("mix-ramp", 0, 24)
        d = trace.to_lane_arrays()
        assert d["arrival_t"].dtype == np.float64
        assert d["cpu_m"].dtype == np.float64
        assert np.array_equal(d["cpu_m"], trace.cpu_m.astype(np.float64))
        assert np.array_equal(d["is_batch"], trace.kind == KIND_BATCH)
        assert all(d[k].size == trace.n for k in
                   ("arrival_t", "cpu_m", "mem_mb", "duration_s", "is_batch"))

    def test_engine_lane_snapshot_and_columns(self):
        """ClusterArrays.lane_snapshot is rank-ordered (id order) and
        PodStore.lane_columns lists pending rows in FIFO order."""
        cell = CellSpec(scenario="heavy-tail", scheduler="best-fit",
                        autoscaler="void", rescheduler="void", seed=0,
                        n_jobs=24, engine="array", initial_workers=3)
        trace = _get_trace("heavy-tail", 0, 24)
        reset_id_counters()
        sim = build_simulation(cell.to_experiment_spec(trace))
        sim.orch.submit_trace(trace, 0, 8)
        cols = sim.orch.store.lane_columns()
        assert np.array_equal(cols["arrival_t"], trace.arrival_time[:8])
        assert np.array_equal(cols["cpu_m"],
                              trace.cpu_m[:8].astype(np.float64))
        snap = sim.orch.cluster.arrays.lane_snapshot()
        assert snap["ready"].all() and snap["used_mem"].shape == (3,)
        sim.orch.cycle(0.0)                     # bind the snapshot
        snap2 = sim.orch.cluster.arrays.lane_snapshot()
        arr = sim.orch.cluster.arrays
        rank = arr._sorted_slots
        assert np.array_equal(snap2["used_mem"], arr.used_mem[rank])
        assert sim.orch.store.lane_columns()["arrival_t"].size \
            < cols["arrival_t"].size            # some rows left PENDING->BOUND


def _big_lane():
    """Two pods larger than a node: blocked in every cycle until stuck."""
    return {"arrival_t": np.array([0.0, 5.0]),
            "cpu_m": np.array([2000.0, 2000.0]),
            "mem_mb": np.array([100.0, 100.0]),
            "duration_s": np.array([60.0, 60.0]),
            "is_batch": np.array([True, True]),
            "n_nodes": 3, "alloc_cpu": ALLOC_CPU, "alloc_mem": ALLOC_MEM}


COUNT_CASES = [(scen, sched, nw) for scen, sched, nw in CASES] + [
    ("paper-bursty", "best-fit", 19), ("paper-slow", "best-fit", 19),
    ("blocked", "best-fit", 3)]


class TestLaneCounters:
    @pytest.mark.parametrize("scen,sched,nw", COUNT_CASES)
    def test_one_lane_counts_its_attempts(self, scen, sched, nw):
        """One lane: a wave step is one attempt (a bind, or a blocked pod
        counted once in each cycle it waits, as ``scale_outs`` counts it),
        a completion step one commit, every inner step busy, and the lane
        active in every outer cycle."""
        lane = (_big_lane() if scen == "blocked"
                else _lane_of(_get_trace(scen, 0, 40), nw))
        out = ml.run_lane_batch(ml.stack_lanes([lane], sched))
        binds = int(out["bound"][0].sum())
        blocked = int(out["scale_outs"][0])
        commits = int(out["done_committed"][0].sum())
        assert int(out["wave_steps"]) == binds + blocked
        assert int(out["completion_steps"]) == commits
        assert int(out["busy_lane_steps"]) == binds + blocked + commits
        assert int(out["active_lane_cycles"]) == int(out["n_cycles"]) > 0
        if scen == "blocked":
            assert binds == 0 and blocked >= 2

    def test_many_lanes_occupancy_at_most_full(self):
        """Several lanes in lockstep: busy lane-steps are every lane's
        attempts and commits, each inner loop runs as long as its busiest
        lane, and busy plus active lane-steps fill at most the lanes times
        the steps."""
        lanes = [_lane_of(_get_trace("heavy-tail", s, nj), nw)
                 for s, nj, nw in ((0, 40, 4), (1, 40, 2), (2, 24, 3))]
        lanes.append(_big_lane())
        out = ml.run_lane_batch(ml.stack_lanes(lanes, "best-fit"))
        L = len(lanes)
        attempts = out["bound"].sum(axis=1) + out["scale_outs"]
        commits = out["done_committed"].sum(axis=1)
        assert int(out["busy_lane_steps"]) == int((attempts + commits).sum())
        assert int(out["wave_steps"]) >= int(attempts.max())
        assert int(out["completion_steps"]) >= int(commits.max())
        steps = int(out["n_cycles"] + out["wave_steps"]
                    + out["completion_steps"])
        used = int(out["busy_lane_steps"] + out["active_lane_cycles"])
        assert 0 < used <= L * steps
        assert int(out["active_lane_cycles"]) <= L * int(out["n_cycles"])

    def test_program_and_scopes_are_named(self):
        """The compiled module is named after its scheduler and its ops
        carry the loop scopes, so a trace says what ran."""
        lane = _lane_of(_get_trace("heavy-tail", 0, 8), 2)
        batch = ml.stack_lanes([lane], "worst-fit")
        with jax.enable_x64(True):
            text = ml._jit_cache("worst-fit", batch.n_pad).lower(
                *ml.program_args(batch)).as_text(debug_info=True)
        assert "lane_program_worst_fit" in text
        for scope in ("completions", "wave", "cycle_end"):
            assert f"/{scope}/" in text, scope

    @pytest.mark.parametrize("case", ["corners", "planted", "narrow"])
    def test_node_sum_fallbacks_count_fsum_states(self, corner_cells,
                                                  monkeypatch, case):
        """``node_sum_fallbacks`` counts the states whose node sums went to
        ``math.fsum``: none on the corner lanes, at least one a lane on
        the planted ratio below the limbs' reach, and every state that
        counts more nodes than the limb sums may (their bound shrunk to
        one node here).  The rows equal the serial ones in each case."""
        planted = case == "planted"
        cells = [c for i, c in enumerate(corner_cells)
                 if (i % len(CORNER_LANES) == FALLBACK_LANE) == planted]
        if case == "narrow":
            monkeypatch.setattr(ev, "_LIMB_NODES", 1)
        rows = run_cells_lanes(cells)
        counts = lane_calls(1)[0]["counts"]
        got = counts["node_sum_fallbacks"]
        if case == "corners":
            assert got == 0
        elif planted:
            assert got >= len(cells) == 2
        else:
            assert 0 < got <= counts["sample_states"]
        _assert_rows_equal(run_cells(cells, workers=1), rows)


def _span_cells():
    """Three lane cells in two buckets (two schedulers) and one serial
    fallback cell."""
    base = dict(autoscaler="void", rescheduler="void", engine="array")
    return [CellSpec(scenario="heavy-tail", scheduler="best-fit", seed=0,
                     n_jobs=40, initial_workers=4, **base),
            CellSpec(scenario="heavy-tail", scheduler="best-fit", seed=1,
                     n_jobs=40, initial_workers=3, **base),
            CellSpec(scenario="diurnal", scheduler="first-fit", seed=0,
                     n_jobs=24, initial_workers=3, **base),
            CellSpec(scenario="heavy-tail", scheduler="k8s-default", seed=0,
                     n_jobs=16, initial_workers=3, **base)]


CHILDREN = {"lanes.prepare", "lanes.stack", "lanes.dispatch", "lanes.wait",
            "lanes.fetch", "lanes.rebuild"}


class TestLaneSpans:
    def test_one_call_records_one_rooted_tree(self):
        """One call: one ``lanes.call`` root, its children named as the
        phases, parented on the root and inside its interval; the call's
        record sums their self times to the root's duration and its
        counts to the buckets' program counts."""
        prof = ml.PROFILER
        first = prof.n_spans_seen
        rows = run_cells_lanes(_span_cells())
        sp = prof.to_payload()["spans"]
        base = prof.n_spans_seen - len(sp["t0"])
        ids = np.arange(base, prof.n_spans_seen)
        mine = ids >= first
        table = prof.to_payload()["names"]
        names = [table[int(i)] for i in sp["name"][mine]]
        t0, dur = sp["t0"][mine], sp["dur_s"][mine]
        parent, own = sp["parent"][mine], ids[mine]
        assert names.count("lanes.call") == 1
        r = names.index("lanes.call")
        assert parent[r] == -1
        kids = [j for j in range(len(names)) if j != r]
        assert {names[j] for j in kids} == CHILDREN
        assert [names[j] for j in kids].count("lanes.prepare") == 1
        for phase in CHILDREN - {"lanes.prepare"}:
            assert [names[j] for j in kids].count(phase) == 2, phase
        for j in kids:
            assert parent[j] == own[r]
            assert t0[r] <= t0[j] and t0[j] + dur[j] <= t0[r] + dur[r]
        rec = lane_calls(1)[0]
        assert (rec["lanes"], rec["buckets"]) == (3, 2)
        assert set(rec["self_s"]) == CHILDREN | {"lanes.call"}
        assert rec["wall_s"] == pytest.approx(dur[r], rel=1e-9)
        assert sum(rec["self_s"].values()) == pytest.approx(dur[r])
        assert all(v >= 0 for v in rec["self_s"].values())
        assert rec["counts"]["lane_steps"] >= (
            rec["counts"]["busy_lane_steps"]
            + rec["counts"]["active_lane_cycles"]) > 0
        assert len(rows) == 4 and all(r is not None for r in rows)

    def test_second_identical_population_compiles_nothing(self):
        cells = _span_cells()[:2]
        ml._jit_cache.cache_clear()
        run_cells_lanes(cells)
        run_cells_lanes(cells)
        first, second = lane_calls(2)
        assert first["compiles"] == 1 and second["compiles"] == 0
        assert second["call"] == first["call"] + 1
        assert first["counts"] == second["counts"]
        assert lane_calls(0) == []

    def test_spans_sit_on_the_trace_host_plane(self, tmp_path):
        """Under the JAX profiler the program's spans are host events
        inside ``lanes.call``, on the clock of the program's operations:
        on the CPU those run on host threads, between the dispatch and
        the end of the wait."""
        import glob

        from jax.profiler import ProfileData
        cells = _span_cells()[:2]
        run_cells_lanes(cells)                    # compiled outside
        with jax.profiler.trace(str(tmp_path)):
            run_cells_lanes(cells)
        path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                          recursive=True)
        spans, ops = [], []
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("lanes."):
                        spans.append((ev.name, ev.start_ns, ev.duration_ns))
                    elif ev.name.startswith("while"):
                        ops.append((ev.start_ns, ev.duration_ns))
        (call,) = [s for s in spans if s[0] == "lanes.call"]
        c0, c1 = call[1], call[1] + call[2]
        kids = {s[0]: s for s in spans if s[0] != "lanes.call"}
        assert set(kids) == CHILDREN
        assert all(c0 <= s[1] and s[1] + s[2] <= c1 for s in spans)
        d0 = kids["lanes.dispatch"][1]
        w1 = kids["lanes.wait"][1] + kids["lanes.wait"][2]
        assert ops and all(d0 <= s and s + d <= w1 for s, d in ops)


# -- the bucket-wide rebuild -------------------------------------------------

TINY = 3584.0 * 2.0 ** -53        # a memory ratio of 2**-53 on m2.small
#: Hand-built lanes, (nodes, pods as (arrival, cpu_m, mem_mb, duration, or
#: None for a service)); each pins corners of the sample replay.
CORNER_LANES = [
    # binds at cycle 0; completions on the grid: td == 20 bound at cycle 0,
    # td == 40 bound at 0 (tc < td - 20) and at 30 (not); the POD_DONE of
    # the pod bound at 30 completes the lane at te == 40
    (4, [(0.0, 300, 512.0, 20.0), (0.0, 300, 512.0, 40.0),
         (25.0, 300, 512.0, 10.0)]),
    # a POD_DONE trigger at te == 60 pushed at cycle 0, before SAMPLE(60)
    (3, [(0.0, 300, 512.0, 60.0), (5.0, 300, 512.0, 15.0),
         (7.0, 200, 256.0, 30.0)]),
    # a CYCLE trigger on the grid: the service bound at cycle 100
    (4, [(0.0, 300, 512.0, 30.0), (2.0, 100, 100.0, 18.0),
         (95.0, 200, 256.0, None)]),
    # a CYCLE trigger off the grid: the service bound at cycle 50
    (3, [(0.0, 300, 512.0, 30.0), (3.0, 100, 100.0, 7.0),
         (45.0, 200, 256.0, None)]),
    # never completes: the fourth service never fits, so the lane samples
    # to the horizon
    (3, [(0.0, 800, 512.0, None)] * 3 + [(5.0, 800, 512.0, None)]),
    (4, []),                                               # no pod
    # a POD_DONE trigger at te == 20 bound at cycle 0: the corner in which
    # it still precedes SAMPLE(20)
    (3, [(0.0, 300, 512.0, 20.0), (0.0, 200, 256.0, 10.0),
         (3.0, 100, 100.0, 7.0)]),
    # float residues: 0.1 and 0.2 MB leave their node a memory of 2**-55
    # MB (a ratio of 2**-66.8), which the samples see until D completes
    (3, [(0.0, 100, 0.1, 30.0), (0.0, 100, 0.2, 50.0),
         (1.0, 300, 500.0, 200.0)]),
    # a node sum just above a rounding midpoint, 1 + 2**-53 + 2**-200: a
    # ratio below the limbs' reach, so the state falls back to math.fsum
    (3, [(0.0, 600, 3584.0, 40.0), (0.0, 600, TINY, 40.0),
         (0.0, 600, TINY * 2.0 ** -147, 40.0)]),
]
FALLBACK_LANE = len(CORNER_LANES) - 1


def _corner_trace(seed, _n_jobs):
    _nodes, pods = CORNER_LANES[seed % len(CORNER_LANES)]
    specs = [PodSpec(f"p{i}", PodKind.SERVICE if dur is None
                     else PodKind.BATCH, Resources(cpu, mem),
                     duration_s=dur or 0.0, moveable=dur is None)
             for i, (_t, cpu, mem, dur) in enumerate(pods)]
    return TraceStore(specs, np.arange(len(pods)), [p[0] for p in pods],
                      duration_s=[p[3] or 0.0 for p in pods],
                      name="rebuild-corners")


@pytest.fixture(scope="module")
def corner_cells():
    register("rebuild-corners", _corner_trace, overwrite=True)
    return [CellSpec(scenario="rebuild-corners", scheduler=sched,
                     autoscaler="void", rescheduler="void", seed=i,
                     engine="array", initial_workers=nodes)
            for sched in ("best-fit", "first-fit")
            for i, (nodes, _pods) in enumerate(CORNER_LANES)]


def _solo_outputs(cell):
    """One lane's program outputs, run alone, and its pod count."""
    trace = _get_trace(cell.scenario, cell.seed, cell.n_jobs)
    if trace.n == 0:
        return None, 0
    out = ml.run_lane_batch(ml.stack_lanes(
        [_lane_of(trace, cell.initial_workers)], cell.scheduler))
    return {k: v[0] for k, v in out.items() if v.ndim}, trace.n


def _replayed_states(o, n):
    """Sample states of one lane, by the per-lane pointer walk the bucket
    rebuild replaced: events in (time, kind, bind_seq) order, each seen
    from its first visible grid point; a new state wherever the walk
    stops at an unseen event, up to the last sample recorded."""
    if o is None:
        return 1                  # no pod: one flat state to the horizon
    sp = 20.0
    bind_t = o["bind_cycle"][:n] * 10.0
    done_t, seq = o["done_t"][:n], o["bind_seq"][:n]
    done = np.nonzero(o["done_committed"][:n])[0]
    ev = []
    for i in done:
        td, tc = done_t[i], bind_t[i]
        early = td % sp == 0 and (tc < td - sp or (tc == 0 and td == sp))
        ev.append((td, 0, seq[i], td if early else
                   (math.floor(td / sp) + 1) * sp))
    for i in np.nonzero(o["bound"][:n])[0]:
        tb = bind_t[i]
        ev.append((tb, 1, seq[i], 0.0 if tb == 0 else
                   (math.floor(tb / sp) + 1) * sp))
    sv = [e[3] for e in sorted(ev)]
    te = float(o["done_time"])
    if not o["completed"]:
        last_s = ml.HORIZON_S
    elif te % sp == 0 and te > 0 and not o["done_is_cycle"]:
        trig = max(done, key=lambda i: (done_t[i], seq[i]))
        tc = bind_t[trig]
        first = tc < te - sp or (tc == 0 and te == sp)
        last_s = te - sp if first else te
    elif te % sp == 0 and te > 0:
        last_s = te
    else:
        last_s = (math.ceil(te / sp) - 1) * sp
    states, ptr, s = 0, 0, 0.0
    while s <= last_s:
        while ptr < len(sv) and sv[ptr] <= s:
            ptr += 1
        states += 1
        s = sv[ptr] if ptr < len(sv) and sv[ptr] <= last_s else last_s + sp
    return states


def _spy_ratios(monkeypatch):
    """The node ratios the rebuild splits into limbs, recorded."""
    seen = []
    limbs = ev._limbs

    def spy(r, out=None):
        seen.append(r.copy())
        return limbs(r, out=out)
    monkeypatch.setattr(ev, "_limbs", spy)
    return seen


def _smallest_ratio(seen):
    r = np.abs(np.concatenate(seen))
    return r[r > 0].min()


class TestBucketRebuild:
    def test_corner_rows_equal_serial(self, corner_cells, monkeypatch):
        """One call over every corner lane under two schedulers, two fleet
        sizes in one bucket and 3- and 4-pod lanes in one pod pad: rows
        equal the serial ``run_cell`` rows field by field, and the lanes
        reach the corners they were built for, float residues among them."""
        seen = _spy_ratios(monkeypatch)
        rows = run_cells(corner_cells, workers="lanes")
        rec = lane_calls(1)[0]
        serial = run_cells(corner_cells, workers=1)
        for s, l in zip(serial, rows):
            for field in _RESULT_FIELDS:
                assert s[field] == l[field], (s["label"], field)
                assert type(s[field]) is type(l[field]), (s["label"], field)
        assert (rec["lanes"], rec["buckets"]) == (16, 2)
        assert 0 < _smallest_ratio(seen) < 2.0 ** -60
        got = [_solo_outputs(c)[0] for c in corner_cells[:len(CORNER_LANES)]]
        assert list(got[0]["bind_cycle"][:3]) == [0, 0, 3]
        assert list(got[0]["done_t"][:3]) == [20.0, 40.0, 40.0]
        ends = [(bool(o["completed"]), bool(o["done_is_cycle"]),
                 float(o["done_time"])) for o in got if o is not None]
        assert ends == [(True, False, 40.0), (True, False, 60.0),
                        (True, True, 100.0), (True, True, 50.0),
                        (False, False, ml.HORIZON_S), (True, False, 20.0),
                        (True, False, 210.0), (True, False, 40.0)]
        assert rows[4]["avg_ram_ratio"] > 0 and rows[5]["max_nodes"] == 4

    def test_sample_states_count_the_replay(self, corner_cells):
        """``sample_states`` is the per-lane walk's state count summed
        over the lanes of the call, zero-pod lanes included."""
        base = dict(autoscaler="void", rescheduler="void", engine="array")
        cells = corner_cells + [
            CellSpec(scenario="heavy-tail", scheduler="best-fit", seed=s,
                     n_jobs=40, initial_workers=nw, **base)
            for s, nw in ((0, 4), (1, 3), (2, 12))]
        run_cells_lanes(cells)
        got = lane_calls(1)[0]["counts"]["sample_states"]
        assert got == sum(_replayed_states(*_solo_outputs(c)) for c in cells)
        assert got >= len(cells)    # every lane here records a sample

    def test_unproven_sums_fall_back_and_are_counted(self, corner_cells):
        """Each state whose node sums the limbs cannot hold goes to
        ``math.fsum`` over its lane's nodes and is counted in
        ``node_sum_fallbacks``, in a bucket of any width; the rows still
        equal the serial one."""
        cell = corner_cells[FALLBACK_LANE]
        cells = [dataclasses.replace(cell, seed=cell.seed
                                     + k * len(CORNER_LANES))
                 for k in range(8)]
        rows = run_cells_lanes(cells)
        assert lane_calls(1)[0]["counts"]["node_sum_fallbacks"] >= len(cells)
        want = run_cells(cells[:1], workers=1)[0]
        for row in rows:
            for field in _RESULT_FIELDS:
                assert row[field] == want[field], field
        assert want["avg_ram_ratio"] != (1.0 + TINY / 3584.0) / 3

    @pytest.mark.parametrize("lanes_per_block", [1, 3])
    def test_blocks_of_lanes_rebuild_the_same_rows(self, corner_cells,
                                                   monkeypatch,
                                                   lanes_per_block):
        """A bucket rebuilt in blocks of lanes, one lane or a width that
        does not divide the bucket, gives the rows and counts of one
        block."""
        whole = run_cells_lanes(corner_cells)
        counts = lane_calls(1)[0]["counts"]
        monkeypatch.setattr(ev, "_REBUILD_BLOCK", 4 * lanes_per_block)
        blocked = run_cells_lanes(corner_cells)
        got = lane_calls(1)[0]["counts"]
        for a, b in zip(whole, blocked):
            assert {k: v for k, v in a.items() if k != "wall_s"} == {
                k: v for k, v in b.items() if k != "wall_s"}
        keys = ("sample_states", "rebuild_fallbacks", "node_sum_fallbacks")
        assert [got[k] for k in keys] == [counts[k] for k in keys]


def _sum_cases(name, rng, n=2048):
    """(terms, columns) arrays of one family of float64 sums."""
    if name == "random":            # both signs, exponents far apart
        return rng.standard_normal((19, n)) * 2.0 ** rng.integers(
            -80, 80, (19, n))
    if name == "ratios":            # node usage over allocatable
        used = rng.integers(0, 40, (19, n)) * rng.choice(
            [128.0, 307.2, 512.0, 1433.6], (19, n))
        return used / rng.choice([3584.0, 940.0, 1.0], n)
    a = rng.random(n) + 1.0
    half = np.spacing(a) / 2
    if name == "ties":              # exactly on a midpoint: ties to even
        x = np.stack([a, half / 2, half / 2, np.zeros(n)])
    else:                           # within 2**-k of a midpoint, k to 200
        tiny = half * 2.0 ** -rng.integers(1, 200, n) * rng.choice(
            [1.0, -1.0], n)
        x = np.stack([a, half, tiny, np.zeros(n)])
    return rng.permuted(x, axis=0)


class TestExactSums:
    @pytest.mark.parametrize("family", ["random", "ratios", "ties",
                                        "near-midpoint"])
    def test_matches_fsum_bits(self, family):
        """Each column's sum is ``math.fsum`` of the column bit for bit;
        only columns it cannot prove fall back."""
        x = _sum_cases(family, np.random.default_rng(11))
        got, fallbacks = _exact_sums(x)
        want = np.array([math.fsum(c) for c in x.T.tolist()])
        assert np.array_equal(got.view(np.int64), want.view(np.int64))
        if family == "near-midpoint":
            assert 0 < fallbacks < x.shape[1]
        else:
            assert fallbacks == 0

    def test_narrow_inputs_sum_by_fsum(self):
        """Narrow inputs (a bucket of a few lanes) take the same passes:
        each column is ``math.fsum``'s, and the columns that fall back to
        it are counted."""
        x = _sum_cases("near-midpoint", np.random.default_rng(12), n=64)
        got, fallbacks = _exact_sums(x)
        want = np.array([math.fsum(c) for c in x.T.tolist()])
        assert np.array_equal(got.view(np.int64), want.view(np.int64))
        assert 0 < fallbacks < x.shape[1]
        one, _ = _exact_sums(x[:, :1])
        assert one.view(np.int64)[0] == want.view(np.int64)[0]
        assert _exact_sums(np.zeros((3, 0)))[0].shape == (0,)

    def test_blocks_cover_every_column(self):
        """Sums run in blocks of columns: a width that is not a multiple
        of the block is summed whole."""
        x = _sum_cases("random", np.random.default_rng(4), n=40000)
        got, _ = _exact_sums(x)
        assert np.array_equal(got, [math.fsum(c) for c in x.T.tolist()])

    def test_split_is_exact_under_sample_counts(self):
        """``m * hi`` and ``m * lo`` are exact products, so their exact sum
        is ``m * v`` for any sample count up to the horizon's."""
        from fractions import Fraction
        rng = np.random.default_rng(9)
        v = rng.random(512) * 2.0 ** rng.integers(-30, 5, 512)
        m = rng.integers(1, ml.MAX_CYCLES + 2, 512)
        hi, lo = _split(v)
        assert np.array_equal(hi + lo, v)
        for vi, mi, h, l in zip(v, m, m * hi, m * lo):
            assert Fraction(h) + Fraction(l) == Fraction(vi) * int(mi)


def _node_ratio_cases(name, rng, n=512):
    """(nodes, states) arrays of node ratios, as a sample sums them: 1 to
    64 nodes a state (the rest zero)."""
    nodes = np.arange(64)[:, None] < rng.integers(1, 65, n)
    if name == "random":            # pod requests summed over allocatable
        used = rng.integers(0, 35840, (64, n)) * 0.1
        return np.where(nodes, used / 3584.0, 0.0)
    if name == "ones":              # full nodes
        return np.where(nodes, 1.0, 0.0)
    if name == "zeros":             # an empty fleet
        return np.zeros((64, n))
    if name == "residues":          # memory left over 3,584 MB after pods go
        res = 2.0 ** -rng.integers(40, 61, (64, n)) * rng.random((64, n))
        used = np.where(rng.random((64, n)) < 0.5,
                        rng.integers(0, 35840, (64, n)) * 0.1,
                        res * rng.choice([1.0, -1.0], (64, n)))
        return np.where(nodes, used / 3584.0, 0.0)
    # a rounding midpoint, exact or one ulp either side of it, spread over
    # the nodes: a + half-ulp(a), less d on one node and plus d on another
    a = rng.random(n) / 2 + 0.5
    half = np.spacing(a) / 2
    x = np.zeros((64, n))
    x[0] = a
    x[1] = {"ties": half, "below": np.nextafter(half, 0.0),
            "above": np.nextafter(half, 1.0)}[name]
    d = rng.integers(1, 2 ** 20, n) * 2.0 ** -40
    x[0] -= d
    x[2] = d
    return rng.permuted(x, axis=0)


class TestLimbSums:
    @pytest.mark.parametrize("family", ["random", "ones", "zeros",
                                        "residues", "ties", "below",
                                        "above"])
    def test_matches_fsum_bits(self, family):
        """Each state's limb sums, rounded once, are ``math.fsum`` of its
        node ratios bit for bit; every ratio here is held."""
        x = _node_ratio_cases(family, np.random.default_rng(21))
        limbs, held = _limbs(x.ravel())
        assert held.all()
        got = _round_limbs(*limbs.reshape(3, *x.shape).sum(axis=1))
        want = np.array([math.fsum(c) for c in x.T.tolist()])
        assert np.array_equal(got.view(np.int64), want.view(np.int64))

    def test_limbs_are_the_ratio(self):
        """Held limbs are the ratio exactly, with its sign, at every
        magnitude from 2**-86 to below 2**11; a ratio past 2**11, or one
        with bits below 2**-138, is not held."""
        from fractions import Fraction
        rng = np.random.default_rng(22)
        r = (rng.random(2048) + 1.0) * 2.0 ** rng.integers(-86, 11, 2048)
        r *= rng.choice([1.0, -1.0], r.size)
        r = np.concatenate([r, [0.0, 1.0, -1.0, 2.0 ** -86,
                                np.nextafter(2.0 ** 11, 0.0)]])
        limbs, held = _limbs(r)
        assert held.all()
        for v, (a, b, c) in zip(r, limbs.T.tolist()):
            assert Fraction(v) == (Fraction(a, 2 ** 32) + Fraction(b, 2 ** 85)
                                   + Fraction(c, 2 ** 138))
        out = np.array([(1.0 + 2.0 ** -52) * 2.0 ** -87, 2.0 ** -90 / 3,
                        2.0 ** -200, 2.0 ** 11, -1e30])
        assert not _limbs(out)[1].any()

    def test_planted_ratio_below_the_limbs_is_not_held(self):
        """A state holding a ratio below 2**-86 is flagged, so the rebuild
        sums it by ``math.fsum`` (and counts it); its other states are
        held."""
        x = _node_ratio_cases("residues", np.random.default_rng(23), n=8)
        x[5, 3] = 2.0 ** -200
        _, held = _limbs(x.ravel())
        assert (~held.reshape(x.shape)).any(axis=0).tolist() == [
            i == 3 for i in range(8)]


# -- autoscaled lanes: the binding autoscaler and Alg. 6 ---------------------

#: Hand-built autoscaled lanes, (static nodes, pods as in CORNER_LANES).
FLEET_LANES = [
    # out, in, out again: B waits for node-1 (launched at 10, READY at
    # 60), which Alg. 6 removes once B is done (160); D launches node-2 at
    # 210, but C's end at 250 frees node-0 first: D binds there, and the
    # empty node-2 goes at 260; D completes the lane at 300
    (1, [(0.0, 900, 100.0, 100.0), (1.0, 900, 100.0, 100.0),
         (200.0, 900, 100.0, 50.0), (201.0, 900, 100.0, 50.0)]),
    # never past one node: everything fits the static worker
    (1, [(0.0, 100, 300.0, 60.0), (4.0, 200, 600.0, None),
         (9.0, 100, 300.0, 30.0)]),
    # step 2: the service S waits for node-1; once A leaves node-0 (100),
    # Alg. 6 moves S there and removes node-1; S binds again at 110, and
    # C completes the lane at 510
    (1, [(0.0, 800, 100.0, 100.0), (1.0, 300, 1000.0, None),
         (3.0, 100, 100.0, 500.0)]),
    # step 3: S and the batch pod B share node-1 (B absorbed by its
    # tracker); at 100 S moves to node-0 and node-1 is tainted until B
    # completes the lane at 360
    (1, [(0.0, 800, 100.0, 100.0), (1.0, 300, 1000.0, None),
         (2.0, 300, 100.0, 300.0)]),
    # B and C wait for node-1 and bind when it joins (60); they leave it
    # at 160 and 180 with a float residue in its memory, which counts in
    # the samples until Alg. 6 removes the empty node
    (1, [(0.0, 900, 100.0, 300.0), (1.0, 300, 100.1, 100.0),
         (2.0, 300, 200.2, 120.0)]),
    # step 3 with fractional memory: B leaves node-1 holding S's 1000.3
    # MB plus a residue, then Alg. 6 evicts S and retires node-1
    (1, [(0.0, 800, 100.0, 100.0), (1.0, 300, 1000.3, None),
         (2.0, 300, 100.7, 50.0)]),
]


def _fleet_trace(seed, _n_jobs):
    _nodes, pods = FLEET_LANES[seed % len(FLEET_LANES)]
    specs = [PodSpec(f"p{i}", PodKind.SERVICE if dur is None
                     else PodKind.BATCH, Resources(cpu, mem),
                     duration_s=dur or 0.0, moveable=dur is None)
             for i, (_t, cpu, mem, dur) in enumerate(pods)]
    return TraceStore(specs, np.arange(len(pods)), [p[0] for p in pods],
                      duration_s=[p[3] or 0.0 for p in pods],
                      name="fleet-corners")


def _fleet_cells(sched="best-fit", **kw):
    register("fleet-corners", _fleet_trace, overwrite=True)
    return [CellSpec(scenario="fleet-corners", scheduler=sched,
                     autoscaler="binding", rescheduler="void", seed=i,
                     engine="array", initial_workers=nodes, **kw)
            for i, (nodes, _pods) in enumerate(FLEET_LANES)]


def _assert_rows_equal(serial, rows):
    assert [r["label"] for r in rows] == [r["label"] for r in serial]
    for s, l in zip(serial, rows):
        for field in _RESULT_FIELDS:
            assert s[field] == l[field], (s["label"], field)
            assert type(s[field]) is type(l[field]), (s["label"], field)
        assert (s["infeasible"], s["n_jobs"]) == (l["infeasible"],
                                                  l["n_jobs"])


def _no_serial_runs(monkeypatch):
    """Fail any cell that would run on the serial engine."""
    import repro.search.runner as runner

    def refuse(cell):
        raise AssertionError(f"{cell.label} ran serially")
    monkeypatch.setattr(runner, "run_cell", refuse)


class TestAutoscaledLanes:
    @pytest.mark.parametrize("scen,sched,n", [
        ("paper-bursty", "best-fit", 16), ("paper-slow", "best-fit", 8),
        ("paper-bursty", "worst-fit", 4), ("paper-mixed", "first-fit", 4)])
    def test_rows_bitwise_equal_serial(self, scen, sched, n, monkeypatch):
        """Seeded paper traces on the paper's chain from one worker: every
        lane row equals the serial ``run_cell`` row, field by field, and
        no cell ran serially."""
        cells = [CellSpec(scenario=scen, scheduler=sched,
                          autoscaler="binding", rescheduler="void",
                          seed=s, initial_workers=1) for s in range(n)]
        serial = run_cells(cells, workers=1)
        with monkeypatch.context() as m:
            _no_serial_runs(m)
            rows = run_cells(cells, workers="lanes")
        rec = lane_calls(1)[0]
        assert (rec["lanes"], rec["counts"]["lane_fallbacks"]) == (n, 0)
        _assert_rows_equal(serial, rows)
        assert sum(r["evictions"] for r in serial) > 0
        assert sum(r["scale_ins"] for r in serial) > 0
        assert max(r["max_nodes"] for r in serial) > 4

    @pytest.mark.parametrize("sched", ["best-fit", "first-fit"])
    def test_hand_built_fleets_equal_serial(self, sched, monkeypatch):
        """Out, in and out again; a fleet that never grows; services
        drained and a node tainted by Alg. 6: rows equal serial."""
        cells = _fleet_cells(sched)
        serial = run_cells(cells, workers=1)
        with monkeypatch.context() as m:
            _no_serial_runs(m)
            seen = _spy_ratios(m)
            rows = run_cells(cells, workers="lanes")
        _assert_rows_equal(serial, rows)
        assert 0 < _smallest_ratio(seen) < 2.0 ** -50
        out_in_out, one_node, drained, tainted, residue, retired = serial
        assert (residue["scale_ins"], residue["max_nodes"]) == (1, 2)
        assert (retired["evictions"], retired["scale_ins"]) == (1, 2)
        assert (out_in_out["scale_ins"], out_in_out["max_nodes"]) == (2, 2)
        assert out_in_out["node_seconds"] == 300 + 150 + 50
        assert (one_node["max_nodes"], one_node["scale_outs"]) == (1, 0)
        assert (drained["evictions"], drained["scale_ins"],
                drained["duration_s"]) == (1, 1, 510.0)
        assert (tainted["evictions"], tainted["scale_ins"],
                tainted["duration_s"]) == (1, 1, 360.0)

    def test_pod_no_node_holds_is_infeasible(self):
        """A pod larger than the template: the serial short-circuit row,
        beside an autoscaled lane that runs."""
        cells = [CellSpec(scenario="paper-bursty", scheduler="best-fit",
                          autoscaler="binding", rescheduler="void", seed=s,
                          initial_workers=1, template_name=tpl)
                 for s, tpl in ((0, "m2.tiny"), (1, None))]
        serial = run_cells(cells, workers=1)
        rows = run_cells(cells, workers="lanes")
        _assert_rows_equal(serial, rows)
        assert rows[0]["infeasible"] and not rows[1]["infeasible"]
        assert lane_calls(1)[0]["lanes"] == 1

    def test_lane_past_its_records_runs_serially(self, monkeypatch):
        """Node records too few for a lane's launches: the lane stops on
        the device and its row comes from the serial engine, counted."""
        cells = [CellSpec(scenario="paper-bursty", scheduler="best-fit",
                          autoscaler="binding", rescheduler="void", seed=s,
                          initial_workers=1) for s in range(3)]
        serial = run_cells(cells, workers=1)
        monkeypatch.setattr(ev, "_node_records", lambda cell, trace: 4)
        rows = run_cells(cells, workers="lanes")
        _assert_rows_equal(serial, rows)
        assert lane_calls(1)[0]["counts"]["lane_fallbacks"] == 3

    def test_fleet_counters_on_one_lane(self):
        """The out-in-out lane by hand: 31 cycles (t = 0 .. 300), node-0
        live in each, node-1 in cycles 2-16 (launched in cycle 1, removed
        by Alg. 6 in cycle 16), node-2 in cycles 22-26: 51 live node
        cycles; two launches, two removals."""
        trace = _fleet_trace(0, None)
        lane = {**_lane_of(trace, 1), "boot_cycles": 5}
        out = ml.run_lane_batch(ml.stack_lanes([lane], "best-fit",
                                               node_pad=8))
        assert int(out["n_cycles"]) == 31
        assert (int(out["scale_out_nodes"]), int(out["scale_in_nodes"]),
                int(out["active_node_cycles"])) == (2, 2, 51)
        assert int(out["scale_ins"][0]) == 2 and not out["overflow"][0]
        seq_of, index_of = ml.node_layout(8)
        assert list(out["launch_k"][0, index_of[:3]]) == [0, 1, 21]
        assert list(out["gone_k"][0, index_of[:3]]) == [-1, 16, 26]
        assert list(out["gone_step"][0, index_of[:3]]) == [0, 1, 1]
        assert list(out["nstate"][0, index_of[:3]]) == [
            ml.NODE_READY, ml.NODE_GONE, ml.NODE_GONE]
        assert sorted(seq_of) == list(range(8))

    def test_node_layout_orders_ids_as_strings(self):
        seq_of, index_of = ml.node_layout(16)
        names = [f"node-{s}" for s in seq_of]
        assert names == sorted(names)
        assert list(index_of[seq_of]) == list(range(16))
        assert seq_of[:3].tolist() == [0, 1, 10]


# Hand-built lanes for Alg. 3 (node-0 .. node-<n-1> static m2.small
# workers, 3584 MB): (static nodes, pods as (arrival, cpu, mem, batch
# duration or None for a moveable service)).
RESCHED_LANES = [
    # node-0 holds S1 and S2 (383.6 MB free), node-1 and node-2 one batch
    # pod each.  P blocks at 10: Alg. 3 skips S1 (no room elsewhere) and
    # moves S2's 1200.3 MB to node-2's shadow, enough for P's 1016.9 MB
    # shortfall; S2 is evicted, and Q, later in the same wave, binds into
    # the room it left.  Node-0's usage after the cycle depends on that
    # order ((u - S2) + Q != (u + Q) - S2 in float64).
    (3, [(0.0, 100, 2000.1, None), (0.0, 100, 1200.3, None),
         (0.0, 100, 2400.7, 600.0), (0.0, 100, 2300.2, 600.0),
         (1.0, 100, 1400.5, 300.0), (2.0, 100, 1500.9, 300.0)]),
    # node-0 and node-1 hold the same two services each, 584 MB free: the
    # tie between the victims goes to node-1, the name that sorts last,
    # whose 800 MB service moves to node-2 for P.
    (3, [(0.0, 100, 2200.0, None), (0.0, 100, 800.0, None),
         (0.0, 100, 2200.0, None), (0.0, 100, 800.0, None),
         (0.0, 100, 2300.0, 600.0), (1.0, 100, 1350.0, 300.0)]),
]
MID_WAVE, TIED = range(len(RESCHED_LANES))


def _resched_trace(seed, _n_jobs):
    _nodes, pods = RESCHED_LANES[seed % len(RESCHED_LANES)]
    specs = [PodSpec(f"p{i}", PodKind.SERVICE if dur is None
                     else PodKind.BATCH, Resources(cpu, mem),
                     duration_s=dur or 0.0, moveable=dur is None)
             for i, (_t, cpu, mem, dur) in enumerate(pods)]
    return TraceStore(specs, np.arange(len(pods)), [p[0] for p in pods],
                      duration_s=[p[3] or 0.0 for p in pods],
                      name="resched-corners")


def _resched_cells():
    """The hand-built lanes as cells with no age gate."""
    register("resched-corners", _resched_trace, overwrite=True)
    return [CellSpec(scenario="resched-corners", scheduler="best-fit",
                     autoscaler="binding", rescheduler="non-binding",
                     seed=i, engine="array", initial_workers=nodes,
                     max_pod_age_s=0.0)
            for i, (nodes, _pods) in enumerate(RESCHED_LANES)]


def _resched_lane(i, age=0.0):
    trace = _resched_trace(i, None)
    return {**_lane_of(trace, RESCHED_LANES[i][0]), "boot_cycles": 5,
            "max_pod_age_s": age}


class TestReschedLanes:
    """Alg. 3, the non-binding rescheduler, in the autoscaled lane
    program: rows equal the serial engine's, which is the reference."""

    @pytest.mark.parametrize("scen", ["paper-bursty", "paper-slow"])
    @pytest.mark.parametrize("block", range(13))
    def test_rows_bitwise_equal_serial(self, scen, block, monkeypatch):
        """Eight seeded paper traces a call on the paper's chain with its
        60 s age gate (208 traces over the blocks): every lane row equals
        the serial ``run_cell`` row, field by field, and no cell ran
        serially."""
        cells = [CellSpec(scenario=scen, scheduler="best-fit",
                          autoscaler="binding", rescheduler="non-binding",
                          seed=8 * block + s, initial_workers=1)
                 for s in range(8)]
        serial = run_cells(cells, workers=1)
        with monkeypatch.context() as m:
            _no_serial_runs(m)
            rows = run_cells(cells, workers="lanes")
        rec = lane_calls(1)[0]
        assert (rec["lanes"], rec["counts"]["lane_fallbacks"]) == (8, 0)
        assert rec["counts"]["resched_plans"] > 0
        _assert_rows_equal(serial, rows)

    def test_rescheduled_lanes_equal_serial(self, monkeypatch):
        """Seeded bursty traces in which Alg. 3 carries out plans, with no
        age gate and from three workers, and other schedulers in the
        wave: rows equal serial."""
        cells = [CellSpec(scenario="paper-bursty", scheduler=sched,
                          autoscaler="binding", rescheduler="non-binding",
                          seed=s, initial_workers=3, max_pod_age_s=0.0)
                 for s in range(4) for sched in ("best-fit", "worst-fit")]
        serial = run_cells(cells, workers=1)
        with monkeypatch.context() as m:
            _no_serial_runs(m)
            rows = run_cells(cells, workers="lanes")
        _assert_rows_equal(serial, rows)
        assert sum(lane_calls(2)[i]["counts"]["resched_done"]
                   for i in range(2)) > 0

    def test_mid_wave_eviction_then_bind_in_the_freed_room(self,
                                                         monkeypatch):
        """The evicted service re-pends at 10 and sits out that wave; the
        later pod Q binds into the room it freed in the same cycle, after
        the eviction in the cycle's sequence; rows equal serial."""
        lane = _resched_lane(MID_WAVE)
        out = ml.run_lane_batch(ml.stack_lanes([lane], "best-fit",
                                               node_pad=8, resched=True))
        _seq, index_of = ml.node_layout(8)
        assert int(out["ev_n"][0]) == 1 and int(out["resched_done"]) == 1
        s2, q = 1, 5
        assert (int(out["ev_pod"][0, 0]), int(out["ev_node"][0, 0]),
                int(out["ev_cycle"][0, 0])) == (s2, index_of[0], 1)
        assert (int(out["bind_node"][0, q]), int(out["bind_cycle"][0, q])
                ) == (index_of[0], 1)
        assert int(out["ev_seq"][0, 0]) < int(out["bind_seq"][0, q])
        # The evicted service waits for the next cycle.
        assert int(out["bind_cycle"][0, s2]) == 2
        cells = _resched_cells()[MID_WAVE:MID_WAVE + 1]
        serial = run_cells(cells, workers=1)
        with monkeypatch.context() as m:
            _no_serial_runs(m)
            rows = run_cells(cells, workers="lanes")
        _assert_rows_equal(serial, rows)
        assert serial[0]["evictions"] == 1

    def test_tied_victims_go_to_the_last_name(self, monkeypatch):
        """Two victims with the same free memory: Alg. 3 tries node-1
        first (descending by free memory, then name), so its service
        moves; rows equal serial."""
        lane = _resched_lane(TIED)
        out = ml.run_lane_batch(ml.stack_lanes([lane], "best-fit",
                                               node_pad=8, resched=True))
        _seq, index_of = ml.node_layout(8)
        assert int(out["resched_done"]) >= 1
        assert (int(out["ev_pod"][0, 0]), int(out["ev_node"][0, 0])) == (
            3, index_of[1])
        cells = _resched_cells()[TIED:TIED + 1]
        serial = run_cells(cells, workers=1)
        with monkeypatch.context() as m:
            _no_serial_runs(m)
            rows = run_cells(cells, workers="lanes")
        _assert_rows_equal(serial, rows)

    def test_per_lane_ages_share_one_bucket(self, monkeypatch):
        """Age gates of 0, 60 and 240 s on the same traces run in one
        bucket, one program, each lane with its own gate; the gate moves
        the rows, and each equals its serial row."""
        cells = [CellSpec(scenario="paper-bursty", scheduler="best-fit",
                          autoscaler="binding", rescheduler="non-binding",
                          seed=s, initial_workers=1, max_pod_age_s=age)
                 for s in range(2) for age in (0.0, 60.0, 240.0)]
        serial = run_cells(cells, workers=1)
        with monkeypatch.context() as m:
            _no_serial_runs(m)
            rows = run_cells(cells, workers="lanes")
        _assert_rows_equal(serial, rows)
        rec = lane_calls(1)[0]
        assert (rec["lanes"], rec["buckets"]) == (len(cells), 1)
        assert rec["counts"]["resched_waits"] > 0
        for s in range(2):
            by_age = [{f: r[f] for f in _RESULT_FIELDS}
                      for r in rows[3 * s:3 * s + 3]]
            assert by_age[0] != by_age[1] != by_age[2]

    def test_lane_past_its_records_runs_serially(self, monkeypatch):
        """Node records too few for a lane's launches: the lane stops on
        the device and its row comes from the serial engine, counted."""
        cells = [CellSpec(scenario="paper-bursty", scheduler="best-fit",
                          autoscaler="binding", rescheduler="non-binding",
                          seed=s, initial_workers=1) for s in range(3)]
        serial = run_cells(cells, workers=1)
        monkeypatch.setattr(ev, "_node_records", lambda cell, trace: 4)
        rows = run_cells(cells, workers="lanes")
        _assert_rows_equal(serial, rows)
        assert lane_calls(1)[0]["counts"]["lane_fallbacks"] == 3

    def test_resched_counters_on_one_lane(self):
        """With no age gate every blocked pod plans: plans are the failed
        plans (the scale-out requests) and the one carried out; with a
        60 s gate the first blocked calls wait.  Only this program
        returns the counts."""
        run = {age: ml.run_lane_batch(ml.stack_lanes(
            [_resched_lane(MID_WAVE, age)], "best-fit", node_pad=8,
            resched=True)) for age in (0.0, 60.0)}
        out = run[0.0]
        assert int(out["resched_waits"]) == 0
        assert (int(out["resched_done"]), int(out["resched_evictions"])
                ) == (1, 1)
        assert int(out["resched_plans"]) == int(out["scale_outs"][0]) + 1
        assert int(out["resched_steps"]) >= 2
        assert int(run[60.0]["resched_waits"]) > 0
        plain = ml.run_lane_batch(ml.stack_lanes(
            [_resched_lane(MID_WAVE)], "best-fit", node_pad=8))
        assert not set(ml.RESCHED_COUNTERS + ("ev_seq",)) & set(plain)
