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
program does its float arithmetic with.
"""
import math

import numpy as np
import pytest

jax = pytest.importorskip("jax")   # lane engine is JAX-gated by design

from repro.cloud.adapter import M2_SMALL
from repro.core import build_simulation, reset_id_counters
from repro.manyworld import lanes as ml
from repro.manyworld.evaluator import (lane_calls, lane_eligible,
                                      run_cells_lanes)
from repro.scenarios.trace import KIND_BATCH
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
            # ineligible: binding autoscaler -> serial fallback
            CellSpec(scenario="heavy-tail", scheduler="best-fit",
                     autoscaler="binding", seed=0, n_jobs=16,
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
        assert not lane_eligible(CellSpec(**{**base, "autoscaler": "binding"}))
        assert not lane_eligible(CellSpec(**{**base, "rescheduler": "non-binding"}))
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
