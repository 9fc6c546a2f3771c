"""Golden parity: the array-backed wave-placement engine must be bit-for-bit
identical to the seed per-pod object-scan engine.

Five layers:

* **End-to-end** — every fig3 policy combo (3 reschedulers x 2 autoscalers),
  the fig4 k8s-default static baseline, and the scheduler ablation produce
  *identical* ``ExperimentResult`` dicts (cost, duration_s, evictions,
  scale_outs, scale_ins, max_nodes, every sampled ratio) under
  ``engine="array"`` and ``engine="object"``.
* **Bind-sequence property** — on randomized clusters/workloads/policy
  combos, wave placement produces the *identical bind sequence* (pod,
  incarnation, node, time — in order) the per-pod loop produces, not just
  identical aggregates.
* **Mirror property** — random bind/unbind/add/remove/taint sequences keep
  the SoA mirror consistent with the object model
  (``check_invariants(deep=True)`` cross-verifies every mirrored field —
  including the incremental Table-5 sampling aggregates against a
  from-scratch scan), without needing hypothesis.
* **Metrics parity** — the incremental sampler (dirty-tracked aggregate
  columns + exact fsum rounding) produces every 20 s sample bit-identical
  to the seed per-node ``fmean`` scan, on curated and randomized runs.
* **Run-length parity** — the best-fit run-length continuation of the wave
  and the per-pod query every other policy takes bind the same pods, with
  the same node used-floats, as the object engine.
"""
import dataclasses
import math

import numpy as np
import pytest

from repro.core import (Arrival, Cluster, ExperimentSpec, Node, Pod, PodKind,
                        PodSpec, Resources, build_simulation, gi,
                        reset_id_counters, run_all_combos, run_experiment,
                        run_k8s_baseline)
from repro.core.failures import FailureInjector, StragglerInjector

COMBOS = [(r, a) for r in ("void", "binding", "non-binding")
          for a in ("non-binding", "binding")]


def _as_dict(result):
    return dataclasses.asdict(result)


def _run_pair(fn):
    """fn(engine) under identical id-counter state; returns (array, object).

    Auto-generated node ids ("node-<seq>") order *lexicographically*, so a
    run's tie-breaks depend on where the global counter starts (node-99 >
    node-100).  Parity runs must therefore start both engines from the same
    counter value — this is test isolation, not an engine difference."""
    reset_id_counters()
    arr = fn("array")
    reset_id_counters()
    obj = fn("object")
    return arr, obj


class TestResultParity:
    @pytest.mark.parametrize("workload", ["slow", "bursty", "mixed"])
    def test_fig3_combos_identical(self, workload):
        arr, obj = _run_pair(
            lambda eng: run_all_combos(workload, seed=0, engine=eng))
        for ra, ro in zip(arr, obj):
            assert _as_dict(ra) == _as_dict(ro), ra.combo()

    def test_fig4_k8s_baseline_identical(self):
        ka, ko = _run_pair(
            lambda eng: run_k8s_baseline("slow", seed=0, engine=eng))
        assert _as_dict(ka) == _as_dict(ko)

    @pytest.mark.parametrize("scheduler", ["best-fit", "first-fit",
                                           "worst-fit", "k8s-default"])
    def test_scheduler_ablation_identical(self, scheduler):
        ra, ro = _run_pair(lambda eng: run_experiment(ExperimentSpec(
            workload="mixed", scheduler=scheduler,
            rescheduler="non-binding", autoscaler="binding",
            seed=1, engine=eng)))
        assert _as_dict(ra) == _as_dict(ro)

    def test_table5_metrics_identical(self):
        """Table-5 utilization metrics come from the 20s sampler — parity on
        the sampled ratios, not just the headline cost numbers."""
        ra, ro = _run_pair(lambda eng: run_experiment(ExperimentSpec(
            workload="bursty", seed=2, rescheduler="non-binding",
            autoscaler="non-binding", engine=eng)))
        assert ra.avg_ram_ratio == ro.avg_ram_ratio
        assert ra.avg_cpu_ratio == ro.avg_cpu_ratio
        assert ra.avg_pods_per_node == ro.avg_pods_per_node
        assert ra.median_pending_s == ro.median_pending_s


class TestFig4Bisection:
    def test_bisection_matches_linear_scan(self):
        """The bisected fig4 baseline must pick the same minimum cluster
        (and therefore the same result row) as the seed linear scan."""
        fast = run_k8s_baseline("slow", seed=0, search="bisect")
        slow = run_k8s_baseline("slow", seed=0, search="linear")
        assert fast.max_nodes == slow.max_nodes
        assert _as_dict(fast) == _as_dict(slow)

    def test_unknown_search_rejected(self):
        with pytest.raises(ValueError):
            run_k8s_baseline("slow", search="exhaustive")


def _random_arrivals(rng, n):
    """A randomized trace mixing services (some moveable) and batch jobs of
    random sizes — deliberately *not* one of the curated paper workloads."""
    out = []
    t = 0.0
    for i in range(n):
        t += float(rng.exponential(20.0))
        if rng.integers(0, 3) == 0:
            spec = PodSpec(f"svc{i}", PodKind.SERVICE,
                           Resources(int(rng.choice([100, 200, 300])),
                                     gi(float(rng.choice([0.3, 0.6, 1.0])))),
                           moveable=bool(rng.integers(0, 2)))
        else:
            spec = PodSpec(f"job{i}", PodKind.BATCH,
                           Resources(int(rng.choice([100, 200, 400])),
                                     gi(float(rng.choice([0.3, 0.9, 1.4])))),
                           duration_s=float(rng.choice([60.0, 180.0, 400.0])))
        out.append(Arrival(t, spec))
    return out


class TestWaveBindSequenceParity:
    """The tentpole property: wave placement commits the *same bind sequence*
    the seed per-pod loop produces — same pods on the same nodes in the same
    order, including rebinds of evicted incarnations — on randomized
    clusters, workloads and policy combos."""

    @pytest.mark.parametrize("seed", range(14))
    def test_bind_sequences_identical(self, seed):
        def run(engine):
            reset_id_counters()
            rng = np.random.default_rng(seed)
            spec = ExperimentSpec(
                workload="rand",
                arrivals=_random_arrivals(rng, 80),
                scheduler=str(rng.choice(["best-fit", "first-fit",
                                          "worst-fit", "k8s-default"])),
                rescheduler=str(rng.choice(["void", "binding",
                                            "non-binding"])),
                autoscaler=str(rng.choice(["non-binding", "binding"])),
                initial_workers=int(rng.integers(1, 4)),
                seed=0, engine=engine)
            sim = build_simulation(spec)
            log = []
            inner = sim.cluster.on_bind

            def spy(pod):
                log.append((pod.uid, pod.incarnation, pod.node_id,
                            pod.bound_time))
                inner(pod)

            sim.cluster.on_bind = spy
            result = sim.run()
            return spec.scheduler, log, dataclasses.asdict(result)

        combo_a, wave_log, wave_result = run("array")
        combo_o, perpod_log, perpod_result = run("object")
        assert combo_a == combo_o          # same randomized policy combo
        assert wave_log, "randomized workload produced no bindings"
        assert wave_log == perpod_log
        assert wave_result == perpod_result


def _mk_pod(rng):
    moveable = bool(rng.integers(0, 2))
    kind = PodKind.SERVICE if moveable or rng.integers(0, 2) else PodKind.BATCH
    mem = float(rng.choice([0.3, 0.6, 0.9, 1.0, 1.4]))
    cpu = int(rng.choice([100, 200, 300]))
    spec = PodSpec("p", kind, Resources(cpu, gi(mem)),
                   duration_s=60.0 if kind == PodKind.BATCH else 0.0,
                   moveable=moveable and kind == PodKind.SERVICE)
    return Pod(spec=spec, submit_time=0.0)


class TestMirrorProperty:
    @pytest.mark.parametrize("seed", range(8))
    def test_random_mutation_sequences_keep_mirror_consistent(self, seed):
        rng = np.random.default_rng(seed)
        cluster = Cluster(use_arrays=True)
        bound = []
        t = 0.0
        n_added = 0
        for step in range(200):
            t += 1.0
            op = rng.integers(0, 6)
            if op == 0 or not cluster.nodes:         # add a node
                node = Node(allocatable=Resources(940, gi(3.5)),
                            node_id=f"s{seed}-n{n_added}",
                            autoscaled=bool(rng.integers(0, 2)))
                node.mark_ready(t)
                cluster.add_node(node)
                n_added += 1
            elif op == 1:                            # bind a fresh pod
                pod = _mk_pod(rng)
                fitting = [n for n in cluster.ready_nodes()
                           if n.fits(pod.requests)]
                if fitting:
                    node = fitting[int(rng.integers(0, len(fitting)))]
                    cluster.bind(pod, node, t)
                    bound.append(pod)
            elif op == 2 and bound:                  # unbind (evict)
                pod = bound.pop(int(rng.integers(0, len(bound))))
                cluster.unbind(pod, t)
            elif op == 3:                            # taint / untaint
                nodes = list(cluster.nodes.values())
                node = nodes[int(rng.integers(0, len(nodes)))]
                if node.state.value == "tainted":
                    node.untaint()
                else:
                    node.taint()
            elif op == 4:                            # remove an empty node
                empties = [n for n in cluster.nodes.values() if not n.pods]
                if empties:
                    cluster.remove_node(
                        empties[int(rng.integers(0, len(empties)))], t)
            elif op == 5 and bound:                  # complete a batch pod
                batch = [p for p in bound if p.is_batch]
                if batch:
                    pod = batch[int(rng.integers(0, len(batch)))]
                    bound.remove(pod)
                    cluster.complete(pod, t)
            cluster.check_invariants(deep=True)

    def test_incremental_used_matches_resum(self):
        """Node.used stays exact (cpu) / within float tolerance (mem) of a
        fresh re-sum across arbitrary add/remove interleavings."""
        rng = np.random.default_rng(7)
        node = Node(allocatable=Resources(10_000, gi(400.0)), node_id="big")
        node.mark_ready(0.0)
        resident = []
        for _ in range(300):
            if resident and rng.integers(0, 2):
                node.remove_pod(resident.pop(int(rng.integers(0, len(resident)))))
            else:
                pod = _mk_pod(rng)
                if node.fits(pod.requests):
                    node.add_pod(pod)
                    resident.append(pod)
            fresh_cpu = sum(p.requests.cpu_m for p in node.pods.values())
            fresh_mem = sum(p.requests.mem_mb for p in node.pods.values())
            assert node.used.cpu_m == fresh_cpu
            assert abs(node.used.mem_mb - fresh_mem) < 1e-6


class TestMetricsParity:
    """Tentpole: Table-5 sampling reads the mirror's incremental aggregate
    columns (O(dirty) maintenance + exact fsum rounding) and must stay
    bit-identical to the seed per-node scan — per *sample*, not just on the
    time-averaged headline numbers."""

    def _samples(self, engine, seed):
        reset_id_counters()
        rng = np.random.default_rng(seed)
        spec = ExperimentSpec(
            workload="rand",
            arrivals=_random_arrivals(rng, 60),
            scheduler=str(rng.choice(["best-fit", "first-fit",
                                      "worst-fit", "k8s-default"])),
            rescheduler=str(rng.choice(["void", "binding", "non-binding"])),
            autoscaler=str(rng.choice(["non-binding", "binding"])),
            initial_workers=int(rng.integers(1, 4)),
            seed=0, engine=engine)
        sim = build_simulation(spec)
        sim.run()
        return ([dataclasses.astuple(s) for s in sim.metrics.samples],
                sim.metrics.node_count_series)

    @pytest.mark.parametrize("seed", range(6))
    def test_sample_series_identical_randomized(self, seed):
        arr_samples, arr_counts = self._samples("array", seed)
        obj_samples, obj_counts = self._samples("object", seed)
        assert arr_samples, "run produced no samples"
        assert arr_samples == obj_samples
        assert arr_counts == obj_counts

    def test_totals_match_scratch_scan_under_mutation(self):
        """Direct aggregate unit: utilization_totals() after an arbitrary
        mutation sequence equals an exact from-scratch fsum of the per-node
        view, on both engines."""
        rng = np.random.default_rng(11)
        for use_arrays in (True, False):
            cluster = Cluster(use_arrays=use_arrays)
            bound = []
            t = 0.0
            for step in range(120):
                t += 1.0
                op = rng.integers(0, 6)
                if op == 0 or not cluster.nodes:
                    node = Node(allocatable=Resources(940, gi(3.5)),
                                node_id=f"mm{use_arrays}-{step}")
                    if rng.integers(0, 3):
                        node.mark_ready(t)   # else stays PROVISIONING
                    cluster.add_node(node)
                elif op == 1:
                    pod = _mk_pod(rng)
                    fitting = [n for n in cluster.ready_nodes()
                               if n.fits(pod.requests)]
                    if fitting:
                        cluster.bind(pod, fitting[0], t)
                        bound.append(pod)
                elif op == 2 and bound:
                    cluster.unbind(bound.pop(), t)
                elif op == 3:
                    nodes = list(cluster.nodes.values())
                    node = nodes[int(rng.integers(0, len(nodes)))]
                    node.taint() if rng.integers(0, 2) else node.untaint()
                elif op == 4:
                    empties = [n for n in cluster.nodes.values()
                               if not n.pods and n.state.value != "provisioning"]
                    if empties:
                        cluster.remove_node(empties[0], t)
                elif op == 5 and bound:
                    batch = [p for p in bound if p.is_batch]
                    if batch:
                        bound.remove(batch[0])
                        cluster.complete(batch[0], t)
                n, ram_sum, cpu_sum, ppn_sum = cluster.utilization_totals()
                n2, ram, cpu, ppn = cluster.utilization_view()
                assert n == n2
                assert ram_sum == math.fsum(ram)
                assert cpu_sum == math.fsum(cpu)
                assert ppn_sum == sum(ppn)

    def test_empty_cluster_sample_recorded(self):
        """Satellite regression: the (now, 0) point must land in
        node_count_series, and non-empty points record the *sampled* node
        count (READY|TAINTED), not len(cluster.nodes)."""
        from repro.core.metrics import MetricsCollector
        cluster = Cluster(use_arrays=True)
        mc = MetricsCollector()
        mc.sample(cluster, 0.0)
        assert mc.node_count_series == [(0.0, 0)]
        assert mc.samples[0].n_nodes == 0
        ready = Node(allocatable=Resources(940, gi(3.5)), node_id="mc-r")
        ready.mark_ready(1.0)
        cluster.add_node(ready)
        cluster.add_node(Node(allocatable=Resources(940, gi(3.5)),
                              node_id="mc-p"))   # stays PROVISIONING
        mc.sample(cluster, 20.0)
        assert mc.node_count_series[-1] == (20.0, 1)   # not len(nodes) == 2
        assert mc.samples[-1].n_nodes == 1


class TestWaveSelectParity:
    """``Scheduler.select_wave_store`` advances the placer's working arrays
    with the same accounting ops as ``WavePlacer.bind``."""

    def test_waveplacer_bind_matches_inlined_wave_ops(self):
        """``WavePlacer.bind`` is the documented reference implementation of
        the four accounting ops ``select_wave_store`` inlines in its pod
        loop; replaying a wave's bindings through it must reproduce the
        placer's working arrays bit-for-bit (guards the two copies against
        drift)."""
        from repro.core.engine import PodStore, WavePlacer
        from repro.core.scheduler import BestFitBinPackingScheduler

        cluster = Cluster(use_arrays=True)
        rng = np.random.default_rng(3)
        for i in range(8):
            node = Node(allocatable=Resources(940, gi(3.5)),
                        node_id=f"wb-{i}")
            node.mark_ready(0.0)
            cluster.add_node(node)
        arr = cluster.arrays
        store = PodStore(arr)
        rows = [store.adopt(_mk_pod(rng)) for _ in range(30)]
        placer = WavePlacer(arr)
        bindings, _ = BestFitBinPackingScheduler().select_wave_store(
            placer, store, rows)
        assert bindings, "wave placed nothing"
        assert len({slot for _, slot in bindings}) > 1
        replay = WavePlacer(arr)   # same snapshot: nothing was committed
        for row, slot in bindings:
            replay.bind(int(arr.id_rank[slot]),
                        Resources(store.cpu_m[row], store.mem_mb[row]))
        for name in ("used_cpu", "used_mem", "free_cpu", "free_mem"):
            assert getattr(placer, name).tolist() == \
                getattr(replay, name).tolist(), name


class TestFailureWaveParity:
    """Satellite: failure / straggler injection interacting with wave
    placement.  A node death (or any mutation the placer did not make)
    bumps the mirror's version counter; the orchestrator must rebuild the
    placer rather than bind pods to stale — possibly dead — nodes."""

    def _run_with_failures(self, engine, straggler=False):
        reset_id_counters()
        injector = FailureInjector(mtbf_s=900.0, seed=3)
        spec = ExperimentSpec(
            workload="slow", rescheduler="non-binding", autoscaler="binding",
            seed=0, engine=engine, failure_injector=injector,
            straggler_threshold=0.8 if straggler else 0.0)
        sim = build_simulation(spec)
        if straggler:
            slowifier = StragglerInjector(every_k=2, slow_factor=0.4)
            for node in sorted(sim.cluster.nodes.values(),
                               key=lambda n: n.node_id):
                slowifier.maybe_slow(node)
        cluster = sim.cluster
        log = []
        inner = cluster.on_bind

        def spy(pod):
            # Every bind must land on a node that is alive *right now*.
            node = cluster.nodes.get(pod.node_id)
            assert node is not None, f"{pod} bound to dead {pod.node_id}"
            assert node.state.value != "terminated"
            log.append((pod.uid, pod.incarnation, pod.node_id,
                        pod.bound_time))
            inner(pod)

        cluster.on_bind = spy
        result = sim.run()
        return dataclasses.asdict(result), log

    def test_failure_injection_parity(self):
        ra, la = self._run_with_failures("array")
        ro, lo = self._run_with_failures("object")
        assert ra["failures_injected"] > 0, "injector never fired"
        assert ra == ro
        assert la == lo

    def test_straggler_and_failure_parity(self):
        ra, la = self._run_with_failures("array", straggler=True)
        ro, lo = self._run_with_failures("object", straggler=True)
        assert ra == ro
        assert la == lo

    def test_mid_cycle_node_loss_never_binds_to_dead_node(self):
        """Direct stale-placer scenario: the cluster loses a node *between*
        the wave snapshot and the bind commit (modelled by a rescheduler
        that kills a node while handling a blocked pod).  The wave must be
        rebuilt — later pods cannot bind to the dead node."""
        from repro.core.autoscaler import VoidAutoscaler
        from repro.core.orchestrator import Orchestrator
        from repro.core.rescheduler import RescheduleOutcome, VoidRescheduler
        from repro.core.scheduler import BestFitBinPackingScheduler

        cluster = Cluster(use_arrays=True)
        big = Node(allocatable=Resources(2000, gi(8.0)), node_id="a-big")
        small = Node(allocatable=Resources(400, gi(1.0)), node_id="b-small")
        big.mark_ready(0.0)
        small.mark_ready(0.0)
        cluster.add_node(big)
        cluster.add_node(small)

        killed = []

        class NodeKillingRescheduler(VoidRescheduler):
            def reschedule(self, cluster_, pod, now):
                # Simulate a NODE_FAIL surfacing mid-cycle: the big node
                # dies while the orchestrator handles the blocked pod.
                if not killed:
                    for p in list(big.pods.values()):
                        cluster_.unbind(p, now, failed=True)
                    cluster_.remove_node(big, now)
                    killed.append(True)
                return RescheduleOutcome.FAILED

        class _NullProvider:
            def request_node(self, *a, **k):
                return None

        orch = Orchestrator(cluster, BestFitBinPackingScheduler(),
                            NodeKillingRescheduler(max_pod_age_s=0.0),
                            VoidAutoscaler(_NullProvider()))

        def mk(name, cpu, mem):
            return Pod(spec=PodSpec(name, PodKind.SERVICE,
                                    Resources(cpu, gi(mem))), submit_time=0.0)

        # p1 fits only the big node, p2 is unplaceable (triggers the
        # rescheduler, which kills the big node), p3 would fit the big
        # node's *stale* free columns but must not land there.
        orch.submit(mk("p1", 600, 2.0))
        orch.submit(mk("p2", 5000, 32.0))
        orch.submit(mk("p3", 600, 2.0))
        orch.cycle(10.0)

        assert killed, "rescheduler never fired"
        assert big.node_id not in cluster.nodes
        for pod in orch.pods:
            assert pod.node_id != big.node_id, \
                f"{pod} bound to the dead node"
        cluster.check_invariants(deep=True)


class TestRunLengthParity:
    """Satellite: the best-fit run-length fast path (one extremum query
    amortized over runs of same-size pods) and the per-pod query that
    every other policy takes must produce bit-identical bind sequences
    *and node used-floats* versus the seed object engine — float
    accumulation order included, which is why the spy records the bound
    node's ``used`` bit patterns at every bind."""

    SCHEDULERS = ["best-fit", "worst-fit", "first-fit", "k8s-default"]

    def _bind_log(self, arrivals, engine, scheduler, initial_workers=2):
        import struct

        reset_id_counters()
        spec = ExperimentSpec(
            workload="runlen", arrivals=list(arrivals),
            scheduler=scheduler, rescheduler="void", autoscaler="binding",
            initial_workers=initial_workers, seed=0, engine=engine)
        sim = build_simulation(spec)
        log = []
        inner = sim.cluster.on_bind

        def spy(pod):
            node = sim.cluster.nodes[pod.node_id]
            log.append((pod.uid, pod.incarnation, pod.node_id,
                        pod.bound_time, node._used_cpu_m,
                        struct.pack("<d", node._used_mem_mb).hex()))
            inner(pod)

        sim.cluster.on_bind = spy
        result = sim.run()
        return log, dataclasses.asdict(result)

    def _assert_all_identical(self, arrivals, scheduler, **kw):
        wave_log, wave_res = self._bind_log(arrivals, "array", scheduler,
                                            **kw)
        obj_log, obj_res = self._bind_log(arrivals, "object", scheduler,
                                          **kw)
        assert wave_log, "workload produced no bindings"
        assert wave_log == obj_log, "wave path diverged from seed"
        assert wave_res == obj_res

    @pytest.mark.parametrize("scheduler", SCHEDULERS)
    def test_same_size_runs(self, scheduler):
        """A pure same-size stream: maximal run lengths, nodes fill one by
        one — the scenario the fast path was built for."""
        spec = PodSpec("rl-same", PodKind.BATCH, Resources(200, gi(0.6)),
                       duration_s=600.0)
        arrivals = [Arrival(float(i), spec) for i in range(40)]
        self._assert_all_identical(arrivals, scheduler)

    @pytest.mark.parametrize("scheduler", SCHEDULERS)
    def test_mixed_size_runs(self, scheduler):
        """Random run lengths of mixed sizes (including services) stress the
        run-break conditions: key changes, ties against the runner-up and
        nodes going infeasible mid-run."""
        rng = np.random.default_rng(7)
        specs = [
            PodSpec("rl-s", PodKind.BATCH, Resources(100, gi(0.3)),
                    duration_s=300.0),
            PodSpec("rl-m", PodKind.BATCH, Resources(200, gi(0.6)),
                    duration_s=420.0),
            PodSpec("rl-l", PodKind.BATCH, Resources(300, gi(0.9)),
                    duration_s=540.0),
            PodSpec("rl-svc", PodKind.SERVICE, Resources(150, gi(0.5)),
                    moveable=True),
        ]
        arrivals = []
        t = 0.0
        while len(arrivals) < 70:
            spec = specs[int(rng.integers(0, len(specs)))]
            for _ in range(int(rng.integers(1, 8))):
                t += float(rng.exponential(3.0))
                arrivals.append(Arrival(t, spec))
        self._assert_all_identical(arrivals, scheduler)

    @pytest.mark.parametrize("scheduler", SCHEDULERS)
    def test_runs_interrupted_by_scale_out(self, scheduler):
        """A one-node cluster forces mid-run blocking: the wave flushes, the
        binding autoscaler provisions, and the run resumes later — bind
        sequences must survive the interruption bit-for-bit."""
        spec = PodSpec("rl-burst", PodKind.BATCH, Resources(300, gi(1.2)),
                       duration_s=900.0)
        arrivals = [Arrival(float(i) * 0.5, spec) for i in range(30)]
        self._assert_all_identical(arrivals, scheduler, initial_workers=1)
