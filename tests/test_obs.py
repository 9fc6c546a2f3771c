"""Observability layer contracts (ISSUE 10).

* the event ring wraps: bounded memory, latest-N retention, total count;
* NPZ and JSON persistence round-trip **bit-exactly** (values and NaN
  pattern), including a wrapped ring;
* recording is passive: an obs-on run produces the bit-identical
  ``ExperimentResult`` on **both** engines;
* attribution is complete: every reactive scale-out request and every
  scale-in in the run appears in the event log;
* the Chrome-trace exporter emits well-formed complete events;
* the cell runner's ``obs_dir`` capture changes nothing about the row.
"""
import dataclasses
import json
import os

import numpy as np
import pytest

from repro.core import ExperimentSpec, reset_id_counters, run_experiment
from repro.obs import (EventLog, ObsConfig, PhaseProfiler, chrome_trace,
                       load_bundle, run_recorded, save_bundle)
from repro.obs.recorder import (EV_FORECAST, EV_SCALE_IN, EV_SCALE_OUT,
                                SO_PRELAUNCH)

N_JOBS = 60


def _spec(engine, obs=None, autoscaler="predictive"):
    return ExperimentSpec(scenario="flash-crowd", scenario_jobs=N_JOBS,
                          autoscaler=autoscaler, rescheduler="non-binding",
                          seed=3, engine=engine, obs=obs)


# -- EventLog unit contracts --------------------------------------------------

def _fill(log: EventLog, n: int) -> None:
    for i in range(n):
        log.record(float(i), i % 3, cycle=i, uid=i,
                   node=f"node-{i % 5}", pending=float(i), v1=float(i) * 0.5,
                   v2=float("nan") if i % 4 == 0 else float(i))


class TestEventRing:
    def test_wraparound_retains_latest(self):
        log = EventLog(capacity=8)
        _fill(log, 20)
        assert log.n_seen == 20          # counts every event ever recorded
        assert len(log) == 8             # but holds only the last capacity
        cols = log.columns()
        # chronological unroll: exactly events 12..19, in order
        assert cols["t"].tolist() == [float(i) for i in range(12, 20)]
        assert cols["uid"].tolist() == list(range(12, 20))
        # interning saw every node id, even ones whose events were dropped
        assert log.node_table == [f"node-{i}" for i in range(5)]

    def test_no_wrap_below_capacity(self):
        log = EventLog(capacity=32)
        _fill(log, 10)
        assert log.n_seen == len(log) == 10
        assert log.columns()["t"].tolist() == [float(i) for i in range(10)]

    @pytest.mark.parametrize("suffix", [".npz", ".json"])
    @pytest.mark.parametrize("n", [10, 20])   # unwrapped and wrapped
    def test_round_trip_bit_exact(self, tmp_path, suffix, n):
        log = EventLog(capacity=16)
        _fill(log, n)
        path = str(tmp_path / f"events{suffix}")
        log.save(path)
        back = EventLog.load(path)
        assert log.same_as(back)
        assert back.same_as(log)
        # and the reloaded log keeps recording correctly (ring re-laid)
        back.record(99.0, 0, uid=99)
        assert back.n_seen == n + 1
        assert back.columns()["uid"][-1] == 99

    def test_same_as_detects_drift(self):
        a, b = EventLog(capacity=8), EventLog(capacity=8)
        _fill(a, 6), _fill(b, 6)
        assert a.same_as(b)
        b.f[3, 0] += 1e-12               # one ULP-ish nudge must be caught
        assert not a.same_as(b)


class TestProfiler:
    def test_span_ring_wraps_aggregates_do_not(self):
        prof = PhaseProfiler(max_spans=4)
        for _ in range(10):
            t0 = prof.start()
            prof.stop("phase_a", t0, 1.0)
        assert prof.n_spans_seen == 10
        payload = prof.to_payload()
        assert payload["count"].tolist() == [10]       # aggregate sees all
        assert len(payload["spans"]["dur_s"]) == 4     # ring keeps last 4
        assert int(payload["hist"].sum()) == 10

    def test_chrome_trace_shape(self):
        prof = PhaseProfiler(max_spans=8)
        for name in ("alpha", "beta", "alpha"):
            t0 = prof.start()
            prof.stop(name, t0, 2.5)
        events = chrome_trace(prof.to_payload())
        assert len(events) == 3
        assert {e["name"] for e in events} == {"alpha", "beta"}
        for e in events:
            assert e["ph"] == "X"
            assert e["ts"] >= 0.0 and e["dur"] >= 0.0
            assert e["args"]["sim_s"] == 2.5


    def test_span_tree_parents_and_self_times(self):
        """Nested ``span``s record their parent's id; a ``stop`` span inside
        a ``span`` takes it as parent; self times subtract the children
        and sum to the root's duration."""
        prof = PhaseProfiler(max_spans=16)
        with prof.span("root") as root:
            with prof.span("child") as child:
                t0 = prof.start()
                prof.stop("leaf", t0)
            with prof.span("child"):
                pass
        with prof.span("other"):
            pass
        sp = prof.to_payload()["spans"]
        names = prof.to_payload()["names"]
        by_id = {i: (names[int(sp["name"][i])], int(sp["parent"][i]),
                     float(sp["dur_s"][i])) for i in range(len(sp["t0"]))}
        assert by_id[root][:2] == ("root", -1)
        assert by_id[child][:2] == ("child", root)
        assert [v[:2] for v in by_id.values()].count(("child", root)) == 2
        assert ("leaf", child) in [v[:2] for v in by_id.values()]
        assert by_id[max(by_id)][:2] == ("other", -1)
        own = prof.self_times(root)
        assert set(own) == {"root", "child", "leaf"}
        assert sum(own.values()) == pytest.approx(by_id[root][2])
        kids = sum(v[2] for v in by_id.values() if v[1] == root)
        assert own["root"] == pytest.approx(by_id[root][2] - kids)
        assert prof.phases()["child"]["count"] == 2

    def test_self_times_empty_once_the_ring_drops_the_root(self):
        prof = PhaseProfiler(max_spans=4)
        with prof.span("root") as root:
            pass
        assert set(prof.self_times(root)) == {"root"}
        for _ in range(4):
            with prof.span("later"):
                pass
        assert prof.self_times(root) == {}
        # A span whose slot was reused while it was open keeps its
        # aggregate but leaves the newer span's slot alone.
        with prof.span("long"):
            for _ in range(5):
                t0 = prof.start()
                prof.stop("inner", t0)
        assert prof.phases()["long"]["count"] == 1
        sp = prof.to_payload()["spans"]
        assert "long" not in [prof.to_payload()["names"][int(i)]
                              for i in sp["name"]]


# -- passive-recording contract on the full stack -----------------------------

class TestBitIdentity:
    @pytest.mark.parametrize("engine", ["array", "object"])
    def test_result_identical_obs_on_vs_off(self, engine):
        reset_id_counters()
        r_off = run_experiment(_spec(engine))
        reset_id_counters()
        r_on, rec = run_recorded(_spec(engine))
        assert dataclasses.asdict(r_on) == dataclasses.asdict(r_off)
        assert rec.events.n_seen > 0
        assert rec.prof.n_spans_seen > 0

    def test_attribution_complete(self):
        """Every reactive scale-out request and every scale-in in the run
        is an attributed event (prelaunches are recorded separately)."""
        reset_id_counters()
        result, rec = run_recorded(_spec("array"))
        cols = rec.events.columns()
        assert rec.events.n_seen <= rec.events.capacity, \
            "test run wrapped the ring; counts below would undercount"
        so = cols["kind"] == EV_SCALE_OUT
        n_reactive = int((so & (cols["v1"] != SO_PRELAUNCH)).sum())
        assert n_reactive == result.scale_outs
        assert int((cols["kind"] == EV_SCALE_IN).sum()) == result.scale_ins
        # the predictive autoscaler publishes its forecasts
        fc = cols["kind"] == EV_FORECAST
        assert fc.any()
        assert np.isfinite(cols["rate"][fc]).all()
        assert np.isfinite(cols["conf"][fc]).all()
        # decision inputs ride on every scale-out record
        assert np.isfinite(cols["pending"][so]).all()
        assert np.isfinite(cols["util"][so]).all()

    def test_event_times_monotone(self):
        reset_id_counters()
        _result, rec = run_recorded(_spec("array"))
        t = rec.events.columns()["t"]
        assert (np.diff(t) >= 0).all()


# -- bundle export / report inputs --------------------------------------------

class TestBundle:
    @pytest.fixture(scope="class")
    def recorded(self):
        reset_id_counters()
        return run_recorded(_spec("array"))

    @pytest.mark.parametrize("suffix", [".npz", ".json"])
    def test_bundle_round_trip(self, tmp_path, recorded, suffix):
        _result, rec = recorded
        path = str(tmp_path / f"bundle{suffix}")
        rec.export(path)
        back = load_bundle(path)
        assert EventLog.from_payload(back["events"]).same_as(rec.events)
        live = rec.prof.to_payload()
        assert back["profile"]["names"] == live["names"]
        assert np.array_equal(back["profile"]["count"], live["count"])
        for key in ("dur_s", "parent"):
            assert np.array_equal(back["profile"]["spans"][key],
                                  live["spans"][key])
        assert back["meta"]["engine"] == "array"
        assert back["meta"]["autoscaler"] == "predictive"

    @pytest.mark.parametrize("suffix", [".npz", ".json"])
    def test_bundle_without_parent_column_loads(self, tmp_path, recorded,
                                                suffix):
        """A bundle saved before the span ring had a parent column loads
        with -1 (no parent) for every span."""
        _result, rec = recorded
        path = str(tmp_path / f"bundle{suffix}")
        rec.export(path)
        if suffix == ".json":
            with open(path) as fh:
                raw = json.load(fh)
            del raw["profile"]["spans"]["parent"]
            with open(path, "w") as fh:
                json.dump(raw, fh)
        else:
            with np.load(path) as z:
                arrays = {k: z[k] for k in z.files if k != "sp_parent"}
            np.savez(path, **arrays)
        spans = load_bundle(path)["profile"]["spans"]
        assert spans["parent"].dtype == np.int64
        assert spans["parent"].tolist() == [-1] * len(spans["name"])
        assert np.array_equal(spans["dur_s"],
                              rec.prof.to_payload()["spans"]["dur_s"])

    def test_node_count_series_exposed(self, recorded):
        """Satellite: the typed MetricsCollector.node_count_series rides
        the obs bundle."""
        _result, rec = recorded
        series = rec._sim.metrics.node_count_series
        assert all(isinstance(t, float) and isinstance(n, int)
                   for t, n in series)
        bundle = rec.bundle()
        assert bundle["node_count_t"].tolist() == [s[0] for s in series]
        assert bundle["node_count_n"].tolist() == [s[1] for s in series]

    def test_report_renders(self, recorded):
        from repro.obs import render_report
        _result, rec = recorded
        text = render_report(rec.bundle(), limit=5)
        assert "cycle-phase profile" in text
        assert "scale_out" in text

    def test_chrome_trace_from_bundle(self, tmp_path, recorded):
        _result, rec = recorded
        events = chrome_trace(rec.bundle()["profile"])
        path = tmp_path / "trace.json"
        path.write_text(json.dumps({"traceEvents": events}))
        loaded = json.loads(path.read_text())["traceEvents"]
        assert len(loaded) == min(rec.prof.n_spans_seen, rec.prof.max_spans)
        assert all(e["ph"] == "X" and e["dur"] >= 0.0 for e in loaded)


# -- cell runner capture ------------------------------------------------------

class TestCellRunnerCapture:
    def test_obs_dir_row_identical_and_bundle_written(self, tmp_path):
        from repro.search.runner import CellSpec, run_cell
        base = dict(scenario="flash-crowd", scheduler="best-fit",
                    autoscaler="predictive", rescheduler="non-binding",
                    seed=3, n_jobs=N_JOBS)
        plain = run_cell(CellSpec(**base))
        captured = run_cell(CellSpec(**base, obs_dir=str(tmp_path)))
        path = os.path.join(str(tmp_path), f"{CellSpec(**base).label}.npz")
        assert os.path.exists(path)
        bundle = load_bundle(path)
        assert bundle["events"]["n_seen"] > 0
        plain.pop("wall_s"), captured.pop("wall_s")
        captured["cell"].pop("obs_dir"), plain["cell"].pop("obs_dir")
        assert captured == plain         # capture is invisible in the row
