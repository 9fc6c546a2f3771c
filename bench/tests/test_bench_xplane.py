"""The trace reduction on hand-made event lists with the planes and lines
of a TPU trace (``/device:TPU:<n>`` with ``XLA Ops`` and ``XLA Modules``,
host spans on ``/host:CPU``)."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
from benchlib import bench_file  # noqa: E402

xp = bench_file("xplane.py")


def planes(second_device=False):
    host = ("/host:CPU", [("python", [("bench.window", 0.0, 1000.0),
                                      ("bench.a", 100.0, 300.0),
                                      ("bench.b", 600.0, 300.0),
                                      ("other", 0.0, 1000.0)])])
    dev = ("/device:TPU:0", [
        ("XLA Ops", [("op1", 0.0, 200.0), ("op2", 150.0, 100.0),
                     ("op3", 500.0, 100.0), ("late", 990.0, 50.0)]),
        ("XLA Modules", [("jit_f", 0.0, 600.0)])])
    out = [host, dev]
    if second_device:
        out.append(("/device:TPU:1", [("XLA Ops", [("op1", 0.0, 50.0)])]))
    return out


def test_busy_union_idle_share_and_ops():
    r = xp.reduce(planes(), "bench.window")
    # union [0, 250] + [500, 600] + [990, 1000] (clipped to the window)
    assert r["devices"] == 1
    assert r["busy_s"] == pytest.approx(360e-9)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["idle_share"] == pytest.approx(0.64)
    ops = dict(r["device_ops"])
    assert ops["op1"] == pytest.approx(200e-9)
    assert ops["late"] == pytest.approx(10e-9)
    assert r["modules"]["jit_f"] == pytest.approx(600e-9)


def test_gaps_are_labelled_by_the_innermost_host_span():
    r = xp.reduce(planes(), "bench.window")
    gaps = r["idle_gaps"]
    assert gaps[0][0] == "bench.b" and gaps[0][1] == pytest.approx(390e-9)
    assert gaps[1][0] == "bench.a" and gaps[1][1] == pytest.approx(250e-9)


def test_busy_is_averaged_over_devices():
    r = xp.reduce(planes(second_device=True), "bench.window")
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx((360e-9 + 50e-9) / 2)


def test_missing_window_span_is_an_error():
    with pytest.raises(ValueError):
        xp.reduce(planes(), "bench.nope")


def test_recorded_trace_file_is_read():
    """A small trace recorded on the CPU (``data/cpu_trace.xplane.pb``):
    the reader finds the window and the benchmark's host spans; a CPU
    trace has no device plane, so nothing counts as busy."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "cpu_trace.xplane.pb")
    found = xp.read_planes(path)
    spans = {e[0] for name, lines in found if name.startswith("/host:")
             for _line, events in lines for e in events}
    assert {"bench.window", "bench.fixture.device",
            "bench.fixture.host"} <= spans
    r = xp.reduce(found, "bench.window")
    assert r["devices"] == 0 and r["busy_s"] == 0.0
    assert r["window_s"] > 0.02
    # With nothing busy, the whole window is one idle gap.
    assert len(r["idle_gaps"]) == 1
    assert r["idle_gaps"][0][1] == pytest.approx(r["window_s"])
