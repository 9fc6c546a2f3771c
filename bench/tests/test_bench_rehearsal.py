"""CPU rehearsal of every cell's driver at a tiny size, and the harness's
refusal to measure without a TPU.

The rehearsal runs the whole path of a run (set-up, window, the check
against the plain reference, the result) on whatever backend JAX has,
with the traffic cut to a few jobs.  The real entry point prints no
result when it finds no TPU.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
from benchlib import bench_file  # noqa: E402

TINY = {"lanes": {"lanes": 4}}


def cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


def driver_of(workload):
    run = bench_file("run.py")
    bench = run.load_benchmark()
    _cell, entry = run.cell_of(bench, workload)
    with open(os.path.join(ROOT, entry["file"])) as f:
        return json.load(f)["driver"]


@pytest.mark.parametrize("workload", cells())
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_is_correct_and_reports(workload, trace):
    run = bench_file("run.py")
    result = run.run_cell(workload, 2**31 + 11, 0.2, bool(trace),
                          require_tpu=False,
                          mix_overrides=TINY[driver_of(workload)])
    assert result["correct"] is True, result["checks"]
    assert list(result)[-1] == "checks"
    assert result["attempted"] >= 1 and result["failed"] == 0
    for key in ("platform", "kind", "count", "memory_peak_bytes"):
        assert key in result["device"]
    bench = run.load_benchmark()
    cell, _ = run.cell_of(bench, workload)
    if not trace:
        want = {m["name"] for m in run.metrics_for(bench, cell, "end_to_end")}
        assert set(result["metrics"]) == want
        assert all(m["value"] > 0 for m in result["metrics"].values())
    else:
        assert "breakdown" in result


def test_no_tpu_means_no_result(monkeypatch, capsys):
    run = bench_file("run.py")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "unused")
    rc = run.main(["--workload", cells()[0], "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out.strip() == ""
    assert "TPU" in out.err


def test_benchmark_files_alone_do_not_run(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's paths has
    no program to measure: the run fails and prints no result."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in bench["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable] + bench["command"][1:]
        + ["--workload", cells()[0], "--seed", "1", "--seconds", "1",
           "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
