#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are looked up by name in
``BENCHMARK.json``; the configuration names its driver
(``bench/drivers/<driver>.py``), the mix is ``bench/traffic/<mix>.json``
and each per-layer metric is read by ``bench/metrics/<metric>.py``.  A run:

1. checks the devices: no TPU, or fewer chips than the cell asks for,
   exits 1 with no result;
2. sets up (inputs from ``--seed``, one warm-up unit of work through the
   timed entry point, so nothing compiles in the window) and records
   ``setup_s`` from process start;
3. measures whole units of work until ``--seconds`` have passed; with
   ``--trace 1`` under the JAX profiler and the program's phase spans;
4. reads the peak device memory, frees the program's state, and compares
   what the window produced with the configuration's plain reference;
5. prints each compared number beside its limit as the last lines of
   standard error, and the result as the last line of standard output.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from benchlib import ROOT, bench_file, use_program  # noqa: E402

WINDOW_SPAN = "bench.window"


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_of(bench: dict, workload: str):
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; one of "
                       f"{sorted(cells)}")
    cell = cells[workload]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return cell, config


def metrics_for(bench: dict, cell: dict, kind: str) -> list:
    """The end-to-end or per-layer metrics that ``cell`` reports."""
    out = []
    for m in bench[kind]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        out.append(m)
    return out


class Context:
    """What a driver gets: the cell, its configuration and mix, the seed,
    and the JAX devices."""

    def __init__(self, cell, config, mix, seed, devices):
        self.cell = cell
        self.config = config
        self.mix = mix
        self.seed = seed
        self.devices = devices


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, mix_overrides: dict | None = None,
             t_start: float | None = None) -> dict:
    """One run of one cell; returns the result dict (``checks`` last).

    ``require_tpu=False`` with ``mix_overrides`` is the CPU rehearsal used
    by the tests: the same path at a tiny size, on any backend."""
    t_start = T_START if t_start is None else t_start
    bench = load_benchmark()
    cell, cfg_entry = cell_of(bench, workload)
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        config = json.load(f)
    mix = bench_file("traffic", "generator.py").load_mix(cell["traffic"])
    mix.update(mix_overrides or {})

    cache_dir = os.path.join(ROOT, ".jax_cache")
    if require_tpu:
        # The compile cache lives at a fixed path inside the checkout.
        os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    import jax
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < int(cell["chips"])):
        raise NoAccelerator(
            f"cell {workload} needs {cell['chips']} TPU chip(s); JAX found "
            f"{len(devices)} {devices[0].platform} device(s)")
    use_program()
    if require_tpu:
        from repro.compile_cache import use_compile_cache
        use_compile_cache()
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    driver = bench_file("drivers", f"{config['driver']}.py")
    ctx = Context(cell, config, mix, int(seed), devices[:int(cell["chips"])])
    state = driver.setup(ctx)
    setup_s = time.perf_counter() - t_start

    tmp = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        if trace:
            jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            win = driver.window(state, float(seconds), traced=bool(trace))
        reduced = None
        if trace:
            jax.profiler.stop_trace()
            xp = bench_file("xplane.py")
            path = xp.find_xplane(tmp)
            if path is not None:
                reduced = xp.reduce(xp.read_planes(path), WINDOW_SPAN)
    finally:
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)

    dev = ctx.devices[0]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in ctx.devices)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(ctx.devices), "memory_peak_bytes": peak}
    checks = driver.check(state)     # frees the program's state first

    metrics = {}
    if not trace:
        for m in metrics_for(bench, cell, "end_to_end"):
            value = setup_s if m["name"] == "setup_s" else win["e2e"].get(
                m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        read_ctx = {"driver": config["driver"], "config": config,
                    "counters": win["counters"], "trace": reduced,
                    "device_kind": dev.device_kind}
        e2e_names = {m["name"] for m in metrics_for(bench, cell,
                                                    "end_to_end")}
        for m in metrics_for(bench, cell, "per_layer"):
            if "workloads" not in m and m["moves"] not in e2e_names:
                continue
            reader = bench_file("metrics", f"{m['name']}.py")
            value = reader.read(read_ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if reduced is not None and reduced["devices"]:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]

    result = {
        "correct": all(c["value"] <= c["limit"] for c in checks),
        "attempted": win["attempted"],
        "failed": win["failed"],
        "metrics": metrics,
        "device": device,
    }
    if reduced is not None:
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in reduced["device_ops"]],
            "idle_gaps": [[n, s] for n, s in reduced["idle_gaps"]],
        }
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoAccelerator as exc:
        print(f"bench: {exc}; nothing was measured", file=sys.stderr)
        return 1
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
