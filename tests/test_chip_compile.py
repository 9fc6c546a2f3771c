"""Compile the device programs for a TPU v5e without a chip.

The TPU compiler is installed with JAX and compiles for a described,
unattached chip: it refuses what the chip would refuse (unsupported
64-bit ops, programs that do not fit the device memory) at no chip time.
Each test lowers one jitted program of the main path at its real size,
on one device of a ``v5e:2x2`` topology, and checks that
``memory_analysis()`` fits one chip's 16 GB.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and
test workers import every test file.
"""
import os
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.manyworld import lanes as ml

V5E_HBM_BYTES = 16 * 10**9
# The many-world lane program at a policy-search population size.
LANES, PODS, NODES = 1024, 2048, 64


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _fits_one_chip(compiled) -> int:
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes + ma.generated_code_size_in_bytes)
    assert 0 < total < V5E_HBM_BYTES, total
    return total


def _sds(tree, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


@pytest.fixture(scope="module")
def lane_program(one_chip):
    """``get(sched, autoscale, resched)``: the lane program compiled for
    one v5e chip, once per module.  Static: 1024 lanes x 2048 pods x 64
    nodes; autoscaled, with or without Alg. 3: the policy-search
    population of its benchmark cells, 4096 lanes x 64 pods x 64 node
    records."""
    cache = {}

    def get(sched, autoscale=False, resched=False):
        key = (sched, autoscale, resched)
        if key not in cache:
            lane = {"arrival_t": np.zeros(1), "cpu_m": np.zeros(1),
                    "mem_mb": np.zeros(1), "duration_s": np.zeros(1),
                    "is_batch": np.ones(1, bool),
                    "moveable": np.zeros(1, bool),
                    "n_nodes": 1 if autoscale else NODES, "alloc_cpu": 940,
                    "alloc_mem": 3584.0, "boot_cycles": 5,
                    "max_pod_age_s": 60.0}
            shape = (4096, 64) if autoscale else (LANES, PODS)
            tiny = ml.stack_lanes([lane], sched,
                                  node_pad=64 if autoscale else None,
                                  resched=resched)
            with jax.enable_x64(True):
                args = [jax.ShapeDtypeStruct(shape if a.ndim == 2
                                             else shape[:1], a.dtype,
                                             sharding=one_chip)
                        for a in ml.program_args(tiny)]
                cache[key] = ml._program_factory(
                    sched, 64 if autoscale else NODES,
                    autoscale, resched).lower(*args).compile()
        return cache[key]

    return get


@pytest.mark.parametrize("sched", ["best-fit", "worst-fit"])
def test_lane_program_compiles_for_v5e(lane_program, sched):
    """The lane program at 1024 lanes x 2048 pods x 64 nodes: int64 bit
    patterns only, so the TPU's x64 rewrite has no float64 to emulate."""
    compiled = lane_program(sched)
    _fits_one_chip(compiled)
    assert "f64[" not in compiled.as_text()      # no float64 operand


def test_autoscaled_lane_program_compiles_for_v5e(lane_program):
    """The autoscaled lane program (binding autoscaler, Alg. 6) at the
    policy-search population of its benchmark cell: 4096 lanes x 64 pods
    x 64 node records, int64 bit patterns only."""
    compiled = lane_program("best-fit", True)
    _fits_one_chip(compiled)
    assert "f64[" not in compiled.as_text()      # no float64 operand


def test_resched_lane_program_compiles_for_v5e(lane_program):
    """The autoscaled lane program with Alg. 3 in its wave, at the same
    population: int64 bit patterns only, and one more named program."""
    compiled = lane_program("best-fit", True, True)
    _fits_one_chip(compiled)
    text = compiled.as_text()
    assert "f64[" not in text                    # no float64 operand
    assert "lane_program_best_fit_autoscaled_resched" in text


_COMP_HEAD = re.compile(r"^(?:ENTRY )?%(\S+) .*\{$")
_INDEXED = re.compile(r" (scatter|gather)\(%")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"(?:calls|to_apply|body|condition)=%([\w.\-]+)")
STEP_LOOPS = ("/wave/", "/completions/", "/reschedule/", "/scale_in/")


def indexed_ops(hlo: str):
    """``(opcode, op_names)`` of every scatter and gather in compiled HLO
    text.  A fusion's root keeps no metadata of its own, so its names are
    those of the other instructions of its fused computation, else of the
    instructions that call that computation."""
    comps, comp = {}, None
    for line in hlo.splitlines():
        head = _COMP_HEAD.match(line)
        if head:
            comp = comps.setdefault(head.group(1), [])
        elif line == "}":
            comp = None
        elif comp is not None:
            comp.append(line)
    callers = {}
    for name, lines in comps.items():
        for line in lines:
            for callee in _CALLS.findall(line):
                callers.setdefault(callee, []).append((name, line))

    def names_of(name, seen=()):
        found = [n for line in comps[name] for n in _OP_NAME.findall(line)]
        if found or name in seen:
            return found
        for caller, line in callers.get(name, ()):
            found += (_OP_NAME.findall(line)
                      or names_of(caller, seen + (name,)))
        return found

    return [(m.group(1), _OP_NAME.findall(line) or names_of(name))
            for name, lines in comps.items() for line in lines
            for m in [_INDEXED.search(line)] if m]


@pytest.mark.parametrize("sched,autoscale,resched", [
    ("best-fit", False, False), ("worst-fit", False, False),
    ("best-fit", True, False), ("best-fit", True, True)])
def test_lane_step_loops_have_no_scatter_or_gather(lane_program, sched,
                                                   autoscale, resched):
    """Inside the step loops (the completions, the wave with Alg. 3 and
    the scale-out, Alg. 6) a lane reads and writes its row element by a
    one-hot select: a TPU scatter or gather with one index per lane runs
    serially over the lanes."""
    ops = indexed_ops(lane_program(sched, autoscale, resched).as_text())
    in_loops = [(op, names) for op, names in ops
                if any(s in n for n in names for s in STEP_LOOPS)]
    assert not in_loops, in_loops
    # Nor once a cycle: a joining node frees its pods by a one-hot too.
    assert not ops, ops


_INSTR = re.compile(r"^\s*(?:ROOT )?%\S+ = \S+ ([\w\-]+)\(")

#: Instructions of each program without Alg. 3, by opcode, in its
#: v5e-compiled HLO text (fused computations included), as the program
#: compiled before Alg. 3 was added to the lane program: adding a program
#: leaves the others as they were.
OP_COUNTS = {
    ("best-fit", False): dict(
        add=106, bitcast=13, broadcast=301, compare=336, constant=224,
        convert=17, copy=19, fusion=17, iota=16, maximum=1, not_=17,
        or_=165, parameter=380, reduce=17, select=302, subtract=41, xor=4,
        **{"and": 213, "bitcast-convert": 106, "copy-done": 54,
           "count-leading-zeros": 10, "custom-call": 24,
           "dynamic-slice": 1, "get-tuple-element": 321, "shift-left": 80,
           "shift-right-arithmetic": 45, "shift-right-logical": 40,
           "slice-done": 24}),
    ("worst-fit", False): dict(
        add=107, bitcast=13, broadcast=301, compare=337, constant=224,
        convert=17, copy=19, fusion=17, iota=16, maximum=1, not_=17,
        or_=165, parameter=380, reduce=17, select=303, subtract=43, xor=4,
        **{"and": 213, "bitcast-convert": 106, "copy-done": 54,
           "count-leading-zeros": 10, "custom-call": 24,
           "dynamic-slice": 1, "get-tuple-element": 321, "shift-left": 80,
           "shift-right-arithmetic": 45, "shift-right-logical": 40,
           "slice-done": 24}),
    ("best-fit", True): dict(
        add=256, bitcast=29, broadcast=740, compare=826, constant=537,
        convert=45, copy=82, fusion=40, iota=44, maximum=7, minimum=2,
        not_=26, or_=387, parameter=881, reduce=40, reverse=1, select=747,
        subtract=107, xor=12,
        **{"and": 482, "bitcast-convert": 252, "copy-done": 62,
           "count-leading-zeros": 24, "custom-call": 51,
           "dynamic-slice": 1, "get-tuple-element": 735, "shift-left": 190,
           "shift-right-arithmetic": 109, "shift-right-logical": 96,
           "slice-done": 108}),
}


def op_counts(hlo: str) -> dict:
    """Instructions of compiled HLO text by opcode."""
    counts = {}
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if m:
            counts[m.group(1)] = counts.get(m.group(1), 0) + 1
    return counts


@pytest.mark.parametrize("sched,autoscale", sorted(OP_COUNTS))
def test_programs_without_alg3_keep_their_op_counts(lane_program, sched,
                                                    autoscale):
    """The static and autoscaled programs compile to the instructions
    they had before Alg. 3 shared their code: its stage traces only into
    its own program."""
    want = {op.rstrip("_"): n for op, n in OP_COUNTS[sched, autoscale].items()}
    assert op_counts(lane_program(sched, autoscale).as_text()) == want


def test_indexed_ops_finds_a_scatter_in_a_step_loop(one_chip):
    """The finder names a per-lane scatter inside a scoped while loop,
    compiled for a v5e, under its scope, fused or not."""
    from jax import lax
    import jax.numpy as jnp

    def prog(x, i):
        li = jnp.arange(x.shape[0])

        def body(c):
            k, x = c
            with jax.named_scope("wave"):
                x = x.at[li, (i + k) % x.shape[1]].set(k)
            return k + 1, x

        return lax.while_loop(lambda c: c[0] < 3, body, (0, x))[1]

    args = (jax.ShapeDtypeStruct((4096, 64), np.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((4096,), np.int32, sharding=one_chip))
    ops = indexed_ops(jax.jit(prog).lower(*args).compile().as_text())
    assert any(op == "scatter" and any("/wave/" in n for n in names)
               for op, names in ops), ops


def _forecaster_shapes(one_chip):
    from repro.forecast import model as fmodel
    from repro.models.params import init_params
    arch = fmodel.forecast_arch()
    params = jax.eval_shape(lambda: init_params(
        jax.random.key(0), fmodel.forecast_specs(arch)))
    return fmodel, arch, _sds(params, one_chip)


def test_forecaster_apply_compiles_for_v5e(one_chip):
    """The per-cycle inference of the learned forecaster, as
    ``LearnedForecaster.predict`` calls it: one history window."""
    from repro.forecast import WindowConfig
    fmodel, arch, params = _forecaster_shapes(one_chip)
    window = WindowConfig()
    live = fmodel.LearnedForecaster(None, arch, window)
    x = jax.ShapeDtypeStruct((1, window.history_bins), np.float32,
                             sharding=one_chip)
    _fits_one_chip(live._apply.lower(params, x).compile())


def test_forecaster_train_step_compiles_for_v5e(one_chip):
    """One AdamW step at the shipped width and batch (d_model 32, 2 heads,
    batch 64, 16 history bins)."""
    from repro.forecast import WindowConfig
    from repro.train.optimizer import OptimizerConfig, init_opt_state
    fmodel, arch, params = _forecaster_shapes(one_chip)
    opt_state = _sds(jax.eval_shape(init_opt_state, params), one_chip)
    step = fmodel.make_train_step(arch, OptimizerConfig())
    h = WindowConfig().history_bins
    xb = jax.ShapeDtypeStruct((64, h), np.float32, sharding=one_chip)
    yb = jax.ShapeDtypeStruct((64,), np.float32, sharding=one_chip)
    _fits_one_chip(step.lower(params, opt_state, xb, yb).compile())
