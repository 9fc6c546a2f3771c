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
    """``get(sched, autoscale)``: the lane program compiled for one v5e
    chip, once per module.  Static: 1024 lanes x 2048 pods x 64 nodes;
    autoscaled: the policy-search population of its benchmark cell, 4096
    lanes x 64 pods x 64 node records."""
    cache = {}

    def get(sched, autoscale=False):
        if (sched, autoscale) not in cache:
            lane = {"arrival_t": np.zeros(1), "cpu_m": np.zeros(1),
                    "mem_mb": np.zeros(1), "duration_s": np.zeros(1),
                    "is_batch": np.ones(1, bool),
                    "moveable": np.zeros(1, bool),
                    "n_nodes": 1 if autoscale else NODES, "alloc_cpu": 940,
                    "alloc_mem": 3584.0, "boot_cycles": 5}
            shape = (4096, 64) if autoscale else (LANES, PODS)
            tiny = ml.stack_lanes([lane], sched,
                                  node_pad=64 if autoscale else None)
            with jax.enable_x64(True):
                args = [jax.ShapeDtypeStruct(shape if a.ndim == 2
                                             else shape[:1], a.dtype,
                                             sharding=one_chip)
                        for a in ml.program_args(tiny)]
                cache[sched, autoscale] = ml._program_factory(
                    sched, 64 if autoscale else NODES,
                    autoscale).lower(*args).compile()
        return cache[sched, autoscale]

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


_COMP_HEAD = re.compile(r"^(?:ENTRY )?%(\S+) .*\{$")
_INDEXED = re.compile(r" (scatter|gather)\(%")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"(?:calls|to_apply|body|condition)=%([\w.\-]+)")
STEP_LOOPS = ("/wave/", "/completions/", "/scale_in/")


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


@pytest.mark.parametrize("sched,autoscale", [
    ("best-fit", False), ("worst-fit", False), ("best-fit", True)])
def test_lane_step_loops_have_no_scatter_or_gather(lane_program, sched,
                                                   autoscale):
    """Inside the step loops (the completions, the wave with its
    scale-out, Alg. 6) a lane reads and writes its row element by a
    one-hot select: a TPU scatter or gather with one index per lane runs
    serially over the lanes."""
    ops = indexed_ops(lane_program(sched, autoscale).as_text())
    in_loops = [(op, names) for op, names in ops
                if any(s in n for n in names for s in STEP_LOOPS)]
    assert not in_loops, in_loops
    # Nor once a cycle: a joining node frees its pods by a one-hot too.
    assert not ops, ops


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
