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


@pytest.mark.parametrize("sched", ["best-fit", "worst-fit"])
def test_lane_program_compiles_for_v5e(one_chip, sched):
    """The lane program at 1024 lanes x 2048 pods x 64 nodes: int64 bit
    patterns only, so the TPU's x64 rewrite has no float64 to emulate."""
    lane = {"arrival_t": np.zeros(1), "cpu_m": np.zeros(1),
            "mem_mb": np.zeros(1), "duration_s": np.zeros(1),
            "is_batch": np.ones(1, bool), "n_nodes": NODES,
            "alloc_cpu": 940, "alloc_mem": 3584.0}
    tiny = ml.stack_lanes([lane], sched)
    with jax.enable_x64(True):
        args = [jax.ShapeDtypeStruct((LANES, PODS) if a.ndim == 2
                                     else (LANES,), a.dtype,
                                     sharding=one_chip)
                for a in ml.program_args(tiny)]
        compiled = ml._program_factory(sched, NODES).lower(*args).compile()
    _fits_one_chip(compiled)
    assert "f64[" not in compiled.as_text()      # no float64 operand


def test_autoscaled_lane_program_compiles_for_v5e(one_chip):
    """The autoscaled lane program (binding autoscaler, Alg. 6) at the
    policy-search population of its benchmark cell: 4096 lanes x 64 pods
    x 64 node records, int64 bit patterns only."""
    lane = {"arrival_t": np.zeros(1), "cpu_m": np.zeros(1),
            "mem_mb": np.zeros(1), "duration_s": np.zeros(1),
            "is_batch": np.ones(1, bool), "moveable": np.zeros(1, bool),
            "n_nodes": 1, "alloc_cpu": 940, "alloc_mem": 3584.0,
            "boot_cycles": 5}
    tiny = ml.stack_lanes([lane], "best-fit", node_pad=64)
    with jax.enable_x64(True):
        args = [jax.ShapeDtypeStruct((4096, 64) if a.ndim == 2
                                     else (4096,), a.dtype,
                                     sharding=one_chip)
                for a in ml.program_args(tiny)]
        compiled = ml._program_factory("best-fit", 64, True).lower(
            *args).compile()
    _fits_one_chip(compiled)
    assert "f64[" not in compiled.as_text()      # no float64 operand


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
