"""Multi-device behaviour (8 forced host devices, run in subprocesses so the
main pytest process keeps its single real CPU device):

* logical-axis sharding rules produce runnable pjit programs,
* int8-compressed hierarchical gradient sync stays close to fp32 psum,
* elastic restore: checkpoint on mesh A, resume on mesh B, identical params.
"""
import os
import subprocess
import sys
import textwrap

import pytest

_ENV = dict(os.environ,
            XLA_FLAGS="--xla_force_host_platform_device_count=8",
            PYTHONPATH=os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "src"))


def _run(code: str) -> str:
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         env=_ENV, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_sharded_train_step_runs_and_matches_single_device():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.distributed.sharding import (DEFAULT_RULES, ShardingCtx,
                                            sharding_ctx, tree_shardings)
    from repro.train.train_step import (init_train_state, make_train_step,
                                        train_state_axes)
    from repro.train.optimizer import OptimizerConfig
    from repro.train.data import SyntheticLM, DataConfig
    from repro.launch.mesh import make_mesh

    cfg = get_config("deepseek-7b", tiny=True)
    data = SyntheticLM(cfg, DataConfig(batch_size=8, seq_len=32))
    batch = jax.tree.map(jnp.asarray, data.batch(0))
    step = make_train_step(cfg, OptimizerConfig(warmup_steps=1))

    # single-device reference
    state0 = init_train_state(jax.random.key(0), cfg)
    ref_state, ref_metrics = jax.jit(step)(state0, batch)

    # sharded over (data=2, model=4)
    mesh = make_mesh((2, 4), ("data", "model"))
    ctx = ShardingCtx(mesh, dict(DEFAULT_RULES))
    state = init_train_state(jax.random.key(0), cfg)
    st_sh = tree_shardings(ctx, jax.eval_shape(lambda: state),
                           train_state_axes(cfg))
    state = jax.tree.map(jax.device_put, state, st_sh)
    b_sh = {k: ctx.sharding_for(v.shape,
                                ("act_batch",) + (None,) * (v.ndim - 1))
            for k, v in batch.items()}
    batch_s = {k: jax.device_put(v, b_sh[k]) for k, v in batch.items()}
    with sharding_ctx(mesh, DEFAULT_RULES):
        fn = jax.jit(step, in_shardings=(st_sh, b_sh))
        new_state, metrics = fn(state, batch_s)
    np.testing.assert_allclose(float(metrics["loss"]),
                               float(ref_metrics["loss"]),
                               rtol=2e-4, atol=2e-4)
    l_ref = jax.tree.leaves(ref_state.params)[0]
    l_new = jax.tree.leaves(new_state.params)[0]
    np.testing.assert_allclose(np.asarray(l_new), np.asarray(l_ref),
                               rtol=5e-3, atol=5e-3)
    print("sharded-vs-single OK", float(metrics["loss"]))
    """)


def test_compressed_grad_sync_close_to_fp32():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.distributed.compression import make_compressed_ddp_step

    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
    W = jax.random.normal(jax.random.key(0), (64, 64))
    X = jax.random.normal(jax.random.key(1), (16, 64))

    def loss_fn(w, x):
        return jnp.mean(jnp.square(jnp.tanh(x @ w)))

    f_c = make_compressed_ddp_step(loss_fn, mesh, compress=True)
    f_f = make_compressed_ddp_step(loss_fn, mesh, compress=False)
    with jax.set_mesh(mesh):
        loss_c, g_c = jax.jit(f_c)(W, X)
        loss_f, g_f = jax.jit(f_f)(W, X)
    np.testing.assert_allclose(float(loss_c), float(loss_f), rtol=1e-6)
    gc, gf = np.asarray(g_c), np.asarray(g_f)
    denom = np.abs(gf).max()
    assert denom > 0
    rel = np.abs(gc - gf).max() / denom
    assert rel < 0.02, f"int8 sync error too large: {rel}"
    print("compression rel err", rel)
    """)


def test_elastic_restore_across_meshes():
    _run("""
    import tempfile, jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.distributed.elastic import restore_elastic, shardings_for_mesh, plan_resize
    from repro.train.checkpoint import CheckpointManager
    from repro.train.train_step import init_train_state

    cfg = get_config("deepseek-7b", tiny=True)
    state = init_train_state(jax.random.key(0), cfg)
    with tempfile.TemporaryDirectory() as d:
        ckpt = CheckpointManager(d)
        ckpt.save(7, state)
        # resume on a (4, 2) mesh (e.g. after scaling data-parallelism)
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        restored, step, _ = restore_elastic(ckpt, cfg, mesh)
        assert step == 7
        a = jax.tree.leaves(state.params)[0]
        b = jax.tree.leaves(restored.params)[0]
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # sharding actually landed on the new mesh
        sh = jax.tree.leaves(restored.params)[0].sharding
        assert sh.mesh.shape == {"data": 4, "model": 2}
    # resize planning respects divisibility
    assert plan_resize(8, cfg) == (2, 4) or plan_resize(8, cfg)[0] * plan_resize(8, cfg)[1] == 8
    print("elastic OK")
    """)
