"""Pallas TPU kernel for the chunkwise-parallel mLSTM (xLSTM matrix memory).

Same math as `repro.models.xlstm._mlstm_chunkwise` (the jnp oracle for this
kernel): an outer sequential walk over chunks carries the stabilized matrix
memory (C, n, m) in VMEM scratch; within a chunk everything is a masked
MXU matmul against the cumulative log-gates.

TPU mapping: grid = (batch, heads, chunks) with the chunk dimension
`arbitrary` (sequential); per-(b,h) the C scratch is a (dk, dv) f32 tile —
VMEM-resident across the whole sequence walk, never touching HBM between
chunks (the HBM traffic is exactly q/k/v/gates in and h out).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 64
NEG_BIG = -1e30


def _mlstm_kernel(q_ref, k_ref, v_ref, i_ref, f_ref, o_ref,
                  c_scr, n_scr, m_scr, *, chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        c_scr[...] = jnp.zeros_like(c_scr)
        n_scr[...] = jnp.zeros_like(n_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG_BIG)

    q = q_ref[0, 0].astype(jnp.float32)               # (L, dk)
    k = k_ref[0, 0].astype(jnp.float32)               # (L, dk)
    v = v_ref[0, 0].astype(jnp.float32)               # (L, dv)
    ii = i_ref[0, 0].astype(jnp.float32)              # (L,)
    ff = f_ref[0, 0].astype(jnp.float32)              # (L,)

    flog = jax.nn.log_sigmoid(ff)
    b = jnp.cumsum(flog)                              # (L,)
    g = b[-1]
    C, n, m = c_scr[...], n_scr[...], m_scr[...][0]

    idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jdx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = idx >= jdx

    log_a = b + m                                     # (L,)
    D = b[:, None] - b[None, :] + ii[None, :]
    D = jnp.where(tri, D, NEG_BIG)
    m_i = jnp.maximum(jnp.maximum(log_a, jnp.max(D, axis=-1)), NEG_BIG)
    inter_w = jnp.exp(log_a - m_i)                    # (L,)
    Sij = jnp.exp(D - m_i[:, None])                   # (L,L)
    qk = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    num = (inter_w[:, None] * jax.lax.dot_general(
        q, C, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
        + jax.lax.dot_general(Sij * qk, v, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32))
    den = inter_w * (q @ n) + jnp.sum(Sij * qk, axis=-1)
    h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_i))[:, None]
    o_ref[0, 0] = h.astype(o_ref.dtype)

    # state update (stabilized)
    w_j = g - b + ii                                  # (L,)
    m_new = jnp.maximum(jnp.maximum(g + m, jnp.max(w_j)), NEG_BIG)
    scale_old = jnp.exp(g + m - m_new)
    wj = jnp.exp(w_j - m_new)
    c_scr[...] = scale_old * C + jax.lax.dot_general(
        k * wj[:, None], v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    n_scr[...] = scale_old * n + jnp.sum(k * wj[:, None], axis=0)
    m_scr[...] = jnp.full_like(m_scr, m_new)


def mlstm_chunkwise(q: jax.Array, k: jax.Array, v: jax.Array,
                    i_raw: jax.Array, f_raw: jax.Array, *,
                    chunk: int = DEFAULT_CHUNK,
                    interpret: bool = False) -> jax.Array:
    """q,k,v: (B, H, T, dh); i_raw/f_raw: (B, H, T) -> h: (B, H, T, dh)."""
    B, H, T, dk = q.shape
    dv = v.shape[-1]
    L = min(chunk, T)
    assert T % L == 0, (T, L)
    grid = (B, H, T // L)

    kernel = functools.partial(_mlstm_kernel, chunk=L)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, L, dk), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, L, dk), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, L, dv), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, L), lambda b, h, c: (b, h, c)),
            pl.BlockSpec((1, 1, L), lambda b, h, c: (b, h, c)),
        ],
        out_specs=pl.BlockSpec((1, 1, L, dv), lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, T, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((dk, dv), jnp.float32),
            pltpu.VMEM((dk,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, i_raw, f_raw)
