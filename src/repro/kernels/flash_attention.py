"""Pallas TPU flash attention: blockwise online-softmax, causal + GQA +
sliding window.

TPU-native design (not a CUDA port — DESIGN.md §8):
  * grid = (batch, q_heads, q_blocks, kv_blocks); the kv_blocks dimension is
    `arbitrary` (sequential) so the online-softmax running state lives in
    VMEM scratch across kv steps — HBM→VMEM staging replaces shared-memory
    tiling, and there is no warp-level anything.
  * q/k/v tiles are MXU-aligned (block sizes multiples of 128 where the
    sequence allows; head_dim 64-256 is fine as the contracted dim).
  * GQA is free: the k/v BlockSpec index_map maps q-head h to kv-head
    h // q_per_kv — no repeated k/v materialization.
  * causal + window masking is done on global indices derived from
    program_ids; fully-masked (q,k) tile pairs are skipped via pl.when.

Numerics: f32 accumulation of logits/softmax state regardless of input
dtype; output cast back to the query dtype.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -2.0 ** 30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr,
                  *, sm_scale: float, causal: bool, window: int,
                  block_q: int, block_k: int, seq_len: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * block_q
    k_start = ik * block_k

    # Skip tiles that the causal/window mask fully zeroes.
    run = jnp.bool_(True)
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + block_q - 1)
    if window > 0:
        run = jnp.logical_and(
            run, k_start + block_k - 1 > q_start - window)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)            # (BQ, hd)
        k = k_ref[0, 0].astype(jnp.float32)            # (BK, hd)
        v = v_ref[0, 0].astype(jnp.float32)            # (BK, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                                # (BQ, BK)
        qi = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                (block_q, block_k), 0)
        ki = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                (block_q, block_k), 1)
        mask = ki < seq_len
        if causal:
            mask = jnp.logical_and(mask, ki <= qi)
        if window > 0:
            mask = jnp.logical_and(mask, ki > qi - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                             # (BQ,)
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        # renormalize the running state
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1)
        acc = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new
        acc_scr[...] = acc

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    sm_scale: Optional[float] = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False) -> jax.Array:
    """q: (B, Hq, T, hd); k/v: (B, Hkv, S, hd); Hq % Hkv == 0.

    Returns (B, Hq, T, hd) in q.dtype.
    """
    B, Hq, T, hd = q.shape
    _, Hkv, S, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    q_per_kv = Hq // Hkv
    if sm_scale is None:
        sm_scale = hd ** -0.5
    bq = min(block_q, T)
    bk = min(block_k, S)
    assert T % bq == 0 and S % bk == 0, (T, bq, S, bk)
    grid = (B, Hq, T // bq, S // bk)

    kernel = functools.partial(
        _flash_kernel, sm_scale=sm_scale, causal=causal, window=window,
        block_q=bq, block_k=bk, seq_len=S)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, iq, ik, qkv=q_per_kv:
                         (b, h // qkv, ik, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, iq, ik, qkv=q_per_kv:
                         (b, h // qkv, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, T, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
