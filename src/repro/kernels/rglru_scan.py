"""Pallas TPU kernel for the RG-LRU diagonal linear recurrence.

Computes h_t = a_t * h_{t-1} + b_t over the time axis, with the carry state
held in VMEM scratch across sequential time-chunk grid steps:

  grid = (batch, channel_blocks, time_chunks); the last dimension is
  `arbitrary` (sequential), so each (b, rblk) pair walks its time chunks in
  order while `h` persists in a (1, block_r) f32 scratch.  Inside a chunk the
  recurrence runs as a fori_loop over rows of the VMEM-resident tile —
  per-step work is a fused multiply-add over `block_r` lanes (VPU-friendly,
  lanes a multiple of 128).

This is the TPU adaptation of a GPU scan kernel: no warp shuffles/shared
memory — the parallelism is (batch × channels) across the grid and 8x128
vector lanes within, with HBM→VMEM tiling over time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_R = 512
DEFAULT_CHUNK_T = 256


def _rglru_kernel(a_ref, b_ref, o_ref, h_scr, *, chunk_t: int):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    def body(t, h):
        a_t = a_ref[0, t, :].astype(jnp.float32)
        b_t = b_ref[0, t, :].astype(jnp.float32)
        h = a_t * h + b_t
        o_ref[0, t, :] = h.astype(o_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk_t, body, h_scr[0])
    h_scr[0] = h


def rglru_scan(a: jax.Array, b: jax.Array, *,
               block_r: int = DEFAULT_BLOCK_R,
               chunk_t: int = DEFAULT_CHUNK_T,
               interpret: bool = False) -> jax.Array:
    """a, b: (B, T, R) -> h: (B, T, R) with h_t = a_t*h_{t-1} + b_t."""
    B, T, R = a.shape
    br = min(block_r, R)
    ct = min(chunk_t, T)
    assert R % br == 0 and T % ct == 0, (R, br, T, ct)
    grid = (B, R // br, T // ct)

    kernel = functools.partial(_rglru_kernel, chunk_t=ct)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, ct, br), lambda bb, rr, tt: (bb, tt, rr)),
            pl.BlockSpec((1, ct, br), lambda bb, rr, tt: (bb, tt, rr)),
        ],
        out_specs=pl.BlockSpec((1, ct, br), lambda bb, rr, tt: (bb, tt, rr)),
        out_shape=jax.ShapeDtypeStruct((B, T, R), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, br), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
