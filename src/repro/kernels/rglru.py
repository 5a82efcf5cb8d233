"""Pallas TPU RG-LRU kernel: fused linear recurrence h_t = a_t h_{t-1} + b_t
over time chunks held in VMEM, with the hidden state carried in scratch across
sequential grid steps. Width is blocked so the working set fits VMEM.

Grid: (batch_blocks, width_blocks, time_chunks); time sequential.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(log_a_ref, bx_ref, h0_ref, h_ref, hlast_ref, carry_ref, *,
                  bt: int, nt: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        carry_ref[...] = h0_ref[...].astype(jnp.float32)

    a = jnp.exp(log_a_ref[...].astype(jnp.float32))      # [bb, bt, bw]
    bx = bx_ref[...].astype(jnp.float32)
    h = carry_ref[...]                                    # [bb, bw]

    def step(t, carry):
        h, out = carry
        h = a[:, t] * h + bx[:, t]
        out = jax.lax.dynamic_update_slice_in_dim(out, h[:, None], t, axis=1)
        return h, out

    out0 = jnp.zeros_like(bx)
    h, out = jax.lax.fori_loop(0, bt, step, (h, out0))
    h_ref[...] = out.astype(h_ref.dtype)
    carry_ref[...] = h

    @pl.when(ti == nt - 1)
    def _final():
        hlast_ref[...] = h.astype(hlast_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_w", "block_t", "interpret"))
def rglru(log_a, bx, h0, *, block_b: int = 8, block_w: int = 512,
          block_t: int = 128, interpret: bool = False):
    """log_a, bx: [B, T, W] (log_a <= 0); h0: [B, W].
    Returns (h [B, T, W] fp32, h_last [B, W] fp32)."""
    B, T, W = log_a.shape
    bb = min(block_b, B)
    bw = min(block_w, W)
    bt = min(block_t, T)
    assert B % bb == 0 and W % bw == 0 and T % bt == 0, (B, T, W, bb, bt, bw)
    grid = (B // bb, W // bw, T // bt)

    kernel = functools.partial(_rglru_kernel, bt=bt, nt=grid[2])
    h, hlast = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, bt, bw), lambda b, w, t: (b, t, w)),
            pl.BlockSpec((bb, bt, bw), lambda b, w, t: (b, t, w)),
            pl.BlockSpec((bb, bw), lambda b, w, t: (b, w)),
        ],
        out_specs=[
            pl.BlockSpec((bb, bt, bw), lambda b, w, t: (b, t, w)),
            pl.BlockSpec((bb, bw), lambda b, w, t: (b, w)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, W), jnp.float32),
            jax.ShapeDtypeStruct((B, W), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bb, bw), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(log_a, bx, h0)
    return h, hlast
