"""Pallas TPU WKV6 kernel (RWKV-6 "Finch" recurrence): one time chunk per
sequential grid step, chunk math in matmul form (MXU-friendly), per-head state
matrix carried in VMEM scratch.

  S_t = diag(w_t) S_{t-1} + k_t^T v_t
  out_t = r_t S_{t-1} + (r_t*u . k_t) v_t

Grid: (batch, heads, time_chunks); time sequential. Decay w must be
pre-clamped (models/rwkv6.py) so within-chunk cumprod ratios stay in fp32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, sout_ref,
                 s_ref, *, ct: int, nt: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        s_ref[...] = s0_ref[0, 0].astype(jnp.float32)

    f32 = jnp.float32
    rb = r_ref[0, 0].astype(f32)                  # [C, hd]
    kb = k_ref[0, 0].astype(f32)
    vb = v_ref[0, 0].astype(f32)
    wb = w_ref[0, 0].astype(f32)
    u = u_ref[0].astype(f32)                      # [hd]
    S = s_ref[...]                                # [hd, hd]

    c = jnp.cumprod(wb, axis=0)                   # [C, hd]
    c_prev = jnp.concatenate([jnp.ones_like(c[:1]), c[:-1]], axis=0)
    rq = rb * c_prev
    kq = kb / c
    A = jax.lax.dot_general(rq, kq, (((1,), (1,)), ((), ())))  # [C, C]
    tri = jnp.tril(jnp.ones((ct, ct), f32), k=-1)
    A = A * tri
    diag = jnp.sum(rb * u[None, :] * kb, axis=1)
    idx = jax.lax.broadcasted_iota(jnp.int32, (ct, ct), 0)
    jdx = jax.lax.broadcasted_iota(jnp.int32, (ct, ct), 1)
    A = jnp.where(idx == jdx, diag[:, None], A)
    out = jax.lax.dot(A, vb) + jax.lax.dot(rq, S)

    c_end = c[-1]
    S_new = c_end[:, None] * S + jax.lax.dot_general(
        kb * (c_end[None, :] / c), vb, (((0,), (0,)), ((), ())))
    s_ref[...] = S_new
    o_ref[0, 0] = out.astype(o_ref.dtype)

    @pl.when(ti == nt - 1)
    def _final():
        sout_ref[0, 0] = S_new.astype(sout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r, k, v, w, u, state, *, chunk: int = 32, interpret: bool = False):
    """r,k,v,w: [B, T, H, hd]; u: [H, hd]; state: [B, H, hd, hd].
    Returns (out [B, T, H, hd] fp32, new_state fp32)."""
    B, T, H, hd = r.shape
    ct = min(chunk, T)
    assert T % ct == 0, (T, ct)
    nt = T // ct
    # head-major [B, H, T, hd]
    tr = lambda x: jnp.swapaxes(x, 1, 2)
    rh, kh, vh, wh = tr(r), tr(k), tr(v), tr(w)

    kernel = functools.partial(_wkv6_kernel, ct=ct, nt=nt)
    out, s_out = pl.pallas_call(
        kernel,
        grid=(B, H, nt),
        in_specs=[
            pl.BlockSpec((1, 1, ct, hd), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, ct, hd), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, ct, hd), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, ct, hd), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, hd), lambda b, h, t: (h, 0)),
            pl.BlockSpec((1, 1, hd, hd), lambda b, h, t: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, ct, hd), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, hd, hd), lambda b, h, t: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, H, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(rh, kh, vh, wh, u, state)
    return jnp.swapaxes(out, 1, 2), s_out
