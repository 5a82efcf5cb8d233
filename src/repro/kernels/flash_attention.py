"""Pallas TPU flash attention (prefill / training): blocked online-softmax
causal attention with GQA head mapping and optional sliding window.

Grid: (batch, q_heads, num_q_blocks, num_kv_blocks); the kv dimension is
"arbitrary" (sequential) -- running max / sum / accumulator live in VMEM
scratch across kv steps. Fully-masked kv blocks above the causal diagonal are
skipped with pl.when, so FLOPs are ~half of the dense rectangle (the jnp
fallback pays the full rectangle; see EXPERIMENTS.md §Perf).

Chunked-prefill support: ``q_offsets`` / ``kv_lens`` give *per-sequence*
query offsets and valid KV lengths (SMEM scalars), so a ragged batch of
prefill continuations -- queries at ``q_offsets[b]..q_offsets[b]+Sq``
attending to keys ``0..q_offsets[b]+Sq`` -- stays on the fused path; blocks
past a sequence's kv_len are skipped, not just masked.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(off_ref, klen_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                  acc_ref, *, scale: float, bq: int, bk: int, nk: int,
                  window: int):
    b = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_offset = off_ref[b]                   # this sequence's chunk offset
    kv_len = klen_ref[b]                    # this sequence's valid kv length
    q_first = qi * bq + q_offset            # absolute position of q block row 0
    q_last = q_first + bq - 1
    k_first = ki * bk
    live = (k_first <= q_last) & (k_first < kv_len)
    if window:
        live &= (k_first + bk - 1) > (q_first - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)              # [bq, hd]
        k = k_ref[0, 0].astype(jnp.float32)              # [bk, hd]
        v = v_ref[0, 0].astype(jnp.float32)              # [bk, hd]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        qpos = q_first + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_first + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = (kpos <= qpos) & (kpos < kv_len)
        if window:
            mask &= kpos > (qpos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                              # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(p, v)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("q_offset", "window", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, q_offset: int = 0, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False, q_offsets=None, kv_lens=None):
    """q: [B, Sq, H, hd]; k, v: [B, Skv, K, hd] -> [B, Sq, H, hd].

    q_offset: static offset shared by the batch (prefill continuation).
    q_offsets: [B] int32 per-sequence offsets (chunked prefill of a ragged
    batch); overrides q_offset. kv_lens: [B] int32 valid KV lengths -- keys
    at or beyond kv_lens[b] are masked and fully-dead blocks skipped
    (defaults to Skv)."""
    B, Sq, H, hd = q.shape
    _, Skv, K, _ = k.shape
    assert H % K == 0
    bq = min(block_q, Sq)
    bk = min(block_k, Skv)
    Sq_pad = ((Sq + bq - 1) // bq) * bq
    Skv_pad = ((Skv + bk - 1) // bk) * bk
    # head-major layout for blocking
    qh = jnp.swapaxes(q, 1, 2)                            # [B, H, Sq, hd]
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    if Sq_pad != Sq:
        qh = jnp.pad(qh, ((0, 0), (0, 0), (0, Sq_pad - Sq), (0, 0)))
    if Skv_pad != Skv:
        kh = jnp.pad(kh, ((0, 0), (0, 0), (0, Skv_pad - Skv), (0, 0)))
        vh = jnp.pad(vh, ((0, 0), (0, 0), (0, Skv_pad - Skv), (0, 0)))
    nq, nk = Sq_pad // bq, Skv_pad // bk
    g = H // K
    if q_offsets is None:
        q_offsets = jnp.full((B,), q_offset, jnp.int32)
    if kv_lens is None:
        kv_lens = jnp.full((B,), Skv, jnp.int32)

    kernel = functools.partial(
        _flash_kernel, scale=1.0 / math.sqrt(hd), bq=bq, bk=bk, nk=nk,
        window=window)

    # per-sequence scalars ride in SMEM by scalar prefetch (whole [B]
    # arrays): Pallas TPU refuses a rank-1 (1,) SMEM block unless B == 1
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd),
                         lambda b, h, qi, ki, of, kl: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, qi, ki, of, kl: (b, h // g, ki, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, qi, ki, of, kl: (b, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda b, h, qi, ki, of, kl: (b, h, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Sq_pad, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q_offsets.astype(jnp.int32), kv_lens.astype(jnp.int32), qh, kh, vh)
    return jnp.swapaxes(out[:, :, :Sq], 1, 2)
