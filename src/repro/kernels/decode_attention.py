"""Pallas TPU attention over a contiguous KV cache with per-sequence state:

* ``chunk_attention`` -- a chunk of C new tokens per sequence at absolute
  positions ``q_offsets[b] .. q_offsets[b]+C-1`` attending to cache positions
  ``0 .. q_offsets[b]+i`` (the prefix+chunk causal mask of chunked prefill;
  optional sliding window).
* ``decode_attention`` -- the C == 1 specialization (the serve_step hot loop),
  expressed through the same kernel.

Mixed prefill+decode batches: per-row ``q_lens`` makes one dispatch carry
prefill rows (q_len == C), decode rows (q_len == 1 -- a degenerate chunk at
the row's current position) and inactive rows (q_len == 0) together. Work is
skipped per row: q blocks at or beyond a row's q_len are dead, and kv blocks
are bounded by the row's own valid end (``q_offset + q_len``), so a decode
row riding in a C=128 chunk dispatch costs one row's context, not the
chunk's maximum.

Grid: (batch, q_heads, num_q_blocks, num_kv_blocks); kv dimension sequential
with online softmax carried in VMEM scratch. KV blocks entirely above the
causal diagonal for a sequence -- and q blocks entirely beyond its valid
chunk length -- are skipped, so FLOPs scale with the *actual* context length,
not the cache allocation. Fully-skipped q blocks (rows >= q_len) finalize to
zeros; rows beyond q_len inside a live block produce garbage (callers mask
their K/V writes and ignore their logits either way).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30

# packed_chunk_attention's q block: callers align packed row starts to it
PACKED_BLOCK_Q = 8


def _chunk_kernel(off_ref, qlen_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, scale: float, bq: int, bk: int,
                  nk: int, window: int):
    b = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_off = off_ref[b]                      # absolute position of chunk row 0
    q_len = qlen_ref[b]                     # valid rows in this chunk
    q_first = q_off + qi * bq               # absolute position of block row 0
    k_first = ki * bk
    # per-row block skip: the block's last VALID row position bounds the kv
    # span, so a q_len==1 decode row in a wide chunk pays its own context,
    # not the chunk's maximum; blocks wholly past q_len are dead
    q_last_valid = q_off + jnp.minimum((qi + 1) * bq, q_len) - 1
    live = (k_first <= q_last_valid) & (qi * bq < q_len)
    if window:
        live &= (k_first + bk - 1) > (q_first - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)              # [bq, hd]
        k = k_ref[0, 0].astype(jnp.float32)              # [bk, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        qpos = q_first + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_first + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos <= qpos
        if window:
            mask &= kpos > (qpos - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                              # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
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
    jax.jit, static_argnames=("window", "block_q", "block_k", "interpret"))
def chunk_attention(q, k_cache, v_cache, q_offsets, q_lens=None, *,
                    window: int = 0, block_q: int = 128, block_k: int = 256,
                    interpret: bool = False):
    """q: [B, C, H, hd]; caches [B, S, K, hd]; q_offsets [B] (absolute
    position of each sequence's chunk row 0; the chunk's own K/V must already
    be written into the cache). q_lens [B] optionally gives the valid rows
    per chunk: q blocks at or past a row's q_len are skipped (zeros) and kv
    blocks are bounded by the row's valid end, so mixed batches of prefill
    (q_len == C), decode (q_len == 1) and inactive (q_len == 0) rows each pay
    their own cost. Rows past q_len inside a live q block are garbage.
    Returns [B, C, H, hd]."""
    B, C, H, hd = q.shape
    _, S, K, _ = k_cache.shape
    assert H % K == 0
    bq = min(block_q, C)
    bk = min(block_k, S)
    C_pad = ((C + bq - 1) // bq) * bq
    S_pad = ((S + bk - 1) // bk) * bk
    qh = jnp.swapaxes(q, 1, 2)                           # [B, H, C, hd]
    kh = jnp.swapaxes(k_cache, 1, 2)                     # [B, K, S, hd]
    vh = jnp.swapaxes(v_cache, 1, 2)
    if C_pad != C:
        qh = jnp.pad(qh, ((0, 0), (0, 0), (0, C_pad - C), (0, 0)))
    if S_pad != S:
        kh = jnp.pad(kh, ((0, 0), (0, 0), (0, S_pad - S), (0, 0)))
        vh = jnp.pad(vh, ((0, 0), (0, 0), (0, S_pad - S), (0, 0)))
    nq, nk = C_pad // bq, S_pad // bk
    g = H // K
    if q_lens is None:
        q_lens = jnp.full((B,), C, jnp.int32)

    kernel = functools.partial(
        _chunk_kernel, scale=1.0 / math.sqrt(hd), bq=bq, bk=bk, nk=nk,
        window=window)

    # per-row scalars ride in SMEM by scalar prefetch (whole [B] arrays):
    # Pallas TPU refuses a rank-1 (1,) SMEM block that is neither the whole
    # array nor a multiple of 128
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd),
                         lambda b, h, qi, ki, of, ql: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, qi, ki, of, ql: (b, h // g, ki, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, qi, ki, of, ql: (b, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda b, h, qi, ki, of, ql: (b, h, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, C_pad, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q_offsets.astype(jnp.int32), q_lens.astype(jnp.int32), qh, kh, vh)
    return jnp.swapaxes(out[:, :, :C], 1, 2)


def _packed_chunk_kernel(brow_ref, starts_ref, offs_ref, qlens_ref,
                         q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                         *, scale: float, bq: int, bk: int, nk: int,
                         window: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    r = brow_ref[qi]                  # row owning this q block (blocks never
                                      # span rows: row_starts are bq-aligned)
    q_len = qlens_ref[r]
    q_off = offs_ref[r]
    blk_off = qi * bq - starts_ref[r]   # block token 0's offset within row r
    q_first = q_off + blk_off           # ... and its absolute position
    k_first = ki * bk
    # dead blocks: alignment-gap/tail-padding tokens (blk_off >= q_len) and
    # kv blocks past the block's last valid position -- identical skip rule
    # to _chunk_kernel, with the row picked per block instead of per batch
    q_last_valid = q_off + jnp.minimum(blk_off + bq, q_len) - 1
    live = (k_first <= q_last_valid) & (blk_off < q_len)
    if window:
        live &= (k_first + bk - 1) > (q_first - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                 # [bq, hd]
        k = k_ref[0, 0].astype(jnp.float32)              # [bk, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        qpos = q_first + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_first + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos <= qpos
        if window:
            mask &= kpos > (qpos - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                              # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(p, v)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "block_q", "block_k", "interpret"))
def packed_chunk_attention(q, k_cache, v_cache, row_starts, q_offsets,
                           q_lens, *, window: int = 0,
                           block_q: int = PACKED_BLOCK_Q,
                           block_k: int = 256, interpret: bool = False):
    """Token-packed ragged chunk attention: q [Np, H, hd] concatenates every
    row's chunk tokens on ONE axis (row b occupies packed positions
    ``row_starts[b] .. row_starts[b] + q_lens[b] - 1``); caches stay
    [B, S, K, hd]. Each q block belongs to exactly one row -- callers must
    align ``row_starts`` to ``block_q`` (pad the packed axis between rows) --
    and the row index is scalar-prefetched per block so the k/v BlockSpec
    DMAs that row's cache pages only: FLOPs and bytes scale with the real
    tokens in the dispatch, not rows x chunk-bucket. Packed positions past a
    row's q_len (alignment gaps, tail padding) finalize to zeros when their
    whole block is dead and garbage inside a live block, exactly like
    ``chunk_attention``'s dead rows. Returns [Np, H, hd]."""
    Np, H, hd = q.shape
    B, S, K, _ = k_cache.shape
    assert H % K == 0
    bq = min(block_q, Np)
    bk = min(block_k, S)
    Np_pad = ((Np + bq - 1) // bq) * bq
    S_pad = ((S + bk - 1) // bk) * bk
    qh = jnp.swapaxes(q, 0, 1)                           # [H, Np, hd]
    kh = jnp.swapaxes(k_cache, 1, 2)                     # [B, K, S, hd]
    vh = jnp.swapaxes(v_cache, 1, 2)
    if Np_pad != Np:
        qh = jnp.pad(qh, ((0, 0), (0, Np_pad - Np), (0, 0)))
    if S_pad != S:
        kh = jnp.pad(kh, ((0, 0), (0, 0), (0, S_pad - S), (0, 0)))
        vh = jnp.pad(vh, ((0, 0), (0, 0), (0, S_pad - S), (0, 0)))
    nq, nk = Np_pad // bq, S_pad // bk
    g = H // K
    starts = row_starts.astype(jnp.int32)
    # row of each q block's first token; tail-padding blocks resolve to the
    # last row and die on the blk_off >= q_len check inside the kernel
    brow = (jnp.searchsorted(starts, jnp.arange(nq, dtype=jnp.int32) * bq,
                             side="right") - 1).astype(jnp.int32)

    kernel = functools.partial(
        _packed_chunk_kernel, scale=1.0 / math.sqrt(hd), bq=bq, bk=bk, nk=nk,
        window=window)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd),
                         lambda h, qi, ki, br, st, of, ql: (h, qi, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda h, qi, ki, br, st, of, ql:
                         (br[qi], h // g, ki, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda h, qi, ki, br, st, of, ql:
                         (br[qi], h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd),
                               lambda h, qi, ki, br, st, of, ql: (h, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((H, Np_pad, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(brow, starts, q_offsets.astype(jnp.int32), q_lens.astype(jnp.int32),
      qh, kh, vh)
    return jnp.swapaxes(out[:, :Np], 0, 1)


def decode_attention(q, k_cache, v_cache, seq_lens, *, window: int = 0,
                     block_k: int = 256, interpret: bool = False):
    """q: [B, H, hd]; caches [B, S, K, hd]; seq_lens [B] (valid prefix length,
    including the token written for this step) -> [B, H, hd]. The one-token
    case of chunk_attention: a single query at position seq_len - 1."""
    out = chunk_attention(q[:, None], k_cache, v_cache,
                          (seq_lens - 1).astype(jnp.int32),
                          window=window, block_q=1, block_k=block_k,
                          interpret=interpret)
    return out[:, 0]
