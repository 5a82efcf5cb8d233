"""Shared neural-net substrate: norms, RoPE, attention (train/prefill chunked
causal + decode-over-cache), MLPs, embeddings.

Parameters are plain nested dicts of jnp arrays. Every init function returns
``(params, logical)`` where ``logical`` mirrors the structure with tuples of
logical axis names consumed by repro.distributed.sharding.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops

Params = Dict[str, Any]

# Cost-probe switch (launch/dryrun.py): XLA's cost_analysis counts while-loop
# bodies once, ignoring trip count, so probe compiles run every model scan
# fully unrolled. Production/runtime paths always keep SCAN_UNROLL=False.
SCAN_UNROLL = False


def xscan(body, init, xs, length=None):
    return jax.lax.scan(body, init, xs, length=length,
                        unroll=True if SCAN_UNROLL else 1)


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(rng, in_dim: int, out_dim: int, logical: Tuple[str, str],
               dtype=jnp.bfloat16, scale: Optional[float] = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(in_dim)
    w = jax.random.normal(rng, (in_dim, out_dim), dtype=jnp.float32) * scale
    return w.astype(dtype), logical


def embed_init(rng, vocab: int, d: int, dtype=jnp.bfloat16):
    w = jax.random.normal(rng, (vocab, d), dtype=jnp.float32) * 0.02
    return w.astype(dtype), ("vocab", "embed")


def norm_init(d: int, dtype=jnp.float32):
    return jnp.ones((d,), dtype=dtype), ("norm",)


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------

def rms_norm(x, w, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)
    return out.astype(dt)


def rope(x, positions, theta: float):
    """Rotary embedding. x: [..., S, H, hd]; positions: [..., S] (int)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32) * (math.log(theta) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs  # [..., S, half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    cos = cos[..., None, :]  # broadcast over heads: [..., S, 1, half]
    sin = sin[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _broadcast_kv(k, n_heads: int):
    """GQA: repeat kv heads to match query heads. k: [B, S, K, hd]."""
    K = k.shape[2]
    if K == n_heads:
        return k
    rep = n_heads // K
    return jnp.repeat(k, rep, axis=2)


# ---------------------------------------------------------------------------
# attention -- chunked causal (train / prefill) and decode-over-cache
# ---------------------------------------------------------------------------

def causal_attention(q, k, v, *, q_offset=0, window: int = 0, q_block: int = 512):
    """Causal (optionally sliding-window) attention.

    q: [B, Sq, H, hd]; k, v: [B, Skv, K, hd] (K divides H; GQA broadcast).
    q_offset: absolute position of q[0] relative to k[0] (prefill continuation).
    Memory-efficient: scans over Q blocks so scores never materialize at
    [Sq, Skv] full size. The backend in use (``kops.default_backend()``:
    the platform, or what ``kops.set_backend`` chose) picks the Pallas flash
    kernel (kernels/flash_attention.py) or this jnp path, with identical
    semantics.
    """
    if kops.default_backend() != "jnp":
        return kops.flash_attention(q, k, v, q_offset=q_offset, window=window)
    B, Sq, H, hd = q.shape
    Skv = k.shape[1]
    scale = 1.0 / math.sqrt(hd)
    kv_pos = jnp.arange(Skv)

    if Sq <= q_block:
        return _attn_block(q, k, v, q_offset + jnp.arange(Sq), kv_pos, scale, window)

    nb = Sq // q_block
    assert Sq % q_block == 0, f"Sq={Sq} not divisible by q_block={q_block}"
    qb = q.reshape(B, nb, q_block, H, hd).transpose(1, 0, 2, 3, 4)

    if window and window + q_block < Skv:
        # Sliding-window: each Q block only needs a [window + q_block] KV
        # slice -- keeps FLOPs O(S*window) instead of O(S^2).
        span = window + q_block

        def body_w(_, args):
            i, qblk = args
            q_start = q_offset + i * q_block
            start = jnp.clip(q_start + q_block - span, 0, Skv - span)
            ks = jax.lax.dynamic_slice_in_dim(k, start, span, axis=1)
            vs = jax.lax.dynamic_slice_in_dim(v, start, span, axis=1)
            qpos = q_start + jnp.arange(q_block)
            kpos = start + jnp.arange(span)
            return None, _attn_block(qblk, ks, vs, qpos, kpos, scale, window)

        _, out = xscan(body_w, None, (jnp.arange(nb), qb))
        return out.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, hd)

    def body(_, args):
        i, qblk = args
        qpos = q_offset + i * q_block + jnp.arange(q_block)
        return None, _attn_block(qblk, k, v, qpos, kv_pos, scale, window)

    _, out = xscan(body, None, (jnp.arange(nb), qb))
    return out.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, hd)


def _attn_block(q, k, v, q_pos, kv_pos, scale, window):
    # q: [B, sq, H, hd]; k/v: [B, Skv, K, hd] (KV heads NOT pre-repeated --
    # grouped-head einsum keeps the KV tensors at K heads and in bf16; the
    # repeat+fp32-copy variant forces GSPMD cache resharding, §Perf #1).
    B, sq, H, hd = q.shape
    K = k.shape[2]
    g = H // K
    qg = q.reshape(B, sq, K, g, hd)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                   preferred_element_type=jnp.float32) * scale
    mask = kv_pos[None, :] <= q_pos[:, None]
    if window:
        mask &= kv_pos[None, :] > (q_pos[:, None] - window)
    s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, sq, H, hd).astype(q.dtype)


def chunk_attention(q, k_cache, v_cache, q_offsets, *, q_lens=None,
                    window: int = 0):
    """Prefix+chunk causal attention (chunked prefill): query row i of
    sequence b sits at absolute position ``q_offsets[b] + i`` and attends to
    cache positions ``0 .. q_offsets[b] + i`` (optionally sliding-window).
    The chunk's own K/V must already be written into the cache
    (cache_write_chunk), so the prefix and the chunk share one fused pass.

    q: [B, C, H, hd]; caches: [B, S, K, hd]; q_offsets, q_lens: [B] int32.
    Returns [B, C, H, hd]. ``q_lens`` marks each row's valid chunk length,
    which is what lets ONE dispatch mix prefill rows (q_len == C), decode
    rows (q_len == 1 -- a degenerate chunk at the current position) and
    inactive rows (q_len == 0): the kernel skips dead q/kv blocks per row.
    Rows produce garbage at query positions past q_len (mask their K/V
    writes instead). Off the jnp backend this is the Pallas kernel
    (kernels/decode_attention.chunk_attention); the jnp path below has
    identical semantics for the valid rows.
    """
    if kops.default_backend() != "jnp":
        return kops.chunk_attention(q, k_cache, v_cache, q_offsets, q_lens,
                                    window=window)
    B, C, H, hd = q.shape
    S, K = k_cache.shape[1], k_cache.shape[2]
    g = H // K
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, C, K, g, hd)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale
    qpos = q_offsets[:, None] + jnp.arange(C)[None, :]        # [B, C]
    kpos = jnp.arange(S)[None, None, :]                       # [1, 1, S]
    mask = kpos <= qpos[:, :, None]                           # [B, C, S]
    if window:
        mask &= kpos > (qpos[:, :, None] - window)
    s = jnp.where(mask[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, C, H, hd).astype(q.dtype)


def packed_row_index(row_starts, q_lens, n_packed: int):
    """Row membership of each packed token position: ``row[p]`` is the row
    whose segment contains packed position p (``row_starts`` non-decreasing,
    row_starts[0] == 0), ``valid[p]`` marks positions inside a row's q_len
    (alignment gaps and tail padding are invalid), and ``off[p]`` is the
    position's offset within its row. Shared by packed attention, packed
    cache writes and the packed prefill bodies so the layout is decoded in
    exactly one place."""
    p_idx = jnp.arange(n_packed)
    row = jnp.searchsorted(row_starts, p_idx, side="right") - 1
    off = p_idx - row_starts[row]
    valid = off < q_lens[row]
    return row, off, valid


def packed_chunk_attention(q, k_cache, v_cache, row_starts, q_offsets,
                           q_lens, *, window: int = 0):
    """Token-packed ragged variant of ``chunk_attention``: q [Np, H, hd]
    concatenates every row's chunk tokens on ONE packed axis (row b occupies
    ``row_starts[b] .. row_starts[b] + q_lens[b] - 1``); caches stay
    [B, S, K, hd] with the chunk's K/V already written. FLOPs scale with the
    real tokens in the dispatch -- a decode row costs 1 packed slot, a
    7-token tail chunk costs 7 -- instead of rows x chunk bucket. The jnp
    path trades that FLOPs win for a gathered [Np, S, K, hd] read of the
    caches (fine at CPU research scale, 1 GiB per layer at yi-6b widths;
    the Pallas kernel, taken off the jnp backend, DMAs per block instead).
    Packed positions past a row's q_len produce zeros. Returns
    [Np, H, hd]; off the jnp backend ``row_starts`` must be aligned to
    ``kops.packed_row_align()``."""
    if kops.default_backend() != "jnp":
        return kops.packed_chunk_attention(q, k_cache, v_cache, row_starts,
                                           q_offsets, q_lens, window=window)
    Np, H, hd = q.shape
    B, S, K, _ = k_cache.shape
    g = H // K
    scale = 1.0 / math.sqrt(hd)
    row, _, valid = packed_row_index(row_starts, q_lens, Np)
    pos = q_offsets[row] + (jnp.arange(Np) - row_starts[row])
    kg = k_cache[row]                                  # [Np, S, K, hd]
    vg = v_cache[row]
    qg = q.reshape(Np, K, g, hd)
    s = jnp.einsum("nkgd,nskd->nkgs", qg, kg,
                   preferred_element_type=jnp.float32) * scale
    kpos = jnp.arange(S)[None, :]                      # [1, S]
    mask = kpos <= pos[:, None]
    if window:
        mask &= kpos > (pos[:, None] - window)
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("nkgs,nskd->nkgd", p, vg,
                     preferred_element_type=jnp.float32)
    out = out.reshape(Np, H, hd).astype(q.dtype)
    return jnp.where(valid[:, None, None], out, 0)


def decode_attention(q, k_cache, v_cache, seq_lens, *, window: int = 0):
    """One-token attention against a contiguous KV cache.

    q: [B, H, hd]; caches: [B, S, K, hd]; seq_lens: [B] (valid prefix length,
    including the token written for this step). Returns [B, H, hd]. Off the
    jnp backend this is the Pallas kernel.
    """
    if kops.default_backend() != "jnp":
        return kops.decode_attention(q, k_cache, v_cache, seq_lens,
                                     window=window)
    B, S, K, hd = k_cache.shape
    H = q.shape[1]
    g = H // K
    scale = 1.0 / math.sqrt(hd)
    # GQA via grouped-head einsum: no jnp.repeat of KV heads and no eager
    # fp32 copy of the cache -- either forces GSPMD to reshard (all-gather)
    # the seq-sharded cache every step (EXPERIMENTS.md §Perf hillclimb #1).
    qg = q.reshape(B, K, g, hd)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(S)[None, :]
    mask = pos < seq_lens[:, None]
    if window:
        mask &= pos >= (seq_lens[:, None] - window)
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# attention block (QKV + rope + out-proj) with KV-cache plumbing
# ---------------------------------------------------------------------------

def attn_init(rng, cfg, cross: bool = False) -> Tuple[Params, Params]:
    d, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(rng, 4)
    p, l = {}, {}
    p["wq"], l["wq"] = dense_init(ks[0], d, H * hd, ("embed", "heads"), cfg.param_dtype)
    p["wk"], l["wk"] = dense_init(ks[1], d, K * hd, ("embed", "kv"), cfg.param_dtype)
    p["wv"], l["wv"] = dense_init(ks[2], d, K * hd, ("embed", "kv"), cfg.param_dtype)
    p["wo"], l["wo"] = dense_init(ks[3], H * hd, d, ("heads", "embed"), cfg.param_dtype)
    return p, l


def attn_qkv(p, x, cfg, positions, rotary: bool = True):
    """x: [B, S, d] -> q [B,S,H,hd], k/v [B,S,K,hd] with RoPE applied."""
    B, S, _ = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(B, S, H, hd)
    k = (x @ p["wk"]).reshape(B, S, K, hd)
    v = (x @ p["wv"]).reshape(B, S, K, hd)
    if rotary:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def attn_out(p, o):
    B, S = o.shape[:2]
    return o.reshape(B, S, -1) @ p["wo"]


def cache_write_token(cache, new, seq_lens):
    """Write one token per sequence into a [B, S, K, hd] cache at positions
    seq_lens. Expressed as a masked elementwise update, NOT a scatter: GSPMD
    cannot partition a scatter across the sequence-sharded cache axis and
    falls back to full rematerialization (replicating the cache through
    collectives every step) -- see EXPERIMENTS.md §Perf hillclimb #1.
    cache: [B, S, K, hd]; new: [B, K, hd]; seq_lens: [B]."""
    S = cache.shape[1]
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, S, 1, 1), 1)
    hit = pos == seq_lens[:, None, None, None]
    return jnp.where(hit, new[:, None].astype(cache.dtype), cache)


def cache_write_chunk(cache, new, offsets, lengths):
    """Write a chunk of tokens per sequence into a [B, S, K, hd] cache:
    ``new[b, :lengths[b]]`` lands at ``cache[b, offsets[b] : offsets[b] +
    lengths[b]]``. Rows with ``lengths[b] == 0`` are untouched bit-for-bit,
    so one chunk dispatch can mix prefill, decode (C == 1: the degenerate
    chunk the unified serve path decodes through) and idle rows. Expressed
    as a masked gather, not a scatter, for the same GSPMD reason as
    cache_write_token. cache: [B, S, K, hd]; new: [B, C, K, hd];
    offsets, lengths: [B] int32."""
    S, C = cache.shape[1], new.shape[1]
    pos = jnp.arange(S)[None, :]                       # [1, S]
    if C == 1:
        # single-token chunk: the source row is new[:, 0] everywhere, so the
        # full-width gather below would only materialize copies --
        # cache_write_token's broadcast form with the length mask folded in
        hit = (pos == offsets[:, None]) & (lengths[:, None] > 0)
        return jnp.where(hit[:, :, None, None], new.astype(cache.dtype),
                         cache)
    idx = pos - offsets[:, None]                       # chunk-relative index
    hit = (idx >= 0) & (idx < lengths[:, None])        # [B, S]
    src = jnp.take_along_axis(new, jnp.clip(idx, 0, C - 1)[:, :, None, None],
                              axis=1)
    return jnp.where(hit[:, :, None, None], src.astype(cache.dtype), cache)


def cache_write_packed(cache, new, rows, pos, valid):
    """Scatter packed tokens into a [B, S, K, hd] cache: packed token p
    (``new[p]``) lands at ``cache[rows[p], pos[p]]``; positions with
    ``valid[p] == False`` (alignment gaps, tail padding, length-0 rows) are
    dropped. Unlike cache_write_chunk this IS a scatter -- valid (row, pos)
    pairs are unique so it is deterministic, and the serving cache is
    unsharded, so the GSPMD scatter caveat of cache_write_token does not
    bite; a sequence-sharded training cache should keep the masked-gather
    forms. cache: [B, S, K, hd]; new: [Np, K, hd]; rows/pos/valid: [Np]."""
    B = cache.shape[0]
    wrows = jnp.where(valid, rows, B)          # out-of-bounds -> dropped
    return cache.at[wrows, pos].set(new.astype(cache.dtype), mode="drop")


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def mlp_init(rng, cfg, d_ff: Optional[int] = None) -> Tuple[Params, Params]:
    d = cfg.d_model
    ff = d_ff or cfg.d_ff
    p, l = {}, {}
    if cfg.activation in ("swiglu", "geglu"):
        k1, k2, k3 = jax.random.split(rng, 3)
        p["wi"], l["wi"] = dense_init(k1, d, ff, ("embed", "mlp"), cfg.param_dtype)
        p["wg"], l["wg"] = dense_init(k2, d, ff, ("embed", "mlp"), cfg.param_dtype)
        p["wo"], l["wo"] = dense_init(k3, ff, d, ("mlp", "embed"), cfg.param_dtype)
    else:  # squared_relu (nemotron)
        k1, k2 = jax.random.split(rng, 2)
        p["wi"], l["wi"] = dense_init(k1, d, ff, ("embed", "mlp"), cfg.param_dtype)
        p["wo"], l["wo"] = dense_init(k2, ff, d, ("mlp", "embed"), cfg.param_dtype)
    return p, l


def mlp_apply(p, x, activation: str):
    if activation == "swiglu":
        return (jax.nn.silu(x @ p["wg"]) * (x @ p["wi"])) @ p["wo"]
    if activation == "geglu":  # gemma-style gated GeLU
        return (jax.nn.gelu(x @ p["wg"]) * (x @ p["wi"])) @ p["wo"]
    h = jax.nn.relu(x @ p["wi"])
    return jnp.square(h) @ p["wo"]
