"""jit'd dispatch wrappers over the Pallas kernels.

Backend selection:
  "tpu"       -- compiled Pallas (the default on a TPU)
  "interpret" -- Pallas interpret mode (CPU validation; used in tests)
  "jnp"       -- pure-jnp reference path (the default elsewhere)
Set globally with set_backend() or per-call with backend=... The models'
attention (models/layers.py) follows the global choice.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels import ref as _ref
from repro.kernels import flash_attention as _fa
from repro.kernels import decode_attention as _da
from repro.kernels import rglru as _rg
from repro.kernels import wkv6 as _wkv

_BACKEND: Optional[str] = None


def default_backend() -> str:
    if _BACKEND is not None:
        return _BACKEND
    return "tpu" if jax.default_backend() == "tpu" else "jnp"


def set_backend(backend: Optional[str]) -> None:
    global _BACKEND
    assert backend in (None, "tpu", "interpret", "jnp")
    _BACKEND = backend


def flash_attention(q, k, v, *, q_offset=0, window=0, q_offsets=None,
                    kv_lens=None, backend=None, **kw):
    b = backend or default_backend()
    if b == "jnp":
        return _ref.flash_attention_ref(q, k, v, q_offset=q_offset,
                                        window=window, q_offsets=q_offsets,
                                        kv_lens=kv_lens)
    return _fa.flash_attention(q, k, v, q_offset=q_offset, window=window,
                               q_offsets=q_offsets, kv_lens=kv_lens,
                               interpret=(b == "interpret"), **kw)


def chunk_attention(q, k_cache, v_cache, q_offsets, q_lens=None, *, window=0,
                    backend=None, **kw):
    """Chunked-prefill attention: q [B, C, H, hd] at per-sequence offsets
    against a contiguous KV cache (prefix+chunk causal mask). Per-row
    ``q_lens`` admits mixed batches -- prefill (q_len == C), decode
    (q_len == 1) and inactive (q_len == 0) rows in ONE dispatch, each
    paying only its own q/kv blocks."""
    b = backend or default_backend()
    if b == "jnp":
        return _ref.chunk_attention_ref(q, k_cache, v_cache, q_offsets,
                                        q_lens, window=window)
    return _da.chunk_attention(q, k_cache, v_cache, q_offsets, q_lens,
                               window=window, interpret=(b == "interpret"),
                               **kw)


def packed_chunk_attention(q, k_cache, v_cache, row_starts, q_offsets,
                           q_lens, *, window=0, backend=None, **kw):
    """Token-packed ragged chunk attention: q [Np, H, hd] concatenates all
    rows' chunk tokens on one axis (row b at packed positions
    ``row_starts[b] .. row_starts[b] + q_lens[b] - 1``) against [B, S, K, hd]
    caches -- the mixed dispatch pays for real tokens, not rows x chunk
    bucket. The Pallas path requires ``row_starts`` aligned to its block_q."""
    b = backend or default_backend()
    if b == "jnp":
        return _ref.packed_chunk_attention_ref(q, k_cache, v_cache,
                                               row_starts, q_offsets, q_lens,
                                               window=window)
    return _da.packed_chunk_attention(q, k_cache, v_cache, row_starts,
                                      q_offsets, q_lens, window=window,
                                      interpret=(b == "interpret"), **kw)


def packed_row_align() -> int:
    """Alignment of packed row starts that ``packed_chunk_attention`` needs
    on the backend in use: the Pallas kernel's q block (no block may
    straddle two rows), or 1 for the jnp path, which packs rows densely."""
    return 1 if default_backend() == "jnp" else _da.PACKED_BLOCK_Q


def decode_attention(q, k_cache, v_cache, seq_lens, *, window=0, backend=None, **kw):
    b = backend or default_backend()
    if b == "jnp":
        return _ref.decode_attention_ref(q, k_cache, v_cache, seq_lens, window=window)
    return _da.decode_attention(q, k_cache, v_cache, seq_lens, window=window,
                                interpret=(b == "interpret"), **kw)


def rglru(log_a, bx, h0, *, backend=None, **kw):
    b = backend or default_backend()
    if b == "jnp":
        return _ref.rglru_ref(log_a, bx, h0)
    return _rg.rglru(log_a, bx, h0, interpret=(b == "interpret"), **kw)


def wkv6(r, k, v, w, u, state, *, backend=None, **kw):
    b = backend or default_backend()
    if b == "jnp":
        return _ref.wkv6_ref(r, k, v, w, u, state)
    return _wkv.wkv6(r, k, v, w, u, state, interpret=(b == "interpret"), **kw)
