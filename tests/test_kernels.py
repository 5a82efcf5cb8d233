"""Per-kernel validation: Pallas (interpret=True) vs the pure-jnp oracles,
swept over shapes and dtypes, plus hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.kernels import ops, ref

jax.config.update("jax_enable_x64", False)


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


TOL = {jnp.float32: 2e-4, jnp.bfloat16: 2e-2}


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Sq,Skv,H,K,hd,bq,bk", [
    (1, 64, 64, 2, 2, 16, 32, 32),
    (2, 96, 96, 4, 2, 32, 32, 32),
    (2, 128, 128, 4, 1, 64, 64, 32),   # MQA
    (1, 100, 100, 2, 2, 16, 32, 32),   # ragged vs block size
])
def test_flash_attention_matches_ref(dtype, B, Sq, Skv, H, K, hd, bq, bk):
    ks = jax.random.split(jax.random.key(0), 3)
    q = _rand(ks[0], (B, Sq, H, hd), dtype)
    k = _rand(ks[1], (B, Skv, K, hd), dtype)
    v = _rand(ks[2], (B, Skv, K, hd), dtype)
    out = ops.flash_attention(q, k, v, backend="interpret", block_q=bq, block_k=bk)
    exp = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("window", [16, 40])
def test_flash_attention_window(window):
    ks = jax.random.split(jax.random.key(1), 3)
    q = _rand(ks[0], (2, 96, 4, 32), jnp.float32)
    k = _rand(ks[1], (2, 96, 2, 32), jnp.float32)
    v = _rand(ks[2], (2, 96, 2, 32), jnp.float32)
    out = ops.flash_attention(q, k, v, window=window, backend="interpret",
                              block_q=32, block_k=32)
    exp = ref.flash_attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(out, exp, atol=2e-4, rtol=2e-4)


def test_flash_attention_jnp_backend_equals_ref():
    ks = jax.random.split(jax.random.key(2), 3)
    q = _rand(ks[0], (1, 64, 2, 16), jnp.float32)
    k = _rand(ks[1], (1, 64, 2, 16), jnp.float32)
    v = _rand(ks[2], (1, 64, 2, 16), jnp.float32)
    out = ops.flash_attention(q, k, v, backend="jnp")
    exp = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(out, exp, atol=0, rtol=0)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,K,hd,bk", [
    (2, 64, 2, 2, 16, 32),
    (3, 130, 4, 2, 32, 64),
    (1, 257, 4, 1, 64, 64),
])
def test_decode_attention_matches_ref(dtype, B, S, H, K, hd, bk):
    ks = jax.random.split(jax.random.key(3), 3)
    q = _rand(ks[0], (B, H, hd), dtype)
    kc = _rand(ks[1], (B, S, K, hd), dtype)
    vc = _rand(ks[2], (B, S, K, hd), dtype)
    sl = jnp.asarray(np.linspace(1, S, B).astype(np.int32))
    out = ops.decode_attention(q, kc, vc, sl, backend="interpret", block_k=bk)
    exp = ref.decode_attention_ref(q, kc, vc, sl)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@given(seq_lens=st.lists(st.integers(1, 96), min_size=2, max_size=2),
       window=st.sampled_from([0, 24]))
@settings(max_examples=10, deadline=None)
def test_decode_attention_property(seq_lens, window):
    """Property: decode attention over a cache only depends on the first
    seq_len positions (garbage beyond is masked)."""
    ks = jax.random.split(jax.random.key(4), 4)
    B, S, H, K, hd = 2, 96, 2, 1, 16
    q = _rand(ks[0], (B, H, hd), jnp.float32)
    kc = _rand(ks[1], (B, S, K, hd), jnp.float32)
    vc = _rand(ks[2], (B, S, K, hd), jnp.float32)
    sl = jnp.asarray(seq_lens, jnp.int32)
    base = ops.decode_attention(q, kc, vc, sl, window=window, backend="interpret")
    # corrupt cache beyond each sequence's length -- output must not change
    noise = _rand(ks[3], (B, S, K, hd), jnp.float32) * 100
    mask = (jnp.arange(S)[None, :, None, None] >= sl[:, None, None, None])
    kc2 = jnp.where(mask, noise, kc)
    vc2 = jnp.where(mask, noise, vc)
    out = ops.decode_attention(q, kc2, vc2, sl, window=window, backend="interpret")
    np.testing.assert_allclose(base, out, atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# chunk attention (chunked prefill: prefix+chunk causal mask)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [0, 24])
@pytest.mark.parametrize("B,C,S,H,K,hd,bq,bk", [
    (2, 24, 96, 4, 2, 16, 8, 32),
    (3, 32, 130, 4, 1, 32, 16, 64),    # MQA, ragged cache vs block size
])
def test_chunk_attention_matches_ref(dtype, window, B, C, S, H, K, hd, bq, bk):
    ks = jax.random.split(jax.random.key(9), 3)
    q = _rand(ks[0], (B, C, H, hd), dtype)
    kc = _rand(ks[1], (B, S, K, hd), dtype)
    vc = _rand(ks[2], (B, S, K, hd), dtype)
    offs = jnp.asarray(np.linspace(0, S - C, B).astype(np.int32))
    out = ops.chunk_attention(q, kc, vc, offs, window=window,
                              backend="interpret", block_q=bq, block_k=bk)
    exp = ref.chunk_attention_ref(q, kc, vc, offs, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


def test_chunk_attention_one_token_equals_decode():
    """decode_attention is the C == 1 case of chunk_attention: a query at
    position seq_len - 1 over the same cache."""
    ks = jax.random.split(jax.random.key(10), 3)
    B, S, H, K, hd = 2, 96, 4, 2, 16
    q = _rand(ks[0], (B, H, hd), jnp.float32)
    kc = _rand(ks[1], (B, S, K, hd), jnp.float32)
    vc = _rand(ks[2], (B, S, K, hd), jnp.float32)
    sl = jnp.array([7, 90], jnp.int32)
    dec = ops.decode_attention(q, kc, vc, sl, backend="interpret")
    chk = ops.chunk_attention(q[:, None], kc, vc, sl - 1,
                              backend="interpret")[:, 0]
    np.testing.assert_allclose(dec, chk, atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("window", [0, 24])
def test_chunk_attention_mixed_row_lengths(window):
    """Mixed prefill+decode batches: per-row q_lens lets one dispatch carry a
    full prefill row (q_len == C), a decode row (q_len == 1 -- the degenerate
    chunk) and an inactive row (q_len == 0). With block_q=1 every dead row is
    a fully-skipped q block, so kernel output must equal the ref (which
    zeroes rows at/past q_len) bit-for-bit across the whole tensor, and the
    valid rows must match a q_lens-free dispatch exactly."""
    ks = jax.random.split(jax.random.key(21), 3)
    B, C, S, H, K, hd = 3, 16, 96, 4, 2, 16
    q = _rand(ks[0], (B, C, H, hd), jnp.float32)
    kc = _rand(ks[1], (B, S, K, hd), jnp.float32)
    vc = _rand(ks[2], (B, S, K, hd), jnp.float32)
    offs = jnp.array([10, 40, 0], jnp.int32)
    qlens = jnp.array([C, 1, 0], jnp.int32)
    out = ops.chunk_attention(q, kc, vc, offs, qlens, window=window,
                              backend="interpret", block_q=1, block_k=32)
    exp = ref.chunk_attention_ref(q, kc, vc, offs, qlens, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               atol=TOL[jnp.float32], rtol=TOL[jnp.float32])
    # dead rows really are zeros (skipped blocks finalize to 0)
    assert np.all(np.asarray(out)[2] == 0)
    assert np.all(np.asarray(out)[1, 1:] == 0)
    # valid rows unchanged by the q_lens skip
    base = ops.chunk_attention(q, kc, vc, offs, window=window,
                               backend="interpret", block_q=1, block_k=32)
    np.testing.assert_array_equal(np.asarray(out)[0], np.asarray(base)[0])
    np.testing.assert_array_equal(np.asarray(out)[1, 0],
                                  np.asarray(base)[1, 0])


@pytest.mark.parametrize("window", [0, 24])
def test_packed_chunk_attention_matches_ref(window):
    """Token-packed ragged dispatch: rows of mixed length (full chunk,
    decode token, inactive, unaligned tail) concatenated on one packed axis
    with block_q-aligned row starts; Pallas (interpret) vs the packed ref."""
    ks = jax.random.split(jax.random.key(22), 3)
    B, S, H, K, hd, bq = 4, 96, 4, 2, 16, 8
    qlens = np.array([16, 1, 0, 5], np.int32)
    starts = np.zeros(B, np.int32)
    cur = 0
    for b in range(B):                    # align row segments to block_q
        starts[b] = cur
        cur += -(-int(qlens[b]) // bq) * bq
    Np = max(cur, bq)
    q = _rand(ks[0], (Np, H, hd), jnp.float32)
    kc = _rand(ks[1], (B, S, K, hd), jnp.float32)
    vc = _rand(ks[2], (B, S, K, hd), jnp.float32)
    offs = jnp.array([10, 40, 0, 63], jnp.int32)
    out = ops.packed_chunk_attention(
        q, kc, vc, jnp.asarray(starts), offs, jnp.asarray(qlens),
        window=window, backend="interpret", block_q=bq, block_k=32)
    exp = ref.packed_chunk_attention_ref(
        q, kc, vc, jnp.asarray(starts), offs, jnp.asarray(qlens),
        window=window)
    # contract: live packed positions match; alignment-gap slots inside a
    # live block may hold garbage in the kernel (the unpack discards them)
    # and are zeros in the ref
    gap = np.ones(Np, bool)
    for b in range(B):
        gap[starts[b]:starts[b] + qlens[b]] = False
    np.testing.assert_allclose(np.asarray(out, np.float32)[~gap],
                               np.asarray(exp, np.float32)[~gap],
                               atol=TOL[jnp.float32], rtol=TOL[jnp.float32])
    assert np.all(np.asarray(exp)[gap] == 0)


def test_packed_equals_padded_chunk_rows():
    """The packed layout is a re-indexing, not a different computation:
    each row's packed slice must match the corresponding padded
    chunk_attention row over the same cache. The two fp32 references
    contract in different einsum orders, so XLA may round differently:
    they agree to fp32 rounding (1e-6), not bit for bit."""
    ks = jax.random.split(jax.random.key(23), 3)
    B, C, S, H, K, hd = 3, 16, 96, 4, 2, 16
    qlens = jnp.array([C, 1, 7], jnp.int32)
    starts = jnp.array([0, C, C + 1], jnp.int32)       # dense, align=1
    Np = C + 1 + 7
    qpad = _rand(ks[0], (B, C, H, hd), jnp.float32)
    kc = _rand(ks[1], (B, S, K, hd), jnp.float32)
    vc = _rand(ks[2], (B, S, K, hd), jnp.float32)
    offs = jnp.array([10, 40, 0], jnp.int32)
    qflat = jnp.concatenate([qpad[b, :qlens[b]] for b in range(B)])
    assert qflat.shape[0] == Np
    packed = ref.packed_chunk_attention_ref(qflat, kc, vc, starts, offs,
                                            qlens)
    padded = ref.chunk_attention_ref(qpad, kc, vc, offs, qlens)
    for b in range(B):
        np.testing.assert_allclose(
            np.asarray(packed)[starts[b]:starts[b] + qlens[b]],
            np.asarray(padded)[b, :qlens[b]], atol=1e-6, rtol=1e-6)


def test_chunk_attention_ignores_stale_cache_tail():
    """Property: output only depends on cache positions <= each query's
    absolute position (stale garbage beyond the written prefix is masked)."""
    ks = jax.random.split(jax.random.key(11), 4)
    B, C, S, H, K, hd = 2, 16, 64, 2, 1, 16
    q = _rand(ks[0], (B, C, H, hd), jnp.float32)
    kc = _rand(ks[1], (B, S, K, hd), jnp.float32)
    vc = _rand(ks[2], (B, S, K, hd), jnp.float32)
    offs = jnp.array([3, 40], jnp.int32)
    base = ops.chunk_attention(q, kc, vc, offs, backend="interpret")
    noise = _rand(ks[3], (B, S, K, hd), jnp.float32) * 100
    dead = jnp.arange(S)[None, :, None, None] >= (offs + C)[:, None, None, None]
    out = ops.chunk_attention(q, jnp.where(dead, noise, kc),
                              jnp.where(dead, noise, vc), offs,
                              backend="interpret")
    np.testing.assert_allclose(base, out, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("H,K,window,layers", [
    pytest.param(H, K, w, None, id=f"{H}-{K}-{w}")
    for H, K in [(32, 8), (32, 4), (8, 8), (32, 1)] for w in (0, 40)] + [
    pytest.param(H, K, 0, 3, id=f"{H}-{K}-0-stack3")
    for H, K in [(32, 8), (32, 4), (32, 1)]])
def test_one_token_chunk_reads_cache_in_place(H, K, window, layers):
    """The C == 1 kernel (grid over rows and kv blocks of the [B, S, K, hd]
    cache as it lies, all heads per block, dead blocks not fetched) against
    both references, at the block size it picks for the shape: queries at
    positions 0, bk-1, bk and S-1, a cache whose S is not a multiple of bk,
    and an inactive row. Garbage in the cache outside each row's window --
    past its position, or before its sliding window -- changes nothing.
    With ``layers``, the caches are [L, B, S, K, hd] stacks and the kernel
    reads layer 0, a middle layer and L-1 by index: each equals the
    references on that layer, and garbage in the other layers changes
    nothing."""
    from repro.kernels import decode_attention as da
    hd = 128
    bk = da.decode_block_k(1 << 20, K, hd, 4)
    S = bk + bk // 2 + 3
    assert da.decode_block_k(S, K, hd, 4) == bk and S % bk
    offs = jnp.array([0, bk - 1, bk, S - 1, S // 2], jnp.int32)
    qlens = jnp.array([1, 1, 1, 1, 0], jnp.int32)
    B = offs.shape[0]
    shape = (B, S, K, hd) if layers is None else (layers, B, S, K, hd)
    ks = jax.random.split(jax.random.key(24), 4)
    q = _rand(ks[0], (B, 1, H, hd), jnp.float32)
    kc = _rand(ks[1], shape, jnp.float32)
    vc = _rand(ks[2], shape, jnp.float32)
    noise = _rand(ks[3], shape, jnp.float32) * 100
    pos = jnp.arange(S)[None, :, None, None]
    dead = pos > offs[:, None, None, None]
    if window:
        dead |= pos <= (offs - window)[:, None, None, None]
    for layer in [None] if layers is None else [0, layers // 2, layers - 1]:
        def attend(k, v, layer=layer):
            return ops.chunk_attention(q, k, v, offs, qlens, window=window,
                                       layer=layer, backend="interpret")
        kl, vl, off_layer = kc, vc, dead
        if layer is not None:
            kl, vl = kc[layer], vc[layer]
            off_layer = dead[None] | (jnp.arange(layers) != layer)[
                :, None, None, None, None]
        out = attend(kc, vc)
        exp = ref.chunk_attention_ref(q, kl, vl, offs, qlens, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   atol=TOL[jnp.float32],
                                   rtol=TOL[jnp.float32])
        dec = ref.decode_attention_ref(q[:, 0], kl, vl, offs + 1,
                                       window=window)
        np.testing.assert_allclose(np.asarray(out)[:4, 0],
                                   np.asarray(dec)[:4],
                                   atol=TOL[jnp.float32],
                                   rtol=TOL[jnp.float32])
        assert np.all(np.asarray(out)[4] == 0)
        stale = attend(jnp.where(off_layer, noise, kc),
                       jnp.where(off_layer, noise, vc))
        np.testing.assert_array_equal(np.asarray(stale), np.asarray(out))


def test_flash_attention_per_sequence_offsets_and_kv_lens():
    """Ragged chunked prefill on the fused path: per-sequence q_offsets and
    kv_lens (SMEM scalars) vs the reference mask."""
    ks = jax.random.split(jax.random.key(12), 3)
    B, Sq, Skv, H, K, hd = 2, 16, 96, 4, 2, 16
    q = _rand(ks[0], (B, Sq, H, hd), jnp.float32)
    k = _rand(ks[1], (B, Skv, K, hd), jnp.float32)
    v = _rand(ks[2], (B, Skv, K, hd), jnp.float32)
    offs = jnp.array([0, 37], jnp.int32)
    lens = offs + Sq
    out = ops.flash_attention(q, k, v, backend="interpret", block_q=8,
                              block_k=32, q_offsets=offs, kv_lens=lens)
    exp = ref.flash_attention_ref(q, k, v, q_offsets=offs, kv_lens=lens)
    np.testing.assert_allclose(out, exp, atol=2e-4, rtol=2e-4)
    # the jnp fallback dispatcher must honor the same ragged parameters
    out_jnp = ops.flash_attention(q, k, v, backend="jnp",
                                  q_offsets=offs, kv_lens=lens)
    np.testing.assert_allclose(out_jnp, exp, atol=0, rtol=0)


# ---------------------------------------------------------------------------
# rglru
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,T,W,bb,bw,bt", [
    (2, 128, 64, 2, 32, 32),
    (4, 256, 128, 2, 64, 64),
    (1, 64, 256, 1, 128, 64),
])
def test_rglru_matches_ref(B, T, W, bb, bw, bt):
    ks = jax.random.split(jax.random.key(5), 3)
    log_a = -jnp.abs(jax.random.normal(ks[0], (B, T, W))) * 0.5
    bx = jax.random.normal(ks[1], (B, T, W))
    h0 = jax.random.normal(ks[2], (B, W))
    h, hl = ops.rglru(log_a, bx, h0, backend="interpret",
                      block_b=bb, block_w=bw, block_t=bt)
    h_ref, hl_ref = ref.rglru_ref(log_a, bx, h0)
    np.testing.assert_allclose(h, h_ref, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(hl, hl_ref, atol=1e-4, rtol=1e-4)


@given(decay=st.floats(0.01, 2.0), t_split=st.integers(1, 7))
@settings(max_examples=10, deadline=None)
def test_rglru_chunking_invariance(decay, t_split):
    """Property: running the recurrence in two chunks (carrying h) equals one
    pass -- the exact invariant the kernel's scratch carry relies on."""
    B, T, W = 2, 8, 16
    ks = jax.random.split(jax.random.key(6), 3)
    log_a = -jnp.abs(jax.random.normal(ks[0], (B, T, W))) * decay
    bx = jax.random.normal(ks[1], (B, T, W))
    h0 = jax.random.normal(ks[2], (B, W))
    full, _ = ref.rglru_ref(log_a, bx, h0)
    h1, carry = ref.rglru_ref(log_a[:, :t_split], bx[:, :t_split], h0)
    h2, _ = ref.rglru_ref(log_a[:, t_split:], bx[:, t_split:], carry)
    np.testing.assert_allclose(jnp.concatenate([h1, h2], 1), full,
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# wkv6
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,T,H,hd,chunk", [
    (1, 32, 1, 8, 8),
    (2, 64, 2, 16, 16),
    (2, 96, 2, 32, 32),
])
def test_wkv6_matches_ref(B, T, H, hd, chunk):
    ks = jax.random.split(jax.random.key(7), 6)
    r = jax.random.normal(ks[0], (B, T, H, hd))
    k = jax.random.normal(ks[1], (B, T, H, hd)) * 0.3
    v = jax.random.normal(ks[2], (B, T, H, hd))
    w = jnp.exp(-jnp.exp(jnp.clip(jax.random.normal(ks[3], (B, T, H, hd)),
                                  -8, 0.7)))
    u = jax.random.normal(ks[4], (H, hd)) * 0.2
    st0 = jax.random.normal(ks[5], (B, H, hd, hd)) * 0.1
    out, s = ops.wkv6(r, k, v, w, u, st0, backend="interpret", chunk=chunk)
    out_ref, s_ref = ref.wkv6_ref(r, k, v, w, u, st0)
    np.testing.assert_allclose(out, out_ref, atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(s, s_ref, atol=2e-3, rtol=2e-3)


def test_wkv6_chunked_jnp_path_matches_sequential():
    """models/rwkv6.wkv_chunked (jnp path) vs the sequential oracle."""
    from repro.models.rwkv6 import wkv_chunked
    ks = jax.random.split(jax.random.key(8), 6)
    B, T, H, hd = 2, 64, 2, 16
    r = jax.random.normal(ks[0], (B, T, H, hd))
    k = jax.random.normal(ks[1], (B, T, H, hd)) * 0.3
    v = jax.random.normal(ks[2], (B, T, H, hd))
    w = jnp.exp(-jnp.exp(jnp.clip(jax.random.normal(ks[3], (B, T, H, hd)),
                                  -8, 0.7)))
    u = jax.random.normal(ks[4], (H, hd)) * 0.2
    st0 = jnp.zeros((B, H, hd, hd))
    out, s = wkv_chunked(r, k, v, w, u, st0)
    out_ref, s_ref = ref.wkv6_ref(r, k, v, w, u, st0)
    np.testing.assert_allclose(out, out_ref, atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(s, s_ref, atol=2e-3, rtol=2e-3)


# ---------------------------------------------------------------------------
# backend wiring (model -> kernels/ops dispatch)
# ---------------------------------------------------------------------------
def test_use_kernel_config_routes_serving_through_pallas_interpret():
    """The attention backend -- the platform's, or what ``ops.set_backend``
    chose -- must route the serving engine's chunked prefill, packed
    dispatch and decode through the Pallas kernels (interpret mode on CPU)
    and produce the same tokens as the jnp path."""
    from repro.configs import get_config
    from repro.serving.engine import ServingEngine

    cfg = get_config("tiny")
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, 500, n).astype(np.int32) for n in (8, 33, 70)]

    def run():
        eng = ServingEngine(cfg, max_slots=4, max_len=128, rng_seed=0)
        slots = eng.add_sequences([dict(prompt=p, max_new=6)
                                   for p in prompts], eager=False)
        while eng.prefill_pending():
            eng.prefill_step()
        while any(not eng.is_done(s) for s in slots):
            eng.step()
        return [eng.result(s) for s in slots], eng.stats

    assert ops.default_backend() == "jnp"
    expect, _ = run()
    ops.set_backend("interpret")
    try:
        assert ops.packed_row_align() == 8
        out, stats = run()
    finally:
        ops.set_backend(None)
    assert out == expect
    assert stats["packed_dispatches"] > 0
