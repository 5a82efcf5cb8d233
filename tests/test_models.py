"""Per-architecture smoke tests (reduced configs, deliverable f) and
prefill<->decode consistency -- the invariant the serving engine and the
paper's Table-7 exactness claim rest on."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import all_archs, get_config
from repro.models import build_model

ARCHS = all_archs()


def _inputs(cfg, B=2, S=64, key=1):
    tokens = jax.random.randint(jax.random.key(key), (B, S), 0, cfg.vocab)
    kw = {}
    if cfg.family == "vlm":
        kw["image_embeds"] = jax.random.normal(
            jax.random.key(key + 1), (B, cfg.num_frontend_tokens, cfg.d_model),
            cfg.dtype)
    return tokens, kw


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_and_train_step(arch):
    """One forward + one train step on the reduced config: output shapes and
    no NaNs (the per-arch smoke gate)."""
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    params, logical = model.init_params(jax.random.key(0))
    assert jax.tree.structure(params) == jax.tree.structure(
        logical, is_leaf=lambda x: isinstance(x, tuple))
    B, S = 2, 64
    tokens, kw = _inputs(cfg, B, S)
    logits = model.forward(params, tokens, **kw)
    assert logits.shape == (B, S, cfg.padded_vocab)
    assert not bool(jnp.any(jnp.isnan(logits.astype(jnp.float32))))

    batch = {"tokens": tokens, "labels": tokens, **kw}
    from repro.training.optimizer import AdamW
    from repro.training.train_loop import make_train_step
    opt = AdamW()
    step = jax.jit(make_train_step(model, opt))
    opt_state = opt.init(params)
    params2, _, metrics = step(params, opt_state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    # params must actually change
    delta = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) -
                                      b.astype(jnp.float32))))
                for a, b in zip(jax.tree.leaves(params),
                                jax.tree.leaves(params2)))
    assert delta > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_consistency(arch):
    """Split prefill+decode must equal one-shot prefill (exactness substrate
    for context switching)."""
    cfg = get_config(arch, smoke=True).replace(dtype=jnp.float32,
                                               param_dtype=jnp.float32)
    model = build_model(cfg)
    params, _ = model.init_params(jax.random.key(0))
    B, S, P = 2, 48, 23
    tokens, kw = _inputs(cfg, B, S)

    cache_a, _ = model.init_cache(B, S + 8)
    cache_a, lg_ref = model.prefill(params, tokens, cache_a, **kw)

    cache, _ = model.init_cache(B, S + 8)
    cache, lg = model.prefill(params, tokens[:, :P], cache, **kw)
    for t in range(P, S):
        cache, lg = model.decode_step(params, tokens[:, t], cache)
    err = float(jnp.max(jnp.abs(lg - lg_ref)))
    scale = float(jnp.max(jnp.abs(lg_ref)))
    assert err < 1e-3 * max(scale, 1.0), (arch, err, scale)


@pytest.mark.parametrize("arch", ["granite-3-8b", "rwkv6-1.6b",
                                  "recurrentgemma-2b"])
def test_ragged_prefill_lengths(arch):
    """Right-padded prefill with per-sequence lengths must match per-sequence
    unpadded prefill."""
    cfg = get_config(arch, smoke=True).replace(dtype=jnp.float32,
                                               param_dtype=jnp.float32)
    model = build_model(cfg)
    params, _ = model.init_params(jax.random.key(0))
    S = 40
    lengths = [17, 33]
    tokens = jax.random.randint(jax.random.key(5), (2, S), 0, cfg.vocab)
    cache, _ = model.init_cache(2, S + 8)
    cache, lg = model.prefill(params, tokens, cache,
                              lengths=jnp.asarray(lengths, jnp.int32))
    for b, L in enumerate(lengths):
        c1, _ = model.init_cache(1, S + 8)
        c1, lg1 = model.prefill(params, tokens[b:b + 1, :L], c1)
        err = float(jnp.max(jnp.abs(lg1[0] - lg[b])))
        assert err < 1e-3, (arch, b, err)


@pytest.mark.parametrize("arch", ARCHS)
def test_chunked_prefill_matches_full_prefill(arch):
    """prefill_chunk fed 16 tokens at a time (ragged lengths, resuming from
    carried KV/recurrent state at per-sequence offsets) must reproduce the
    one-shot prefill -- the substrate of batched burst admission."""
    cfg = get_config(arch, smoke=True).replace(dtype=jnp.float32,
                                               param_dtype=jnp.float32)
    model = build_model(cfg)
    params, _ = model.init_params(jax.random.key(0))
    B, S = 2, 48
    tokens, kw = _inputs(cfg, B, S)
    lengths = jnp.array([S, 37], jnp.int32)

    cache_a, _ = model.init_cache(B, S + 8)
    cache_a, lg_ref = model.prefill(params, tokens, cache_a, lengths=lengths,
                                    **kw)

    cache, _ = model.init_cache(B, S + 8)
    done = jnp.zeros((B,), jnp.int32)
    lg_keep = jnp.zeros_like(lg_ref)
    for start in range(0, S, 16):
        ln = jnp.clip(lengths - done, 0, 16)
        cache, lg = model.prefill_chunk(params, tokens[:, start:start + 16],
                                        cache, q_offset=done, lengths=ln, **kw)
        finishing = (ln > 0) & (done + ln == lengths)
        lg_keep = jnp.where(finishing[:, None], lg, lg_keep)
        done = done + ln
    err = float(jnp.max(jnp.abs(lg_keep - lg_ref)))
    scale = float(jnp.max(jnp.abs(lg_ref)))
    assert err < 1e-3 * max(scale, 1.0), (arch, err, scale)
    assert bool(jnp.all(cache["seq_lens"] == cache_a["seq_lens"]))


@pytest.mark.parametrize("arch", ["granite-3-8b", "rwkv6-1.6b",
                                  "recurrentgemma-2b", "moonshot-v1-16b-a3b"])
def test_chunked_prefill_len0_rows_untouched(arch):
    """A chunk dispatch must be a strict no-op for rows with lengths == 0 --
    the invariant that lets one dispatch share the batch with decoding or
    already-finished slots."""
    cfg = get_config(arch, smoke=True).replace(dtype=jnp.float32,
                                               param_dtype=jnp.float32)
    model = build_model(cfg)
    params, _ = model.init_params(jax.random.key(0))
    B, S = 2, 32
    tokens = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab)
    cache, _ = model.init_cache(B, S + 8)
    cache, _ = model.prefill(params, tokens, cache,
                             lengths=jnp.array([20, 32], jnp.int32))
    before = jax.tree.leaves(cache)
    cache2, _ = model.prefill_chunk(params,
                                    jnp.full((B, 16), 3, jnp.int32), cache,
                                    q_offset=jnp.zeros((B,), jnp.int32),
                                    lengths=jnp.zeros((B,), jnp.int32))
    after = jax.tree.leaves(cache2)
    for a, b in zip(before, after):
        assert bool(jnp.all(a == b))


def test_param_counts_match_published_scale():
    """Analytic parameter counts should land near the published sizes."""
    # moonshot: the assigned 48L x 64e x 1408ff implies ~28B total (the
    # "16b" name comes from the 27-layer Moonlight original; the assignment's
    # numbers are authoritative -- DESIGN.md §4). musicgen-large is 3.3B.
    expect = {"granite-3-8b": 8e9, "yi-9b": 9e9, "yi-6b": 6e9,
              "nemotron-4-15b": 15e9, "arctic-480b": 480e9,
              "moonshot-v1-16b-a3b": 28e9, "rwkv6-1.6b": 1.6e9,
              "llama-3.2-vision-90b": 90e9, "recurrentgemma-2b": 2.7e9,
              "musicgen-large": 3.3e9}
    for arch, target in expect.items():
        n = get_config(arch).param_count()
        assert 0.55 * target < n < 1.45 * target, (arch, n, target)


def _per_layer_decode_tick(model, params, tokens, cache, q_offset, lengths):
    """The one-token tick one layer at a time, the way the chunk path runs
    it: a scan over the layers and the K/V stacks, each step writing the
    token into its layer with ``cache_write_chunk`` (a select over the
    layer) and attending over that layer, the layers stacked back as the
    scan's output. (A scan, as the model's own loop is: the same ops
    unrolled fuse differently and round bf16 differently.) Returns
    (k, v, logits)."""
    from repro.models import layers as L
    cfg = model.cfg
    x = params["embed"][tokens].astype(cfg.dtype)
    positions = q_offset[:, None]

    def layer(x, xs):
        blk, kc, vc = xs
        h = L.rms_norm(x, blk["ln1"], cfg.norm_eps)
        q, k, v = L.attn_qkv(blk["attn"], h, cfg, positions)
        kc = L.cache_write_chunk(kc, k, q_offset, lengths)
        vc = L.cache_write_chunk(vc, v, q_offset, lengths)
        o = L.chunk_attention(q, kc, vc, q_offset, q_lens=lengths)
        x = model._ffn(blk, x + L.attn_out(blk["attn"], o), infer=True)
        return x, (kc, vc)

    x, (k, v) = jax.lax.scan(layer, x,
                             (params["blocks"], cache["k"], cache["v"]))
    x = L.rms_norm(x, params["lnf"], cfg.norm_eps)
    return k, v, x[:, 0] @ params["head"]


@pytest.mark.parametrize("rows", ["edges", "narrow"])
@pytest.mark.parametrize("backend", ["jnp", "interpret"])
@pytest.mark.parametrize("arch", ["granite-3-8b", "arctic-480b"])
def test_decode_tick_writes_carried_stack_in_place(arch, backend, rows):
    """The pure-decode tick (``prefill_chunk`` with C == 1) carries the
    whole K/V stacks through its layer loop, writes each row's token in
    place and reads each layer out of the stack. It must equal the
    per-layer tick bit for bit -- logits and both stacks -- for a dense
    GQA and an MoE model, on the jnp path and through the Pallas kernel:
    rows at positions 0 and S-1, a row with lengths == 0 (its cache left
    as it was, bit for bit) and, with ``kv_width`` < S, a narrow bucket
    the carried path has no need of."""
    from repro.kernels import ops as kops
    cfg = get_config(arch, smoke=True).replace(num_layers=3)
    model = build_model(cfg)
    params, _ = model.init_params(jax.random.key(0))
    B, S = 4, 64
    if rows == "edges":
        q_offset = jnp.array([0, S - 1, 17, 40], jnp.int32)
        lengths = jnp.array([1, 1, 0, 1], jnp.int32)
        kv_width = None
    else:
        q_offset = jnp.array([0, 9, 50, 31], jnp.int32)
        lengths = jnp.array([1, 1, 0, 1], jnp.int32)
        kv_width = 32
    cache, _ = model.init_cache(B, S)
    kk, kv, kt = jax.random.split(jax.random.key(3), 3)
    cache["k"] = jax.random.normal(kk, cache["k"].shape).astype(cfg.dtype)
    cache["v"] = jax.random.normal(kv, cache["v"].shape).astype(cfg.dtype)
    cache["seq_lens"] = q_offset
    tokens = jax.random.randint(kt, (B, 1), 0, cfg.vocab)

    kops.set_backend(backend)
    try:
        tick = jax.jit(lambda p, t, c, o, n: model.prefill_chunk(
            p, t, c, q_offset=o, lengths=n, kv_width=kv_width))
        got, logits = tick(params, tokens, dict(cache), q_offset, lengths)
        k_ref, v_ref, logits_ref = jax.jit(
            lambda p, t, c, o, n: _per_layer_decode_tick(model, p, t, c, o, n)
        )(params, tokens, cache, q_offset, lengths)
    finally:
        kops.set_backend(None)
    np.testing.assert_array_equal(np.asarray(got["k"]), np.asarray(k_ref))
    np.testing.assert_array_equal(np.asarray(got["v"]), np.asarray(v_ref))
    live = np.asarray(lengths) > 0
    np.testing.assert_array_equal(np.asarray(logits)[live],
                                  np.asarray(logits_ref)[live])
    for name in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(got[name])[:, ~live],
                                      np.asarray(cache[name])[:, ~live])
    assert np.all(np.asarray(got["seq_lens"]) ==
                  np.where(live, np.asarray(q_offset) + 1, np.asarray(q_offset)))
