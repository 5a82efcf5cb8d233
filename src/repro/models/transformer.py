"""Dense decoder-only transformer (llama/granite/yi/nemotron family), plus the
audio-token (musicgen) and cross-attention VLM (llama-3.2-vision) variants.

Layers are stacked on a leading "layers" axis and executed with lax.scan so
the HLO stays small at 100-layer scale; remat policy is configurable.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L


def _remat(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "dots":
        pol = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    else:
        pol = jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint(fn, policy=pol)


def _is_logical(x):
    return isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x)


def _stack_init(rng, n: int, init_fn):
    """Map an init over layer rngs -> params stacked on a leading "layers"
    axis. init_fn(rng) -> (params, logical); logical (static strings) is
    harvested via a side channel since mapped outputs must be arrays. The
    map is a loop, one layer at a time, so a draw's float32 temporaries
    span one layer's leaf, never the whole stack (at yi-6b widths the
    stacked MLP leaf would be 5.8 GB in float32)."""
    ks = jax.random.split(rng, n)
    side = {}

    def params_only(k):
        p, l = init_fn(k)
        side["logical"] = l
        return p

    params = jax.lax.map(params_only, ks)
    logical = jax.tree.map(lambda l: ("layers",) + l, side["logical"],
                           is_leaf=_is_logical)
    return params, logical


class DenseTransformer:
    """family in {dense, audio, vlm}."""

    # chunked prefill reads nothing but the K/V it wrote itself (causal mask
    # covers stale cache rows), so a fresh prompt needs no state reset
    stateful_prefill = False

    # speculative decoding needs rollback = seq_lens truncation: stale K/V
    # beyond seq_len is masked by the causal/q_offset attention masks and
    # overwritten when the position is re-reached, so rejecting drafted
    # tokens costs nothing. True for every causal-attention arch; recurrent
    # and rolling-buffer archs (state mutated in place per token) gate out.
    supports_spec_decode = True

    def __init__(self, cfg):
        self.cfg = cfg
        self.is_vlm = cfg.family == "vlm" and cfg.cross_attn_every > 0
        # VLM rows carry per-conversation frontend K/V (xk/xv) across chunks,
        # so a fresh prompt must start from a pristine row (zero image K/V =
        # "no image") even though the self-attention cache needs no reset
        self.reset_fresh_rows = self.is_vlm
        if self.is_vlm:
            # num_layers counts self + cross layers (llama-3.2-vision: 100 =
            # 80 self + 20 cross). Super-block = (every-1) self + 1 cross.
            assert cfg.num_layers % cfg.cross_attn_every == 0
            self.n_super = cfg.num_layers // cfg.cross_attn_every
            self.n_self_per = cfg.cross_attn_every - 1
        else:
            self.n_super = cfg.num_layers

    # -- init ---------------------------------------------------------------
    def _block_init(self, rng):
        cfg = self.cfg
        k1, k2 = jax.random.split(rng)
        p, l = {}, {}
        p["ln1"], l["ln1"] = L.norm_init(cfg.d_model)
        p["attn"], l["attn"] = L.attn_init(k1, cfg)
        p["ln2"], l["ln2"] = L.norm_init(cfg.d_model)
        p["mlp"], l["mlp"] = L.mlp_init(k2, cfg)
        return p, l

    def _super_block_init(self, rng):
        """VLM super-block: (cross_attn_every - 1) self layers + one full
        cross-attention layer (cross-attn + its own MLP)."""
        cfg = self.cfg
        k1, k2 = jax.random.split(rng)
        selfs, l_selfs = _stack_init(k1, self.n_self_per,
                                     lambda r: self._block_init(r))
        p, l = {}, {}
        p["selfs"], l["selfs"] = selfs, l_selfs
        kx1, kx2 = jax.random.split(k2)
        p["xln"], l["xln"] = L.norm_init(cfg.d_model)
        p["xattn"], l["xattn"] = L.attn_init(kx1, cfg, cross=True)
        p["xgate"] = jnp.zeros((1,), dtype=jnp.float32)
        l["xgate"] = ("norm",)
        p["xln2"], l["xln2"] = L.norm_init(cfg.d_model)
        p["xmlp"], l["xmlp"] = L.mlp_init(kx2, cfg)
        return p, l

    def init_params(self, rng) -> Tuple[Dict, Dict]:
        cfg = self.cfg
        k1, k2, k3 = jax.random.split(rng, 3)
        p, l = {}, {}
        p["embed"], l["embed"] = L.embed_init(k1, cfg.padded_vocab, cfg.d_model, cfg.param_dtype)
        init = self._super_block_init if self.is_vlm else self._block_init
        p["blocks"], l["blocks"] = _stack_init(k2, self.n_super, init)
        p["lnf"], l["lnf"] = L.norm_init(cfg.d_model)
        p["head"], l["head"] = L.dense_init(k3, cfg.d_model, cfg.padded_vocab,
                                            ("embed", "vocab"), cfg.param_dtype)
        return p, l

    # -- single-layer bodies --------------------------------------------------
    def _self_layer(self, blk, x, positions, *, q_offset=0):
        cfg = self.cfg
        h = L.rms_norm(x, blk["ln1"], cfg.norm_eps)
        q, k, v = L.attn_qkv(blk["attn"], h, cfg, positions)
        o = L.causal_attention(q, k, v, q_offset=q_offset)
        x = x + L.attn_out(blk["attn"], o)
        h = L.rms_norm(x, blk["ln2"], cfg.norm_eps)
        x = x + L.mlp_apply(blk["mlp"], h, cfg.activation)
        return x, (k, v)

    def _ffn(self, blk, x, *, infer: bool = False):
        """Post-attention feed-forward half of a self layer (ln2 + MLP).
        MoETransformer overrides this with the expert MLP so prefill_chunk is
        inherited unchanged; `infer` selects inference routing there."""
        cfg = self.cfg
        h = L.rms_norm(x, blk["ln2"], cfg.norm_eps)
        return x + L.mlp_apply(blk["mlp"], h, cfg.activation)

    def _cross_layer(self, blk, x, img):
        """Gated cross-attention onto frontend (image) embeddings."""
        cfg = self.cfg
        h = L.rms_norm(x, blk["xln"], cfg.norm_eps)
        B, S, _ = h.shape
        H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        q = (h @ blk["xattn"]["wq"]).reshape(B, S, H, hd)
        xk = (img @ blk["xattn"]["wk"]).reshape(B, -1, K, hd)
        xv = (img @ blk["xattn"]["wv"]).reshape(B, -1, K, hd)
        o = self._cross_attend(q, xk, xv)
        gate = jnp.tanh(blk["xgate"]).astype(x.dtype)
        x = x + gate * L.attn_out(blk["xattn"], o)
        h = L.rms_norm(x, blk["xln2"], cfg.norm_eps)
        x = x + L.mlp_apply(blk["xmlp"], h, cfg.activation)
        return x, (xk, xv)

    def _cross_attend(self, q, xk, xv):
        import math
        H = q.shape[2]
        k = L._broadcast_kv(xk, H)
        v = L._broadcast_kv(xv, H)
        s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
        s = s / math.sqrt(q.shape[-1])
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(q.dtype)

    # -- train forward --------------------------------------------------------
    def forward(self, params, tokens, *, image_embeds=None):
        cfg = self.cfg
        x = params["embed"][tokens].astype(cfg.dtype)
        B, S = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))

        if self.is_vlm:
            def body(x, blk):
                def inner(x2, sub):
                    x2, _ = self._self_layer(sub, x2, positions)
                    return x2, None
                x, _ = L.xscan(inner, x, blk["selfs"])
                x, _ = self._cross_layer(blk, x, image_embeds)
                return x, None
        else:
            def body(x, blk):
                x, _ = self._self_layer(blk, x, positions)
                return x, None

        x, _ = L.xscan(_remat(body, cfg.remat_policy), x, params["blocks"])
        x = L.rms_norm(x, params["lnf"], cfg.norm_eps)
        logits = x @ params["head"]
        if cfg.logits_softcap:
            logits = jnp.tanh(logits / cfg.logits_softcap) * cfg.logits_softcap
        return logits

    def loss_fn(self, params, batch):
        logits = self.forward(params, batch["tokens"],
                              image_embeds=batch.get("image_embeds"))
        labels = batch["labels"]
        lg = logits.astype(jnp.float32)
        logz = jax.scipy.special.logsumexp(lg, axis=-1)
        ll = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
        mask = batch.get("mask", jnp.ones_like(labels, dtype=jnp.float32))
        loss = jnp.sum((logz - ll) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        return loss

    # -- KV cache -------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int) -> Tuple[Dict, Dict]:
        cfg = self.cfg
        K, hd = cfg.n_kv_heads, cfg.head_dim
        nl = self.n_super
        if self.is_vlm:
            kv_shape = (nl, self.n_self_per, batch, max_len, K, hd)
            kv_logical = ("layers", "layers", "batch", "kv_seq", "kv", None)
        else:
            kv_shape = (nl, batch, max_len, K, hd)
            kv_logical = ("layers", "batch", "kv_seq", "kv", None)
        cache = {
            "k": jnp.zeros(kv_shape, cfg.dtype),
            "v": jnp.zeros(kv_shape, cfg.dtype),
            "seq_lens": jnp.zeros((batch,), jnp.int32),
        }
        logical = {
            "k": kv_logical,
            "v": kv_logical,
            "seq_lens": ("batch",),
        }
        if self.is_vlm:
            T = cfg.num_frontend_tokens
            cache["xk"] = jnp.zeros((nl, batch, T, K, hd), cfg.dtype)
            cache["xv"] = jnp.zeros((nl, batch, T, K, hd), cfg.dtype)
            logical["xk"] = ("layers", "batch", None, "kv", None)
            logical["xv"] = ("layers", "batch", None, "kv", None)
        return cache, logical

    # -- prefill --------------------------------------------------------------
    def prefill(self, params, tokens, cache, *, image_embeds=None, lengths=None):
        """tokens: [B, S_prompt] right-padded; returns (cache, last_logits).
        Stale cache beyond lengths is masked by decode_attention's seq_lens."""
        cfg = self.cfg
        B, S = tokens.shape
        x = params["embed"][tokens].astype(cfg.dtype)
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))

        if self.is_vlm:
            def body(x, xs):
                blk, kc, vc = xs
                def inner(x2, sub):
                    sblk, kcl, vcl = sub
                    h = L.rms_norm(x2, sblk["ln1"], cfg.norm_eps)
                    q, k, v = L.attn_qkv(sblk["attn"], h, cfg, positions)
                    o = L.causal_attention(q, k, v)
                    x2 = x2 + L.attn_out(sblk["attn"], o)
                    h = L.rms_norm(x2, sblk["ln2"], cfg.norm_eps)
                    x2 = x2 + L.mlp_apply(sblk["mlp"], h, cfg.activation)
                    kcl = jax.lax.dynamic_update_slice_in_dim(kcl, k, 0, axis=1)
                    vcl = jax.lax.dynamic_update_slice_in_dim(vcl, v, 0, axis=1)
                    return x2, (kcl, vcl)
                x, (kc, vc) = L.xscan(inner, x, (blk["selfs"], kc, vc))
                x, (xk, xv) = self._cross_layer(blk, x, image_embeds)
                return x, (kc, vc, xk, xv)
            x, (kn, vn, xk, xv) = L.xscan(
                _remat(body, cfg.remat_policy), x,
                (params["blocks"], cache["k"], cache["v"]))
            cache = dict(cache, k=kn, v=vn, xk=xk, xv=xv)
        else:
            def body(x, xs):
                blk, kc, vc = xs
                x, (k, v) = self._self_layer(blk, x, positions)
                kc = jax.lax.dynamic_update_slice_in_dim(kc, k, 0, axis=1)
                vc = jax.lax.dynamic_update_slice_in_dim(vc, v, 0, axis=1)
                return x, (kc, vc)
            x, (kn, vn) = L.xscan(
                _remat(body, cfg.remat_policy), x,
                (params["blocks"], cache["k"], cache["v"]))
            cache = dict(cache, k=kn, v=vn)

        x = L.rms_norm(x, params["lnf"], cfg.norm_eps)
        if lengths is None:
            lengths = jnp.full((B,), S, jnp.int32)
        idx = jnp.clip(lengths - 1, 0)
        last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
        last_logits = last @ params["head"]
        if cfg.logits_softcap:
            last_logits = jnp.tanh(last_logits / cfg.logits_softcap) * cfg.logits_softcap
        cache["seq_lens"] = lengths
        return cache, last_logits

    # -- chunked prefill -------------------------------------------------------
    def prefill_chunk(self, params, tokens, cache, *, q_offset, lengths,
                      image_embeds=None, image_mask=None, kv_width=None,
                      logits_upto=None):
        """Batched chunked prefill AND decode in one dispatch: consume chunk
        ``tokens`` [B, C] with row b at absolute positions
        ``q_offset[b] .. q_offset[b] + lengths[b] - 1``, attending over the
        existing KV prefix (cache positions < q_offset[b]) plus the chunk
        itself. A decoding slot is simply a ``lengths[b] == 1`` row at its
        current position (bit-identical to ``decode_step``), and rows with
        ``lengths[b] == 0`` are a strict no-op (cache, seq_lens and K/V
        preserved bit-for-bit) -- this per-row mask is what lets one
        scheduler step run prefill chunks, decode tokens and idle slots as
        ONE model dispatch, with no separate decode-step keep-guard.

        q_offset, lengths: [B] int32 (q_offset is only read where
        lengths > 0). kv_width (static) bounds every sequence's context after
        this chunk (max q_offset+lengths <= kv_width): K/V writes and
        attention run on a [.., :kv_width] view of the cache, so chunk cost
        scales with the actual context, not the cache allocation.
        image_mask [B] bool marks which rows' frontend (image) K/V to
        recompute from ``image_embeds`` -- rows outside the mask (text
        prompts, decoding slots) keep their cached xk/xv, so VLM prompts can
        ride in mixed chunk batches. Returns (cache, last_logits) where
        last_logits[b] is the logits at the chunk's final valid position
        (garbage when lengths[b] == 0 -- callers keep the logits of the
        finishing chunk).

        logits_upto (static): when set, additionally return per-position
        logits for the first ``logits_upto`` chunk positions of every row
        ([B, logits_upto, V]) -- the verify surface for speculative
        decoding, where a decode row carries [pending, draft_1..draft_m]
        and the engine needs the model's distribution at EACH position to
        run acceptance. Return becomes (cache, last_logits, pos_logits).

        One-token chunks (C == 1, the pure-decode tick) without
        ``logits_upto`` take ``_decode_layers``: the layer loop carries the
        whole K/V stacks, writes each row's token in place and attention
        reads its layer out of the stack, so no cache layer is sliced,
        rewritten or copied; ``kv_width`` is not needed there (attention
        reads each row's live blocks only). The VLM keeps the per-layer
        path below: its self layers sit in a nested scan.
        """
        cfg = self.cfg
        B, C = tokens.shape
        x = params["embed"][tokens].astype(cfg.dtype)
        positions = q_offset[:, None] + jnp.arange(C)[None, :]

        def self_chunk(blk, x, kc, vc):
            h = L.rms_norm(x, blk["ln1"], cfg.norm_eps)
            q, k, v = L.attn_qkv(blk["attn"], h, cfg, positions)
            narrow = kv_width is not None and kv_width < kc.shape[1]
            kw = kc[:, :kv_width] if narrow else kc
            vw = vc[:, :kv_width] if narrow else vc
            kw = L.cache_write_chunk(kw, k, q_offset, lengths)
            vw = L.cache_write_chunk(vw, v, q_offset, lengths)
            o = L.chunk_attention(q, kw, vw, q_offset, q_lens=lengths)
            if narrow:
                kc = jax.lax.dynamic_update_slice_in_dim(kc, kw, 0, axis=1)
                vc = jax.lax.dynamic_update_slice_in_dim(vc, vw, 0, axis=1)
            else:
                kc, vc = kw, vw
            x = x + L.attn_out(blk["attn"], o)
            return self._ffn(blk, x, infer=True), kc, vc

        if C == 1 and not self.is_vlm and logits_upto is None:
            x, kn, vn = self._decode_layers(params, x, cache["k"], cache["v"],
                                            positions, q_offset, lengths)
            cache = dict(cache, k=kn, v=vn)
        elif self.is_vlm:
            has_img = lengths > 0
            if image_mask is not None:
                has_img &= image_mask
            upd = has_img[:, None, None, None]

            def body(x, xs):
                blk, kc, vc, xk, xv = xs

                def inner(x2, sub):
                    sblk, kcl, vcl = sub
                    x2, kcl, vcl = self_chunk(sblk, x2, kcl, vcl)
                    return x2, (kcl, vcl)

                x, (kc, vc) = L.xscan(inner, x, (blk["selfs"], kc, vc))
                h = L.rms_norm(x, blk["xln"], cfg.norm_eps)
                H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
                q = (h @ blk["xattn"]["wq"]).reshape(B, C, H, hd)
                if image_embeds is not None:
                    # recompute image K/V (position-independent, identical
                    # every chunk); keep other rows' cached values intact
                    xkn = (image_embeds @ blk["xattn"]["wk"]).reshape(B, -1, K, hd)
                    xvn = (image_embeds @ blk["xattn"]["wv"]).reshape(B, -1, K, hd)
                    xk = jnp.where(upd, xkn.astype(xk.dtype), xk)
                    xv = jnp.where(upd, xvn.astype(xv.dtype), xv)
                o = self._cross_attend(q, xk, xv)
                gate = jnp.tanh(blk["xgate"]).astype(x.dtype)
                x = x + gate * L.attn_out(blk["xattn"], o)
                h = L.rms_norm(x, blk["xln2"], cfg.norm_eps)
                x = x + L.mlp_apply(blk["xmlp"], h, cfg.activation)
                return x, (kc, vc, xk, xv)

            x, (kn, vn, xk, xv) = L.xscan(
                _remat(body, cfg.remat_policy), x,
                (params["blocks"], cache["k"], cache["v"],
                 cache["xk"], cache["xv"]))
            cache = dict(cache, k=kn, v=vn, xk=xk, xv=xv)
        else:
            def body(x, xs):
                blk, kc, vc = xs
                x, kc, vc = self_chunk(blk, x, kc, vc)
                return x, (kc, vc)

            x, (kn, vn) = L.xscan(
                _remat(body, cfg.remat_policy), x,
                (params["blocks"], cache["k"], cache["v"]))
            cache = dict(cache, k=kn, v=vn)

        x = L.rms_norm(x, params["lnf"], cfg.norm_eps)
        idx = jnp.clip(lengths - 1, 0)
        last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
        last_logits = last @ params["head"]
        if cfg.logits_softcap:
            last_logits = jnp.tanh(last_logits / cfg.logits_softcap) * cfg.logits_softcap
        cache["seq_lens"] = jnp.where(lengths > 0, q_offset + lengths,
                                      cache["seq_lens"])
        if logits_upto is not None:
            pos_logits = x[:, :logits_upto] @ params["head"]
            if cfg.logits_softcap:
                pos_logits = jnp.tanh(pos_logits / cfg.logits_softcap) \
                    * cfg.logits_softcap
            return cache, last_logits, pos_logits
        return cache, last_logits

    def _decode_layers(self, params, x, k_stack, v_stack, positions,
                       q_offset, lengths):
        """The layer loop of a one-token chunk (x [B, 1, d]) with the whole
        [L, B, S, K, hd] K/V stacks in the scan's carry: layer l writes row
        b's token at (l, b, q_offset[b]) by a scatter on the carried stack
        (rows with lengths == 0 dropped) and attention reads layer l where
        it lies. The stacks are neither scanned over nor stacked back, so
        the program updates them in place. Returns (x, k_stack, v_stack)."""
        cfg = self.cfg

        def body(carry, xs):
            x, ks, vs = carry
            blk, layer = xs
            h = L.rms_norm(x, blk["ln1"], cfg.norm_eps)
            q, k, v = L.attn_qkv(blk["attn"], h, cfg, positions)
            ks = L.cache_write_stacked_token(ks, k, layer, q_offset, lengths)
            vs = L.cache_write_stacked_token(vs, v, layer, q_offset, lengths)
            o = L.chunk_attention(q, ks, vs, q_offset, q_lens=lengths,
                                  layer=layer)
            x = x + L.attn_out(blk["attn"], o)
            return (self._ffn(blk, x, infer=True), ks, vs), None

        (x, k_stack, v_stack), _ = L.xscan(
            _remat(body, cfg.remat_policy), (x, k_stack, v_stack),
            (params["blocks"], jnp.arange(self.n_super, dtype=jnp.int32)))
        return x, k_stack, v_stack

    # -- token-packed ragged prefill -------------------------------------------
    def prefill_packed(self, params, tokens, cache, *, row_starts, q_offset,
                       lengths, chunk=None, image_embeds=None,
                       image_mask=None, kv_width=None, logits_upto=None):
        """Token-packed variant of ``prefill_chunk``: ``tokens`` is [Np] --
        every row's chunk tokens concatenated on ONE packed axis, row b at
        packed positions ``row_starts[b] .. row_starts[b] + lengths[b] - 1``
        -- so the dispatch's FLOPs scale with the real tokens it carries (a
        decode row costs 1 packed slot, a 7-token tail chunk costs 7) instead
        of rows x chunk bucket. Same per-row semantics as prefill_chunk:
        row b's tokens sit at absolute positions ``q_offset[b] ..``, rows
        with ``lengths[b] == 0`` are preserved bit-for-bit (they simply own
        no packed slots), and last_logits[b] reads the row's final valid
        packed position (garbage for length-0 rows). ``chunk`` (static) is
        interface parity with the recurrent archs' unpack-and-delegate
        packed path; dense attention doesn't need it. VLM rows ride packed
        dispatches too: cross-attention gathers each packed token's own
        cached xk/xv row, and when ``image_embeds`` [B, T, d] is given the
        rows selected by ``image_mask`` recompute their frontend K/V first
        (image K/V is position-independent, so the padded and packed
        layouts write identical xk/xv). ``logits_upto`` (static) mirrors
        prefill_chunk: also return [B, logits_upto, V] per-position logits
        gathered from each row's packed slots (the speculative-decode
        verify surface); return becomes (cache, last_logits, pos_logits)."""
        cfg = self.cfg
        Np = tokens.shape[0]
        B = lengths.shape[0]
        x = params["embed"][tokens][None].astype(cfg.dtype)      # [1, Np, d]
        row, off, valid = L.packed_row_index(row_starts, lengths, Np)
        pos = q_offset[row] + off                                # [Np]
        positions = pos[None]                                    # [1, Np]

        def self_packed(blk, x, kc, vc):
            h = L.rms_norm(x, blk["ln1"], cfg.norm_eps)
            q, k, v = L.attn_qkv(blk["attn"], h, cfg, positions)
            narrow = kv_width is not None and kv_width < kc.shape[1]
            kw = kc[:, :kv_width] if narrow else kc
            vw = vc[:, :kv_width] if narrow else vc
            kw = L.cache_write_packed(kw, k[0], row, pos, valid)
            vw = L.cache_write_packed(vw, v[0], row, pos, valid)
            o = L.packed_chunk_attention(q[0], kw, vw, row_starts, q_offset,
                                         lengths)
            if narrow:
                kc = jax.lax.dynamic_update_slice_in_dim(kc, kw, 0, axis=1)
                vc = jax.lax.dynamic_update_slice_in_dim(vc, vw, 0, axis=1)
            else:
                kc, vc = kw, vw
            x = x + L.attn_out(blk["attn"], o[None])
            return self._ffn(blk, x, infer=True), kc, vc

        if self.is_vlm:
            has_img = lengths > 0
            if image_mask is not None:
                has_img &= image_mask
            upd = has_img[:, None, None, None]

            def body(x, xs):
                blk, kc, vc, xk, xv = xs

                def inner(x2, sub):
                    sblk, kcl, vcl = sub
                    x2, kcl, vcl = self_packed(sblk, x2, kcl, vcl)
                    return x2, (kcl, vcl)

                x, (kc, vc) = L.xscan(inner, x, (blk["selfs"], kc, vc))
                h = L.rms_norm(x, blk["xln"], cfg.norm_eps)
                H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
                q = (h @ blk["xattn"]["wq"]).reshape(Np, H, hd)
                if image_embeds is not None:
                    # recompute image K/V for masked rows (identical to the
                    # padded layout: position-independent), keep the rest
                    xkn = (image_embeds @ blk["xattn"]["wk"]).reshape(B, -1, K, hd)
                    xvn = (image_embeds @ blk["xattn"]["wv"]).reshape(B, -1, K, hd)
                    xk = jnp.where(upd, xkn.astype(xk.dtype), xk)
                    xv = jnp.where(upd, xvn.astype(xv.dtype), xv)
                o = self._cross_attend_packed(q, xk[row], xv[row])
                gate = jnp.tanh(blk["xgate"]).astype(x.dtype)
                x = x + gate * L.attn_out(blk["xattn"], o[None])
                h = L.rms_norm(x, blk["xln2"], cfg.norm_eps)
                x = x + L.mlp_apply(blk["xmlp"], h, cfg.activation)
                return x, (kc, vc, xk, xv)

            x, (kn, vn, xk, xv) = L.xscan(
                _remat(body, cfg.remat_policy), x,
                (params["blocks"], cache["k"], cache["v"],
                 cache["xk"], cache["xv"]))
            cache = dict(cache, k=kn, v=vn, xk=xk, xv=xv)
        else:
            def body(x, xs):
                blk, kc, vc = xs
                x, kc, vc = self_packed(blk, x, kc, vc)
                return x, (kc, vc)

            x, (kn, vn) = L.xscan(
                _remat(body, cfg.remat_policy), x,
                (params["blocks"], cache["k"], cache["v"]))
            cache = dict(cache, k=kn, v=vn)

        x = L.rms_norm(x, params["lnf"], cfg.norm_eps)[0]        # [Np, d]
        last_idx = jnp.clip(row_starts + jnp.clip(lengths - 1, 0), 0, Np - 1)
        last = x[last_idx]                                       # [B, d]
        last_logits = last @ params["head"]
        if cfg.logits_softcap:
            last_logits = jnp.tanh(last_logits / cfg.logits_softcap) * cfg.logits_softcap
        cache["seq_lens"] = jnp.where(lengths > 0, q_offset + lengths,
                                      cache["seq_lens"])
        if logits_upto is not None:
            idx = jnp.clip(row_starts[:, None]
                           + jnp.arange(logits_upto)[None, :], 0, Np - 1)
            pos_logits = x[idx] @ params["head"]                 # [B, u, V]
            if cfg.logits_softcap:
                pos_logits = jnp.tanh(pos_logits / cfg.logits_softcap) \
                    * cfg.logits_softcap
            return cache, last_logits, pos_logits
        return cache, last_logits

    def _cross_attend_packed(self, q, xk, xv):
        """Per-packed-token cross-attention onto each token's own row of
        cached frontend K/V. q: [Np, H, hd]; xk/xv: [Np, T, K, hd]."""
        import math
        H = q.shape[1]
        K = xk.shape[2]
        if K != H:
            xk = jnp.repeat(xk, H // K, axis=2)
            xv = jnp.repeat(xv, H // K, axis=2)
        s = jnp.einsum("nhd,nthd->nht", q.astype(jnp.float32),
                       xk.astype(jnp.float32))
        s = s / math.sqrt(q.shape[-1])
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("nht,nthd->nhd", p,
                          xv.astype(jnp.float32)).astype(q.dtype)

    # -- decode ---------------------------------------------------------------
    def decode_step(self, params, tokens, cache):
        """tokens: [B] int32 -> (cache, logits [B, V])."""
        cfg = self.cfg
        B = tokens.shape[0]
        x = params["embed"][tokens][:, None, :].astype(cfg.dtype)  # [B,1,d]
        seq_lens = cache["seq_lens"]
        positions = seq_lens[:, None]  # new token position

        def self_step(blk, x, kc, vc):
            h = L.rms_norm(x, blk["ln1"], cfg.norm_eps)
            q, k, v = L.attn_qkv(blk["attn"], h, cfg, positions)
            kc = L.cache_write_token(kc, k[:, 0], seq_lens)
            vc = L.cache_write_token(vc, v[:, 0], seq_lens)
            o = L.decode_attention(q[:, 0], kc, vc, seq_lens + 1)
            x = x + L.attn_out(blk["attn"], o[:, None])
            h = L.rms_norm(x, blk["ln2"], cfg.norm_eps)
            x = x + L.mlp_apply(blk["mlp"], h, cfg.activation)
            return x, kc, vc

        if self.is_vlm:
            def body(x, xs):
                blk, kc, vc, xk, xv = xs
                def inner(x2, sub):
                    sblk, kcl, vcl = sub
                    x2, kcl, vcl = self_step(sblk, x2, kcl, vcl)
                    return x2, (kcl, vcl)
                x, (kc, vc) = L.xscan(inner, x, (blk["selfs"], kc, vc))
                # cross-attn reuses cached image K/V
                h = L.rms_norm(x, blk["xln"], cfg.norm_eps)
                q = (h @ blk["xattn"]["wq"]).reshape(B, 1, cfg.n_heads, cfg.head_dim)
                o = self._cross_attend(q, xk, xv)
                gate = jnp.tanh(blk["xgate"]).astype(x.dtype)
                x = x + gate * L.attn_out(blk["xattn"], o)
                h = L.rms_norm(x, blk["xln2"], cfg.norm_eps)
                x = x + L.mlp_apply(blk["xmlp"], h, cfg.activation)
                return x, (kc, vc)
            x, (kn, vn) = L.xscan(
                body, x, (params["blocks"], cache["k"], cache["v"],
                          cache["xk"], cache["xv"]))
        else:
            def body(x, xs):
                blk, kc, vc = xs
                x, kc, vc = self_step(blk, x, kc, vc)
                return x, (kc, vc)
            x, (kn, vn) = L.xscan(
                body, x, (params["blocks"], cache["k"], cache["v"]))

        cache = dict(cache, k=kn, v=vn, seq_lens=seq_lens + 1)
        x = L.rms_norm(x, params["lnf"], cfg.norm_eps)
        logits = x[:, 0, :] @ params["head"]
        if cfg.logits_softcap:
            logits = jnp.tanh(logits / cfg.logits_softcap) * cfg.logits_softcap
        return cache, logits
