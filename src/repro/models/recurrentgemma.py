"""RecurrentGemma-2B (Griffin, arXiv:2402.19427): RG-LRU recurrent blocks +
local sliding-window attention, pattern (rec, rec, attn).

TPU adaptation (DESIGN.md §2): the GPU reference uses a custom CUDA linear
scan; here the RG-LRU recurrence runs as a log-depth jax.lax.associative_scan
(train/prefill) and an O(1) state update (decode). The Pallas kernel
(kernels/rglru.py) is the fused-VMEM chunk variant.

26 layers = 8 scanned super-blocks of (rec, rec, attn) + 2 tail rec layers.
Attention is MQA (kv=1) with window 2048 over a rolling KV buffer.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.transformer import _stack_init, _remat

C_RGLRU = 8.0  # Griffin's fixed gate sharpness


# ---------------------------------------------------------------------------
# RG-LRU + conv1d primitives
# ---------------------------------------------------------------------------

def rglru_scan(log_a, bx, h0):
    """h_t = a_t * h_{t-1} + bx_t via associative scan.

    log_a, bx: [B, T, W] fp32; h0: [B, W]. Returns (h [B,T,W], h_last)."""
    a = jnp.exp(log_a)

    def combine(left, right):
        al, bl = left
        ar, br = right
        return al * ar, ar * bl + br

    acc_a, acc_b = jax.lax.associative_scan(combine, (a, bx), axis=1)
    # contribution of initial state: prod_{s<=t} a_s * h0
    h = acc_b + acc_a * h0[:, None, :]
    return h, h[:, -1]


def rglru_step(log_a, bx, h0):
    """Single-step recurrence: [B, W] each."""
    return jnp.exp(log_a) * h0 + bx


def causal_conv1d(x, w, b, conv_state):
    """Depthwise causal conv, width cw. x: [B, T, W]; w: [cw, W]; b: [W];
    conv_state: [B, cw-1, W] (previous inputs). Returns (y, new_state)."""
    cw = w.shape[0]
    xp = jnp.concatenate([conv_state.astype(x.dtype), x], axis=1)  # [B, T+cw-1, W]
    T = x.shape[1]
    y = sum(xp[:, i:i + T] * w[i] for i in range(cw)) + b
    new_state = xp[:, -(cw - 1):] if cw > 1 else conv_state
    return y.astype(x.dtype), new_state


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------

class RecurrentGemma:
    # chunked prefill resumes from carried RG-LRU/conv state and the rolling
    # buffer, so a fresh prompt's rows must be reset before its first chunk
    stateful_prefill = True
    reset_fresh_rows = True
    # RG-LRU state and the rolling attention buffer advance destructively
    # per token (no positional rewind), so rejected drafts cannot roll back
    # via seq_lens truncation -- spec decoding gates out
    supports_spec_decode = False

    def __init__(self, cfg):
        self.cfg = cfg
        self.n_super = cfg.num_layers // 3
        self.n_tail = cfg.num_layers - self.n_super * 3  # trailing rec layers
        self.W = cfg.lru_width or cfg.d_model

    # -- init -----------------------------------------------------------------
    def _rec_block_init(self, rng):
        cfg = self.cfg
        d, W, cw = cfg.d_model, self.W, cfg.conv_width
        ks = jax.random.split(rng, 7)
        dt = cfg.param_dtype
        p, l = {}, {}
        p["ln1"], l["ln1"] = L.norm_init(d)
        p["wx"], l["wx"] = L.dense_init(ks[0], d, W, ("embed", "rnn"), dt)
        p["wgate"], l["wgate"] = L.dense_init(ks[1], d, W, ("embed", "rnn"), dt)
        p["conv_w"] = (jax.random.normal(ks[2], (cw, W), jnp.float32) * 0.1).astype(jnp.float32)
        l["conv_w"] = ("conv", "rnn")
        p["conv_b"] = jnp.zeros((W,), jnp.float32)
        l["conv_b"] = ("rnn",)
        p["wa"], l["wa"] = L.dense_init(ks[3], W, W, ("rnn", None), dt)
        p["ba"] = jnp.zeros((W,), jnp.float32); l["ba"] = ("rnn",)
        p["wi"], l["wi"] = L.dense_init(ks[4], W, W, ("rnn", None), dt)
        p["bi"] = jnp.zeros((W,), jnp.float32); l["bi"] = ("rnn",)
        # lambda init so sigma(lam) in ~(0.9, 0.999)
        p["lam"] = jnp.linspace(2.2, 6.9, W, dtype=jnp.float32)
        l["lam"] = ("rnn",)
        p["wo"], l["wo"] = L.dense_init(ks[5], W, d, ("rnn", "embed"), dt)
        p["ln2"], l["ln2"] = L.norm_init(d)
        p["mlp"], l["mlp"] = L.mlp_init(ks[6], cfg)
        return p, l

    def _attn_block_init(self, rng):
        cfg = self.cfg
        k1, k2 = jax.random.split(rng)
        p, l = {}, {}
        p["ln1"], l["ln1"] = L.norm_init(cfg.d_model)
        p["attn"], l["attn"] = L.attn_init(k1, cfg)
        p["ln2"], l["ln2"] = L.norm_init(cfg.d_model)
        p["mlp"], l["mlp"] = L.mlp_init(k2, cfg)
        return p, l

    def _super_block_init(self, rng):
        k1, k2 = jax.random.split(rng)
        p, l = {}, {}
        p["recs"], l["recs"] = _stack_init(k1, 2, self._rec_block_init)
        p["attn_blk"], l["attn_blk"] = self._attn_block_init(k2)
        return p, l

    def init_params(self, rng):
        cfg = self.cfg
        k1, k2, k3, k4 = jax.random.split(rng, 4)
        p, l = {}, {}
        p["embed"], l["embed"] = L.embed_init(k1, cfg.padded_vocab, cfg.d_model, cfg.param_dtype)
        p["blocks"], l["blocks"] = _stack_init(k2, self.n_super, self._super_block_init)
        if self.n_tail:
            p["tail"], l["tail"] = _stack_init(k3, self.n_tail, self._rec_block_init)
        p["lnf"], l["lnf"] = L.norm_init(cfg.d_model)
        p["head"], l["head"] = L.dense_init(k4, cfg.d_model, cfg.padded_vocab,
                                            ("embed", "vocab"), cfg.param_dtype)
        return p, l

    # -- recurrent layer body --------------------------------------------------
    def _rec_layer(self, blk, x, state, *, decode: bool, mask=None, lengths=None):
        """state: {"h": [B, W] f32, "conv": [B, cw-1, W]}."""
        cfg = self.cfg
        h_in = L.rms_norm(x, blk["ln1"], cfg.norm_eps)
        gate = jax.nn.gelu(h_in @ blk["wgate"])
        xr = h_in @ blk["wx"]
        if mask is not None:
            xr = jnp.where(mask[..., None], xr, 0.0)
        y, conv_state = causal_conv1d(xr, blk["conv_w"], blk["conv_b"], state["conv"])
        if lengths is not None:
            # exact conv carry: last cw-1 inputs ending at the final *valid*
            # token; indices < 0 resolve into the previous conv state.
            cw = blk["conv_w"].shape[0]
            xp = jnp.concatenate([state["conv"].astype(xr.dtype), xr], axis=1)
            idx = jnp.clip(lengths[:, None] + jnp.arange(cw - 1)[None, :], 0,
                           xp.shape[1] - 1)
            conv_state = jnp.take_along_axis(xp, idx[:, :, None], axis=1)
        yf = y.astype(jnp.float32)
        r = jax.nn.sigmoid(yf @ blk["wa"].astype(jnp.float32) + blk["ba"])
        i = jax.nn.sigmoid(yf @ blk["wi"].astype(jnp.float32) + blk["bi"])
        log_a = -C_RGLRU * r * jax.nn.softplus(-blk["lam"])     # <= 0
        bx = jnp.sqrt(jnp.clip(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * (i * yf)
        if mask is not None:  # pads: a=1 (no decay), bx=0 (no write)
            m = mask[..., None]
            log_a = jnp.where(m, log_a, 0.0)
            bx = jnp.where(m, bx, 0.0)
        if decode:
            hs = rglru_step(log_a[:, 0], bx[:, 0], state["h"])
            h_seq, h_last = hs[:, None], hs
        else:
            h_seq, h_last = rglru_scan(log_a, bx, state["h"])
        out = (h_seq.astype(x.dtype) * gate) @ blk["wo"]
        x = x + out
        h2 = L.rms_norm(x, blk["ln2"], cfg.norm_eps)
        x = x + L.mlp_apply(blk["mlp"], h2, cfg.activation)
        return x, {"h": h_last, "conv": conv_state}

    # -- attention layer body ---------------------------------------------------
    def _attn_layer(self, blk, x, positions):
        cfg = self.cfg
        h = L.rms_norm(x, blk["ln1"], cfg.norm_eps)
        q, k, v = L.attn_qkv(blk["attn"], h, cfg, positions)
        o = L.causal_attention(q, k, v, window=cfg.window)
        x = x + L.attn_out(blk["attn"], o)
        h = L.rms_norm(x, blk["ln2"], cfg.norm_eps)
        x = x + L.mlp_apply(blk["mlp"], h, cfg.activation)
        return x, (k, v)

    # -- train forward ----------------------------------------------------------
    def forward(self, params, tokens, *, image_embeds=None):
        cfg = self.cfg
        B, T = tokens.shape
        x = params["embed"][tokens].astype(cfg.dtype)
        positions = jnp.broadcast_to(jnp.arange(T), (B, T))
        zero_rec = {
            "h": jnp.zeros((B, self.W), jnp.float32),
            "conv": jnp.zeros((B, cfg.conv_width - 1, self.W), cfg.dtype),
        }

        def body(x, blk):
            def rec_body(x2, rec):
                x2, _ = self._rec_layer(rec, x2, zero_rec, decode=False)
                return x2, None
            x, _ = L.xscan(rec_body, x, blk["recs"])
            x, _ = self._attn_layer(blk["attn_blk"], x, positions)
            return x, None

        x, _ = L.xscan(_remat(body, cfg.remat_policy), x, params["blocks"])
        if self.n_tail:
            def tail_body(x2, rec):
                x2, _ = self._rec_layer(rec, x2, zero_rec, decode=False)
                return x2, None
            x, _ = L.xscan(tail_body, x, params["tail"])
        x = L.rms_norm(x, params["lnf"], cfg.norm_eps)
        logits = x @ params["head"]
        if cfg.logits_softcap:
            logits = jnp.tanh(logits / cfg.logits_softcap) * cfg.logits_softcap
        return logits

    def loss_fn(self, params, batch):
        logits = self.forward(params, batch["tokens"])
        labels = batch["labels"]
        lg = logits.astype(jnp.float32)
        logz = jax.scipy.special.logsumexp(lg, axis=-1)
        ll = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
        mask = batch.get("mask", jnp.ones_like(labels, dtype=jnp.float32))
        return jnp.sum((logz - ll) * mask) / jnp.maximum(jnp.sum(mask), 1.0)

    # -- cache -------------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int):
        cfg = self.cfg
        Wn = min(cfg.window, max_len)
        K, hd = cfg.n_kv_heads, cfg.head_dim
        cw = cfg.conv_width
        cache = {
            "rec_h": jnp.zeros((self.n_super, 2, batch, self.W), jnp.float32),
            "rec_conv": jnp.zeros((self.n_super, 2, batch, cw - 1, self.W), cfg.dtype),
            "ak": jnp.zeros((self.n_super, batch, Wn, K, hd), cfg.dtype),
            "av": jnp.zeros((self.n_super, batch, Wn, K, hd), cfg.dtype),
            "apos": jnp.full((self.n_super, batch, Wn), -1, jnp.int32),
            "tail_h": jnp.zeros((self.n_tail, batch, self.W), jnp.float32),
            "tail_conv": jnp.zeros((self.n_tail, batch, cw - 1, self.W), cfg.dtype),
            "seq_lens": jnp.zeros((batch,), jnp.int32),
        }
        logical = {
            "rec_h": ("layers", "layers", "batch", "rnn"),
            "rec_conv": ("layers", "layers", "batch", None, "rnn"),
            "ak": ("layers", "batch", "kv_seq", "kv", None),
            "av": ("layers", "batch", "kv_seq", "kv", None),
            "apos": ("layers", "batch", "kv_seq"),
            "tail_h": ("layers", "batch", "rnn"),
            "tail_conv": ("layers", "batch", None, "rnn"),
            "seq_lens": ("batch",),
        }
        return cache, logical

    # -- prefill -----------------------------------------------------------------
    def prefill(self, params, tokens, cache, *, image_embeds=None, lengths=None):
        cfg = self.cfg
        B, T = tokens.shape
        Wn = cache["ak"].shape[2]
        x = params["embed"][tokens].astype(cfg.dtype)
        if lengths is None:
            lengths = jnp.full((B,), T, jnp.int32)
        positions = jnp.broadcast_to(jnp.arange(T), (B, T))
        valid = positions < lengths[:, None]

        # rolling-buffer fill: slot s holds the latest token p with p%Wn==s
        slots = jnp.arange(Wn)[None, :]                         # [1, Wn]
        p_src = lengths[:, None] - 1 - ((lengths[:, None] - 1 - slots) % Wn)
        p_valid = (slots <= lengths[:, None] - 1) & (p_src >= 0)
        p_idx = jnp.clip(p_src, 0, T - 1)

        def fill_buffer(k_full, v_full):
            ks = jnp.take_along_axis(k_full, p_idx[:, :, None, None], axis=1)
            vs = jnp.take_along_axis(v_full, p_idx[:, :, None, None], axis=1)
            pos = jnp.where(p_valid, p_src, -1)
            return ks, vs, pos

        def body(x, xs):
            blk, rh, rc = xs
            def rec_body(x2, sub):
                rec, h0, c0 = sub
                x2, ns = self._rec_layer(rec, x2, {"h": h0, "conv": c0},
                                         decode=False, mask=valid, lengths=lengths)
                return x2, (ns["h"], ns["conv"])
            x, (rh, rc) = L.xscan(rec_body, x, (blk["recs"], rh, rc))
            x, (k, v) = self._attn_layer(blk["attn_blk"], x, positions)
            ks, vs, pos = fill_buffer(k, v)
            return x, (rh, rc, ks, vs, pos)

        x, (rh, rc, ak, av, apos) = L.xscan(
            _remat(body, cfg.remat_policy), x,
            (params["blocks"], cache["rec_h"], cache["rec_conv"]))

        if self.n_tail:
            def tail_body(x2, sub):
                rec, h0, c0 = sub
                x2, ns = self._rec_layer(rec, x2, {"h": h0, "conv": c0},
                                         decode=False, mask=valid, lengths=lengths)
                return x2, (ns["h"], ns["conv"])
            x, (th, tc) = L.xscan(
                tail_body, x, (params["tail"], cache["tail_h"], cache["tail_conv"]))
        else:
            th, tc = cache["tail_h"], cache["tail_conv"]

        x = L.rms_norm(x, params["lnf"], cfg.norm_eps)
        idx = jnp.clip(lengths - 1, 0)
        last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
        logits = last @ params["head"]
        if cfg.logits_softcap:
            logits = jnp.tanh(logits / cfg.logits_softcap) * cfg.logits_softcap
        cache = dict(cache, rec_h=rh, rec_conv=rc, ak=ak, av=av, apos=apos,
                     tail_h=th, tail_conv=tc, seq_lens=lengths)
        return cache, logits

    # -- chunked prefill ----------------------------------------------------------
    def prefill_chunk(self, params, tokens, cache, *, q_offset, lengths,
                      image_embeds=None, image_mask=None, kv_width=None):
        """Chunked prefill resuming from carried state: RG-LRU h / conv
        carries and the rolling attention buffer in ``cache`` hold everything
        before position ``q_offset[b]``; this call consumes ``lengths[b]``
        more tokens. A decoding slot is a ``lengths[b] == 1`` row at its
        current position (the rolling-buffer merge then writes exactly the
        slot ``position % Wn`` a decode step would); rows with
        ``lengths[b] == 0`` keep all state untouched -- the per-model-leaf
        guard where rolling buffers and recurrent carries wrap. kv_width /
        image_mask are accepted for interface parity; the rolling buffer is
        already bounded by the attention window, so there is nothing to
        narrow."""
        cfg = self.cfg
        B, T = tokens.shape
        Wn = cache["ak"].shape[2]
        x = params["embed"][tokens].astype(cfg.dtype)
        positions = q_offset[:, None] + jnp.arange(T)[None, :]
        valid = jnp.arange(T)[None, :] < lengths[:, None]
        end = q_offset + lengths

        # rolling-buffer merge: slot s's new occupant is the latest position
        # p < end with p % Wn == s; entries older than the chunk stay put.
        slots = jnp.arange(Wn)[None, :]                          # [1, Wn]
        p_src = end[:, None] - 1 - ((end[:, None] - 1 - slots) % Wn)
        from_chunk = (p_src >= q_offset[:, None]) & (p_src >= 0) & \
            (lengths[:, None] > 0)
        c_idx = jnp.clip(p_src - q_offset[:, None], 0, T - 1)

        def merge_buffer(k_full, v_full, ak, av, apos):
            ks = jnp.take_along_axis(k_full, c_idx[:, :, None, None], axis=1)
            vs = jnp.take_along_axis(v_full, c_idx[:, :, None, None], axis=1)
            m = from_chunk[:, :, None, None]
            ak = jnp.where(m, ks.astype(ak.dtype), ak)
            av = jnp.where(m, vs.astype(av.dtype), av)
            apos = jnp.where(from_chunk, p_src, apos)
            return ak, av, apos

        def attn_chunk(blk, x, ak, av, apos):
            """Windowed attention over (rolling-buffer prefix) U (chunk)."""
            h = L.rms_norm(x, blk["ln1"], cfg.norm_eps)
            q, k, v = L.attn_qkv(blk["attn"], h, cfg, positions)
            H = q.shape[2]
            k_all = jnp.concatenate(
                [L._broadcast_kv(ak, H).astype(jnp.float32),
                 L._broadcast_kv(k, H).astype(jnp.float32)], axis=1)
            v_all = jnp.concatenate(
                [L._broadcast_kv(av, H).astype(jnp.float32),
                 L._broadcast_kv(v, H).astype(jnp.float32)], axis=1)
            kpos = jnp.concatenate([apos, positions], axis=1)    # [B, Wn+T]
            kvalid = jnp.concatenate(
                [(apos >= 0) & (apos < q_offset[:, None]), valid], axis=1)
            s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                           k_all) / math.sqrt(q.shape[-1])
            mask = kvalid[:, None, :] & (kpos[:, None, :] <= positions[:, :, None])
            mask &= kpos[:, None, :] > (positions[:, :, None] - cfg.window)
            s = jnp.where(mask[:, None], s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bhqk,bkhd->bqhd", p, v_all).astype(x.dtype)
            x = x + L.attn_out(blk["attn"], o)
            h = L.rms_norm(x, blk["ln2"], cfg.norm_eps)
            x = x + L.mlp_apply(blk["mlp"], h, cfg.activation)
            return x, merge_buffer(k, v, ak, av, apos)

        def body(x, xs):
            blk, rh, rc, ak, av, apos = xs

            def rec_body(x2, sub):
                rec, h0, c0 = sub
                x2, ns = self._rec_layer(rec, x2, {"h": h0, "conv": c0},
                                         decode=False, mask=valid,
                                         lengths=lengths)
                return x2, (ns["h"], ns["conv"])

            x, (rh, rc) = L.xscan(rec_body, x, (blk["recs"], rh, rc))
            x, (ak, av, apos) = attn_chunk(blk["attn_blk"], x, ak, av, apos)
            return x, (rh, rc, ak, av, apos)

        x, (rh, rc, ak, av, apos) = L.xscan(
            _remat(body, cfg.remat_policy), x,
            (params["blocks"], cache["rec_h"], cache["rec_conv"],
             cache["ak"], cache["av"], cache["apos"]))

        if self.n_tail:
            def tail_body(x2, sub):
                rec, h0, c0 = sub
                x2, ns = self._rec_layer(rec, x2, {"h": h0, "conv": c0},
                                         decode=False, mask=valid,
                                         lengths=lengths)
                return x2, (ns["h"], ns["conv"])
            x, (th, tc) = L.xscan(
                tail_body, x, (params["tail"], cache["tail_h"],
                               cache["tail_conv"]))
        else:
            th, tc = cache["tail_h"], cache["tail_conv"]

        x = L.rms_norm(x, params["lnf"], cfg.norm_eps)
        idx = jnp.clip(lengths - 1, 0)
        last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
        logits = last @ params["head"]
        if cfg.logits_softcap:
            logits = jnp.tanh(logits / cfg.logits_softcap) * cfg.logits_softcap
        new_lens = jnp.where(lengths > 0, end, cache["seq_lens"])
        cache = dict(cache, rec_h=rh, rec_conv=rc, ak=ak, av=av, apos=apos,
                     tail_h=th, tail_conv=tc, seq_lens=new_lens)
        return cache, logits

    def prefill_packed(self, params, tokens, cache, *, row_starts, q_offset,
                       lengths, chunk, image_embeds=None, image_mask=None,
                       kv_width=None):
        """Token-packed entry point: unpack [Np] back to the dense
        [B, chunk] buffer and delegate to ``prefill_chunk`` -- the RG-LRU
        scan is sequential per row and the attention window is already
        bounded by the rolling buffer, so packing has no rectangle to
        delete; this keeps the engine's packed layout uniform across archs,
        bitwise identical by construction (same static ``chunk`` bucket,
        gap slots unpack to the same zero pad tokens)."""
        Np = tokens.shape[0]
        idx = row_starts[:, None] + jnp.arange(chunk)[None, :]   # [B, chunk]
        dense = jnp.where(jnp.arange(chunk)[None, :] < lengths[:, None],
                          tokens[jnp.clip(idx, 0, Np - 1)], 0)
        return self.prefill_chunk(params, dense, cache, q_offset=q_offset,
                                  lengths=lengths, image_embeds=image_embeds,
                                  image_mask=image_mask, kv_width=kv_width)

    # -- decode ------------------------------------------------------------------
    def decode_step(self, params, tokens, cache):
        cfg = self.cfg
        B = tokens.shape[0]
        Wn = cache["ak"].shape[2]
        x = params["embed"][tokens][:, None].astype(cfg.dtype)
        seq_lens = cache["seq_lens"]
        positions = seq_lens[:, None]
        slot = seq_lens % Wn

        def body(x, xs):
            blk, rh, rc, ak, av, apos = xs
            def rec_body(x2, sub):
                rec, h0, c0 = sub
                x2, ns = self._rec_layer(rec, x2, {"h": h0, "conv": c0}, decode=True)
                return x2, (ns["h"], ns["conv"])
            x, (rh, rc) = L.xscan(rec_body, x, (blk["recs"], rh, rc))
            # windowed attention over rolling buffer
            h = L.rms_norm(x, blk["attn_blk"]["ln1"], cfg.norm_eps)
            q, k, v = L.attn_qkv(blk["attn_blk"]["attn"], h, cfg, positions)
            ak = L.cache_write_token(ak, k[:, 0], slot)
            av = L.cache_write_token(av, v[:, 0], slot)
            hit = jax.lax.broadcasted_iota(jnp.int32, (1, Wn), 1) == slot[:, None]
            apos = jnp.where(hit, seq_lens[:, None], apos)
            o = self._buffer_attention(q[:, 0], ak, av, apos, seq_lens)
            x = x + L.attn_out(blk["attn_blk"]["attn"], o[:, None])
            h = L.rms_norm(x, blk["attn_blk"]["ln2"], cfg.norm_eps)
            x = x + L.mlp_apply(blk["attn_blk"]["mlp"], h, cfg.activation)
            return x, (rh, rc, ak, av, apos)

        x, (rh, rc, ak, av, apos) = L.xscan(
            body, x, (params["blocks"], cache["rec_h"], cache["rec_conv"],
                      cache["ak"], cache["av"], cache["apos"]))

        if self.n_tail:
            def tail_body(x2, sub):
                rec, h0, c0 = sub
                x2, ns = self._rec_layer(rec, x2, {"h": h0, "conv": c0}, decode=True)
                return x2, (ns["h"], ns["conv"])
            x, (th, tc) = L.xscan(
                tail_body, x, (params["tail"], cache["tail_h"], cache["tail_conv"]))
        else:
            th, tc = cache["tail_h"], cache["tail_conv"]

        x = L.rms_norm(x, params["lnf"], cfg.norm_eps)
        logits = x[:, 0] @ params["head"]
        if cfg.logits_softcap:
            logits = jnp.tanh(logits / cfg.logits_softcap) * cfg.logits_softcap
        cache = dict(cache, rec_h=rh, rec_conv=rc, ak=ak, av=av, apos=apos,
                     tail_h=th, tail_conv=tc, seq_lens=seq_lens + 1)
        return cache, logits

    def _buffer_attention(self, q, ak, av, apos, seq_lens):
        """q: [B, H, hd]; rolling buffers [B, Wn, K, hd]; apos absolute pos."""
        H = q.shape[1]
        k = L._broadcast_kv(ak, H)
        v = L._broadcast_kv(av, H)
        s = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) / math.sqrt(q.shape[-1])
        mask = (apos >= 0) & (apos <= seq_lens[:, None])
        s = jnp.where(mask[:, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhk,bkhd->bhd", p, v.astype(jnp.float32)).astype(q.dtype)
