"""Mixture-of-Experts transformer (arctic-480b: 128e top-2 + dense residual;
moonshot-v1-16b-a3b: 64e top-6).

Dispatch is capacity-based sort+scatter (GShard-style, static shapes): FLOPs
scale with top_k * capacity_factor, not num_experts, so cost_analysis stays
honest for the roofline. Experts are sharded on the "experts"->model mesh axis
(expert parallelism); GSPMD inserts the dispatch all-to-alls.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.transformer import DenseTransformer, _stack_init, _remat


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def moe_init(rng, cfg) -> Tuple[Dict, Dict]:
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    k1, k2, k3, k4, k5 = jax.random.split(rng, 5)
    scale = 1.0 / math.sqrt(d)
    p, l = {}, {}
    p["router"], l["router"] = L.dense_init(k1, d, E, ("embed", None), jnp.float32)
    def ew(key, shape):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(cfg.param_dtype)
    p["wi"] = ew(k2, (E, d, ff)); l["wi"] = ("experts", "embed", "mlp")
    p["wg"] = ew(k3, (E, d, ff)); l["wg"] = ("experts", "embed", "mlp")
    p["wo"] = (jax.random.normal(k4, (E, ff, d), jnp.float32) / math.sqrt(ff)).astype(cfg.param_dtype)
    l["wo"] = ("experts", "mlp", "embed")
    return p, l


def moe_apply(p, x, cfg, *, dropless: bool = False):
    """x: [B, S, d] -> [B, S, d] plus load-balance aux loss.

    dropless=True sets capacity to T (each expert can receive every token),
    making routing execution independent per token -- required for exact
    prefill<->decode consistency in the serving engine."""
    B, S, d = x.shape
    T = B * S
    E, k = cfg.num_experts, cfg.top_k
    xt = x.reshape(T, d)

    logits = (xt.astype(jnp.float32) @ p["router"].astype(jnp.float32))  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, k)                 # [T, k]
    w = w / jnp.sum(w, axis=-1, keepdims=True)       # renormalize (mixtral-style)

    # load-balance aux loss (Switch): E * sum_e f_e * P_e
    dense_mask = jax.nn.one_hot(ids[:, 0], E)        # primary assignment
    f = jnp.mean(dense_mask, axis=0)
    Pm = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(f * Pm)

    flat_e = ids.reshape(-1)                         # [T*k]
    sort_idx = jnp.argsort(flat_e)                   # stable sort
    sorted_e = flat_e[sort_idx]
    tok = sort_idx // k                              # source token per slot
    # position within each expert's group
    seg_start = jnp.searchsorted(sorted_e, jnp.arange(E))
    pos = jnp.arange(T * k) - seg_start[sorted_e]

    if dropless:
        cap = T
    else:
        cap = min(_round_up(int(math.ceil(k * T / E * cfg.capacity_factor)), 8), T)

    # Dispatch/combine are GATHERS driven by small replicated index arrays
    # (scatters only build [E, cap] int32 tables). Scattering the activations
    # directly across the expert-sharded axis makes GSPMD replicate the full
    # dispatch tensor through collectives -- EXPERIMENTS.md §Perf hillclimb #2.
    gather_idx = jnp.zeros((E, cap), jnp.int32).at[sorted_e, pos].set(
        tok, mode="drop")
    slot_valid = jnp.zeros((E, cap), bool).at[sorted_e, pos].set(
        True, mode="drop")
    xg = jnp.where(slot_valid[..., None], xt[gather_idx], 0)   # [E, cap, d]

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xg, p["wg"])) * \
        jnp.einsum("ecd,edf->ecf", xg, p["wi"])
    y = jnp.einsum("ecf,efd->ecd", h, p["wo"])       # [E, cap, d]

    # combine in token order: slot of (token, choice) via inverse permutation
    inv = jnp.argsort(sort_idx)                      # flat assignment -> sorted slot
    pos_tok = pos[inv].reshape(T, k)
    keep_tok = (pos_tok < cap)
    y_at = y[ids, jnp.minimum(pos_tok, cap - 1)]     # [T, k, d] gather
    wk = (w * keep_tok).astype(x.dtype)[..., None]
    out = jnp.sum(y_at * wk, axis=1)
    return out.reshape(B, S, d), aux


class MoETransformer(DenseTransformer):
    """Dense attention + MoE MLP each layer; arctic adds a parallel dense
    residual MLP (cfg.dense_residual)."""

    def _block_init(self, rng):
        cfg = self.cfg
        k1, k2, k3 = jax.random.split(rng, 3)
        p, l = {}, {}
        p["ln1"], l["ln1"] = L.norm_init(cfg.d_model)
        p["attn"], l["attn"] = L.attn_init(k1, cfg)
        p["ln2"], l["ln2"] = L.norm_init(cfg.d_model)
        p["moe"], l["moe"] = moe_init(k2, cfg)
        if cfg.dense_residual:
            p["dense"], l["dense"] = L.mlp_init(k3, cfg)
        return p, l

    # -- shared layer-body pieces --------------------------------------------
    def _mlp_part(self, blk, x, *, infer: bool = False):
        cfg = self.cfg
        h = L.rms_norm(x, blk["ln2"], cfg.norm_eps)
        y, aux = moe_apply(blk["moe"], h, cfg,
                           dropless=infer and cfg.infer_dropless)
        if cfg.dense_residual:
            y = y + L.mlp_apply(blk["dense"], h, cfg.activation)
        return x + y, aux

    def _ffn(self, blk, x, *, infer: bool = False):
        """Expert-MLP feed-forward half; lets DenseTransformer.prefill_chunk
        drive MoE layers unchanged (aux loss is a training-only signal) --
        including mixed prefill+decode dispatches, where a decoding slot is a
        length-1 chunk row: ``infer_dropless`` routing is per-token, so a
        token's expert outputs are independent of the other rows' lengths
        (what keeps mixed batches bit-identical to decode_step)."""
        x, _ = self._mlp_part(blk, x, infer=infer)
        return x

    def forward(self, params, tokens, *, image_embeds=None, return_aux=False):
        cfg = self.cfg
        x = params["embed"][tokens].astype(cfg.dtype)
        B, S = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))

        def body(carry, blk):
            x, aux = carry
            h = L.rms_norm(x, blk["ln1"], cfg.norm_eps)
            q, kk, vv = L.attn_qkv(blk["attn"], h, cfg, positions)
            o = L.causal_attention(q, kk, vv)
            x = x + L.attn_out(blk["attn"], o)
            x, a = self._mlp_part(blk, x)
            return (x, aux + a), None

        (x, aux), _ = L.xscan(_remat(body, cfg.remat_policy),
                                   (x, jnp.zeros((), jnp.float32)),
                                   params["blocks"])
        x = L.rms_norm(x, params["lnf"], cfg.norm_eps)
        logits = x @ params["head"]
        if return_aux:
            return logits, aux / cfg.num_layers
        return logits

    def loss_fn(self, params, batch):
        logits, aux = self.forward(params, batch["tokens"], return_aux=True)
        labels = batch["labels"]
        lg = logits.astype(jnp.float32)
        logz = jax.scipy.special.logsumexp(lg, axis=-1)
        ll = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
        mask = batch.get("mask", jnp.ones_like(labels, dtype=jnp.float32))
        loss = jnp.sum((logz - ll) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        return loss + 0.01 * aux

    def prefill(self, params, tokens, cache, *, image_embeds=None, lengths=None):
        cfg = self.cfg
        B, S = tokens.shape
        x = params["embed"][tokens].astype(cfg.dtype)
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))

        def body(x, xs):
            blk, kc, vc = xs
            h = L.rms_norm(x, blk["ln1"], cfg.norm_eps)
            q, kk, vv = L.attn_qkv(blk["attn"], h, cfg, positions)
            o = L.causal_attention(q, kk, vv)
            x = x + L.attn_out(blk["attn"], o)
            x, _ = self._mlp_part(blk, x, infer=True)
            kc = jax.lax.dynamic_update_slice_in_dim(kc, kk, 0, axis=1)
            vc = jax.lax.dynamic_update_slice_in_dim(vc, vv, 0, axis=1)
            return x, (kc, vc)

        x, (kn, vn) = L.xscan(_remat(body, cfg.remat_policy), x,
                                   (params["blocks"], cache["k"], cache["v"]))
        if lengths is None:
            lengths = jnp.full((B,), S, jnp.int32)
        cache = dict(cache, k=kn, v=vn, seq_lens=lengths)
        x = L.rms_norm(x, params["lnf"], cfg.norm_eps)
        idx = jnp.clip(lengths - 1, 0)
        last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
        return cache, last @ params["head"]

    def decode_step(self, params, tokens, cache):
        cfg = self.cfg
        B = tokens.shape[0]
        x = params["embed"][tokens][:, None, :].astype(cfg.dtype)
        seq_lens = cache["seq_lens"]
        positions = seq_lens[:, None]

        def body(x, xs):
            blk, kc, vc = xs
            h = L.rms_norm(x, blk["ln1"], cfg.norm_eps)
            q, kk, vv = L.attn_qkv(blk["attn"], h, cfg, positions)
            kc = L.cache_write_token(kc, kk[:, 0], seq_lens)
            vc = L.cache_write_token(vc, vv[:, 0], seq_lens)
            o = L.decode_attention(q[:, 0], kc, vc, seq_lens + 1)
            x = x + L.attn_out(blk["attn"], o[:, None])
            x, _ = self._mlp_part(blk, x, infer=True)
            return x, (kc, vc)

        x, (kn, vn) = L.xscan(body, x,
                                   (params["blocks"], cache["k"], cache["v"]))
        cache = dict(cache, k=kn, v=vn, seq_lens=seq_lens + 1)
        x = L.rms_norm(x, params["lnf"], cfg.norm_eps)
        return cache, x[:, 0, :] @ params["head"]
