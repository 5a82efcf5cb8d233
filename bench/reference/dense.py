"""Plain float32 reference of a dense decoder-only transformer.

Written from the published equations of the Llama-style block that Yi-6B
and Granite-3.0 use, not from the served model's code:

    h   = x + Wo . Attn(RoPE(Wq . n1(x)), RoPE(Wk . n1(x)), Wv . n1(x))
    out = h + Wd . (silu(Wg . n2(h)) * Wu . n2(h))
    n(x) = x / sqrt(mean(x^2) + eps) * g                       (RMSNorm)

RoPE uses the rotate-half convention of the Llama modeling code, with
inverse frequencies theta^(-i/half); attention is causal softmax attention
with grouped query heads (query head h reads kv head h // (H / K)) scaled by
1/sqrt(head_dim). Weights stay in the type they are served in (bf16) and are
widened to float32; every product runs at ``Precision.HIGHEST``.

The weights live in the layout the served model takes (``embed``,
``blocks`` stacked on a leading layer axis, ``lnf``, ``head``), and this
module makes them itself from the seed (``make_params``), so the reference
reads nothing the served program made. It runs one layer at a time, so a
check fits beside nothing else on the chip once the served model is freed.

``quant=True`` is the control: the same forward with every weight product
computed in int8 (weights per output channel, activations per token, both
symmetric), the precision one step below the configuration's bf16.
"""
from __future__ import annotations

import functools
import math
import zlib
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
SEQ_BUCKET = 512        # sequences pad to a multiple of this (few programs)
Q_BLOCK = 256           # query rows per attention block


def dims(cfg: Dict[str, Any]) -> tuple:
    """(layers, d, heads, kv_heads, head_dim, d_ff, vocab, rope_theta, eps)
    from a configuration file's published keys."""
    d = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    return (cfg["num_hidden_layers"], d, H, cfg["num_key_value_heads"],
            cfg.get("head_dim", d // H), cfg["intermediate_size"],
            cfg["vocab_size"], float(cfg["rope_theta"]),
            float(cfg["rms_norm_eps"]))


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------
def _leaf_key(base, path: str):
    return jax.random.fold_in(base, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def _draw(key, name: str, shape, dtype):
    """One leaf (or one layer of a stacked leaf). Norm gains 1 + 0.1 N;
    the embedding 0.02 N; every matrix N / sqrt(fan_in). Matrices are drawn
    in blocks of rows so no float32 copy of a whole leaf exists."""
    if len(shape) == 1:
        return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)
    scale = 0.02 if name == "embed" else 1.0 / math.sqrt(shape[0])
    rows = shape[0]
    blk = math.gcd(rows, 256)

    def block(i):
        k = jax.random.fold_in(key, i)
        return (scale * jax.random.normal(k, (blk,) + tuple(shape[1:]),
                                          jnp.float32)).astype(dtype)

    out = jax.lax.map(block, jnp.arange(rows // blk))
    return out.reshape(shape)


def seed_key(seed: int):
    """A PRNG key from a seed of any size (run seeds may exceed 32 bits)."""
    seed = int(seed)
    k = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0x7FFFFFFF)


def make_params(shapes, seed: int):
    """Weights for the served layout ``shapes`` (a tree of
    ShapeDtypeStruct) drawn from ``seed`` on the device, in one jitted call,
    in the dtypes given. Leaves under ``blocks`` carry the layer axis first
    and are drawn one layer at a time."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        out = []
        for path, sd in flat:
            names = [getattr(p, "key", str(p)) for p in path]
            k = _leaf_key(key, "/".join(names))
            name = names[-1] if len(names) == 1 else names[-2] + "/" + names[-1]
            if names[0] == "blocks":
                layer = functools.partial(_draw, name=name,
                                          shape=sd.shape[1:], dtype=sd.dtype)
                out.append(jax.lax.map(
                    lambda i: layer(jax.random.fold_in(k, i)),
                    jnp.arange(sd.shape[0])))
            else:
                out.append(_draw(k, names[-1], sd.shape, sd.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(seed_key(seed))


# ---------------------------------------------------------------------------
# the forward pass, one layer per call
# ---------------------------------------------------------------------------
def _fake_int8(x, axis):
    """Symmetric int8 round trip along ``axis`` (the reduced axis holds the
    values that share one scale)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _mm(x, w, quant: bool):
    w = w.astype(jnp.float32)
    if quant:
        x = _fake_int8(x, -1)          # per token
        w = _fake_int8(w, 0)           # per output channel
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _rope(x, pos, theta):
    """x [S, heads, hd]; rotate-half RoPE at integer positions ``pos``."""
    half = x.shape[-1] // 2
    inv = jnp.exp(-jnp.arange(half, dtype=jnp.float32) * (math.log(theta)
                                                          / half))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal GQA attention. q [S, H, hd]; k, v [S, K, hd] -> [S, H*hd]."""
    S, H, hd = q.shape
    K = k.shape[1]
    g = H // K
    nb = S // Q_BLOCK
    qb = q.reshape(nb, Q_BLOCK, K, g, hd)
    kpos = jnp.arange(S)

    def block(args):
        i, qi = args
        s = jnp.einsum("qkgd,skd->kgqs", qi, k, precision=HIGHEST)
        s = s / math.sqrt(hd)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, None, None, :] <= qpos[None, None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", p, v, precision=HIGHEST)

    o = jax.lax.map(block, (jnp.arange(nb), qb))
    return o.reshape(S, H * hd)


@functools.partial(jax.jit, static_argnames=("dm", "quant"))
def _layer(x, blocks, layer, dm, quant):
    _, d, H, K, hd, _, _, theta, eps = dm
    blk = jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False),
        blocks)
    S = x.shape[0]
    pos = jnp.arange(S)
    h = _rms(x, blk["ln1"], eps)
    a = blk["attn"]
    q = _rope(_mm(h, a["wq"], quant).reshape(S, H, hd), pos, theta)
    k = _rope(_mm(h, a["wk"], quant).reshape(S, K, hd), pos, theta)
    v = _mm(h, a["wv"], quant).reshape(S, K, hd)
    x = x + _mm(_attention(q, k, v), a["wo"], quant)
    h = _rms(x, blk["ln2"], eps)
    m = blk["mlp"]
    u = jax.nn.silu(_mm(h, m["wg"], quant)) * _mm(h, m["wi"], quant)
    return x + _mm(u, m["wo"], quant)


@functools.partial(jax.jit, static_argnames=("dm", "quant"))
def _head(x, lnf, head, rows, dm, quant):
    vocab, eps = dm[6], dm[8]
    h = _rms(x[rows], lnf, eps)
    return _mm(h, head[:, :vocab], quant)


def logits_at(params, cfg: Dict[str, Any], tokens: Sequence[int],
              positions: Sequence[int], *, quant: bool = False) -> np.ndarray:
    """Float32 logits [len(positions), vocab] of the causal forward pass
    over ``tokens``, read at ``positions`` (index i predicts token i + 1).
    ``quant`` selects the int8 control."""
    dm = dims(cfg)
    n = len(tokens)
    S = -(-n // SEQ_BUCKET) * SEQ_BUCKET
    buf = np.zeros((S,), np.int32)
    buf[:n] = np.asarray(tokens, np.int32)
    x = params["embed"][jnp.asarray(buf)].astype(jnp.float32)
    for layer in range(dm[0]):
        x = _layer(x, params["blocks"], jnp.int32(layer), dm, quant)
    rows = jnp.asarray(np.asarray(positions, np.int32))
    return np.asarray(_head(x, params["lnf"], params["head"], rows, dm,
                            quant))


def gaps_of(ref: np.ndarray, picked: np.ndarray) -> np.ndarray:
    """How far below the reference's best logit each picked token lies."""
    ref = np.asarray(ref, np.float32)
    return ref.max(-1) - ref[np.arange(len(picked)), np.asarray(picked)]
