"""Model/run configuration dataclasses shared by all architectures.

Every assigned architecture gets a ``<id>.py`` in this package defining:
  CONFIG  -- the exact published configuration (full scale),
  SMOKE   -- a reduced same-family config for CPU smoke tests,
  SHAPES  -- the input-shape cells that apply to this arch (with skip notes).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax.numpy as jnp


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | audio | vlm
    num_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 128
    activation: str = "swiglu"  # swiglu | squared_relu
    # --- MoE ---
    num_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    dense_residual: bool = False  # arctic: dense MLP in parallel with MoE
    # Inference MoE dispatch: dropless (cap=T, exact per-token routing; used
    # by the CPU serving engine + exactness tests) vs capacity-based (honest
    # FLOPs at scale; paper-Table-7 exactness then holds for logits-based
    # restore up to capacity-drop ties -- DESIGN.md §4).
    infer_dropless: bool = True
    # --- hybrid / ssm ---
    window: int = 0            # local-attention window (recurrentgemma)
    lru_width: int = 0         # RG-LRU recurrent width
    conv_width: int = 4
    rwkv_head_dim: int = 64
    attn_every: int = 0        # hybrid: 1 attention layer every N layers
    # --- vlm / audio ---
    cross_attn_every: int = 0  # vlm: cross-attn block every N layers
    num_frontend_tokens: int = 0  # stubbed modality-frontend token count
    # --- training defaults ---
    train_accum: int = 4   # microbatch grad-accumulation (fits residuals in HBM)
    # --- numerics / misc ---
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    remat_policy: str = "nothing"  # nothing | dots | none (no remat)
    fsdp: bool = False  # additionally shard params/opt-state over data axis
    logits_softcap: float = 0.0

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // max(self.n_kv_heads, 1)

    @property
    def padded_vocab(self) -> int:
        """Vocab padded to a multiple of 256 so the embedding/head shard
        cleanly on the 16-way model axis (padded logits are masked at
        sampling; labels never reach the padded range)."""
        return ((self.vocab + 255) // 256) * 256

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # Parameter count (analytic; used for MODEL_FLOPS in the roofline).
    def param_count(self, active_only: bool = False) -> int:
        d, ff, V = self.d_model, self.d_ff, self.vocab
        H, K, hd = self.n_heads, self.n_kv_heads, self.head_dim
        emb = V * d * 2  # untied in/out embeddings
        if self.family == "ssm":  # rwkv6
            att = d * d * 4 + d * 64 * 6  # r,k,v,o (+ small lora adapters)
            mlp = d * ff * 2 + d * d
            per_layer = att + mlp
            return emb + per_layer * self.num_layers
        attn = d * (H * hd) + 2 * d * (K * hd) + (H * hd) * d
        if self.activation == "swiglu":
            mlp = 3 * d * ff
        else:
            mlp = 2 * d * ff
        if self.family == "moe":
            moe = self.num_experts * 3 * d * ff + d * self.num_experts
            dense = 3 * d * (2 * ff) if self.dense_residual else 0
            per_layer = attn + moe + dense
            if active_only:
                act_moe = self.top_k * 3 * d * ff + d * self.num_experts
                per_layer = attn + act_moe + dense
            return emb + per_layer * self.num_layers
        if self.family == "hybrid":  # recurrentgemma
            w = self.lru_width or d
            rec = d * w * 2 + w * d + w * self.conv_width + 2 * w * (w // max(1, self.n_heads)) + 2 * w
            n_attn = self.num_layers // (self.attn_every + 1) if self.attn_every else 0
            n_rec = self.num_layers - max(n_attn, self.num_layers // 3)
            n_attn = self.num_layers - n_rec
            per_attn = attn + mlp
            per_rec = rec + mlp
            return emb + per_attn * n_attn + per_rec * n_rec
        if self.family == "vlm" and self.cross_attn_every:
            n_cross = self.num_layers // self.cross_attn_every
            cross = attn  # cross-attn block adds another attention's worth
            return emb + (attn + mlp) * self.num_layers + cross * n_cross
        return emb + (attn + mlp) * self.num_layers


@dataclass(frozen=True)
class ShapeCell:
    """One (input-shape) cell of the dry-run/roofline grid."""
    name: str           # train_4k | prefill_32k | decode_32k | long_500k
    kind: str           # train | prefill | decode
    seq_len: int
    global_batch: int
    skip: Optional[str] = None  # reason, if this arch skips the cell


TRAIN_4K = ShapeCell("train_4k", "train", 4096, 256)
PREFILL_32K = ShapeCell("prefill_32k", "prefill", 32768, 32)
DECODE_32K = ShapeCell("decode_32k", "decode", 32768, 128)
LONG_500K = ShapeCell("long_500k", "decode", 524288, 1)


def lm_shapes(sub_quadratic: bool) -> Tuple[ShapeCell, ...]:
    long = LONG_500K if sub_quadratic else dataclasses.replace(
        LONG_500K, skip="full-attention arch: 512k dense-KV decode is sub-quadratic-only (DESIGN.md §4)")
    return (TRAIN_4K, PREFILL_32K, DECODE_32K, long)
