"""Ahead-of-time compiles of the four serving attention kernels for a
described TPU v5e, at yi-6b serving widths (B=8 slots, S=2048 positions,
H=32 query heads, K=4 kv heads, hd=128, bf16).

Nothing runs: the TPU compiler that ships with jaxlib compiles for a chip
that is described, not attached, and refuses what the chip would refuse
(unaligned blocks, SMEM shapes, VMEM overflow) -- faults that interpret
mode cannot show. The topology is described inside a fixture, never at
import time, so every xdist worker collects the same tests and only the
worker that runs this file loads the TPU library."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import decode_attention as da
from repro.kernels import flash_attention as fa

B, S, H, K, HD = 8, 2048, 32, 4, 128
DT = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=DT):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _chunk(sh):
    C = 256
    fn = lambda q, k, v, off, ql: da.chunk_attention(q, k, v, off, ql)
    return fn, (_spec(sh, (B, C, H, HD)), _spec(sh, (B, S, K, HD)),
                _spec(sh, (B, S, K, HD)), _spec(sh, (B,), jnp.int32),
                _spec(sh, (B,), jnp.int32))


def _decode(sh):
    fn = lambda q, k, v, sl: da.decode_attention(q, k, v, sl)
    return fn, (_spec(sh, (B, H, HD)), _spec(sh, (B, S, K, HD)),
                _spec(sh, (B, S, K, HD)), _spec(sh, (B,), jnp.int32))


def _packed(sh):
    Np = 512
    fn = lambda q, k, v, st, off, ql: da.packed_chunk_attention(
        q, k, v, st, off, ql)
    return fn, (_spec(sh, (Np, H, HD)), _spec(sh, (B, S, K, HD)),
                _spec(sh, (B, S, K, HD)), _spec(sh, (B,), jnp.int32),
                _spec(sh, (B,), jnp.int32), _spec(sh, (B,), jnp.int32))


def _flash(sh):
    Sq = 2048
    fn = lambda q, k, v, off, kl: fa.flash_attention(
        q, k, v, q_offsets=off, kv_lens=kl)
    return fn, (_spec(sh, (B, Sq, H, HD)), _spec(sh, (B, Sq, K, HD)),
                _spec(sh, (B, Sq, K, HD)), _spec(sh, (B,), jnp.int32),
                _spec(sh, (B,), jnp.int32))


@pytest.mark.parametrize("build", [_chunk, _decode, _packed, _flash],
                         ids=["chunk_attention_C256", "decode_attention",
                              "packed_chunk_attention_Np512",
                              "flash_attention_B8"])
def test_kernel_compiles_for_v5e(one_chip, build):
    fn, args = build(one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
