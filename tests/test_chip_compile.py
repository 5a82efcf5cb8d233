"""Ahead-of-time compiles of the four serving attention kernels for a
described TPU v5e, at yi-6b serving widths (B=8 slots, S=2048 positions,
H=32 query heads, K=4 kv heads, hd=128, bf16); of the one-token kernel at
K = 8, 4 and 32 kv heads; and of the pure-decode program of a 2-layer
model at granite-3.0-8b widths.

Nothing runs: the TPU compiler that ships with jaxlib compiles for a chip
that is described, not attached, and refuses what the chip would refuse
(unaligned blocks, SMEM shapes, VMEM overflow) -- faults that interpret
mode cannot show. The topology is described inside a fixture, never at
import time, so every xdist worker collects the same tests and only the
worker that runs this file loads the TPU library."""
import os
import re

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


def _custom_calls(hlo):
    """Instruction names of the Pallas kernels in a compiled program."""
    return re.findall(r"%([\w.-]+) = \S+ custom-call\([^\n]*"
                      r'custom_call_target="tpu_custom_call"', hlo)


@pytest.mark.parametrize("kv_heads", [8, 4, 32])
def test_one_token_chunk_compiles_for_v5e(one_chip, kv_heads):
    """The C == 1 kernel at 8 slots x 4096 positions, for granite's 8 kv
    heads, yi's 4 and an MHA cache of 32: the (bk, K, hd) blocks that
    ``decode_block_k`` picks fit the core's scoped VMEM, and the kernel
    reads the [B, S, K, hd] cache as it lies, with no transposed copy."""
    S_, K_ = 4096, kv_heads
    fn = lambda q, k, v, off, ql: da.chunk_attention(q, k, v, off, ql)
    args = (_spec(one_chip, (B, 1, H, HD)), _spec(one_chip, (B, S_, K_, HD)),
            _spec(one_chip, (B, S_, K_, HD)), _spec(one_chip, (B,), jnp.int32),
            _spec(one_chip, (B,), jnp.int32))
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    calls = _custom_calls(hlo)
    assert calls and all(c.startswith("chunk_attention_decode")
                         for c in calls), calls
    assert f"[{B},{K_},{S_},{HD}]" not in hlo


def test_mixed_decode_reads_cache_in_place(one_chip):
    """The pure-decode tick of a 2-layer model at granite-3.0-8b widths
    (8 slots x 4096, 32 heads on 8 kv heads): its attention is the
    one-token kernel, named so that the benchmark's roofline reader
    (``^chunk_attention``) finds it, and the layer loop updates the K/V
    stacks where they lie. No op builds a cache layer -- no [B, K, S, hd]
    transposed copy, no [B, S, K, hd] slice, select or write-back -- no
    stack is copied or given a fresh buffer (``AllocateBuffer``), no cache
    is placed in VMEM, and the program's temporaries stay under one cache
    layer."""
    from repro.configs.granite_3_8b import CONFIG
    from repro.kernels import ops as kops
    from repro.serving import engine as engine_mod

    slots, max_len = 8, 4096
    cfg = CONFIG.replace(num_layers=2)
    spec = lambda tree: jax.tree.map(  # noqa: E731
        lambda s: _spec(one_chip, s.shape, s.dtype), tree)
    kops.set_backend("tpu")
    try:
        js = engine_mod._EngineJits(cfg, 0.0)
        params = spec(jax.eval_shape(
            lambda k: js.model.init_params(k)[0], jax.random.key(0)))
        cache = spec(jax.eval_shape(
            lambda: js.model.init_cache(slots, max_len)[0]))
        compiled = js.mixed_decode.lower(
            params, _spec(one_chip, (slots,), jnp.int32), cache,
            _spec(one_chip, (slots,), jnp.bool_), kv=max_len).compile()
    finally:
        kops.set_backend(None)
    hlo = compiled.as_text()
    calls = _custom_calls(hlo)
    assert calls and all(c.startswith("chunk_attention") for c in calls), calls
    K_, hd = cfg.n_kv_heads, cfg.head_dim
    layer_bytes = slots * max_len * K_ * hd * 2
    assert f"[{slots},{K_},{max_len},{hd}]" not in hlo
    assert f"[{slots},{max_len},{K_},{hd}]" not in hlo
    assert "AllocateBuffer" not in hlo
    stack = rf"\[{cfg.num_layers},{slots},{max_len},{K_},{hd}\]"
    copies = re.findall(rf"= bf16{stack}\S* copy(?:-start)?\(", hlo)
    assert not copies, copies
    # the kernel reads its stack from HBM, not from a copy staged in VMEM
    # (memory space S(1))
    assert not re.search(rf"{stack}\{{[^}}]*S\(1\)\}}", hlo)
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes
