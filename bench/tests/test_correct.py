"""The comparison that decides ``correct``, on the CPU at a size a test run
holds: the reference against the served model, the int8 control, and runs
of the harness with the served path broken underneath.

Each harness run boots the kernel on a 2-layer model of width 256 with two
slots, warms up, drives closed-loop traffic for a few seconds, and checks a
sample of what it served, as a chip run does. The readings at this size
(program 0.011-0.014, control 0.14-0.15, two seeds) set LIMIT between them.
"""
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness

LIMIT = 0.05
D = 256


def _spec():
    config = {
        "name": "small", "family": "dense", "hidden_size": D,
        "intermediate_size": 2 * D, "num_hidden_layers": 2,
        "num_attention_heads": D // 32, "num_key_value_heads": 2,
        "vocab_size": 2048, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
        "serving": {"max_slots": 2, "max_len": 2048, "dtype": "bfloat16"},
        "program": {"base_arch": "tiny", "overrides": {
            "d_model": D, "n_heads": D // 32, "n_kv_heads": 2, "head_dim": 32,
            "d_ff": 2 * D, "vocab": 2048, "rope_theta": 10000.0}}}
    traffic = {"kind": "closed_loop", "warmup": "shape_grid", "clients": 2,
               "lead_s": 2, "population_seed": 3,
               "prompt_tokens": [300, 500], "output_tokens": [40, 60],
               "requests": 30}
    limits = {"logit_gap": LIMIT, "sample_tokens": 120, "sample_passes": 3,
              "min_tokens": 8}
    bench = harness.load_json("BENCHMARK.json")
    return harness.CellSpec(name="small", chips=1, config=config,
                            traffic=traffic, limits=limits,
                            end_to_end=bench["end_to_end"], per_layer=[])


def _patched_away(fn):
    from repro.serving import engine as engine_mod
    engine_mod._JIT_CACHE.clear()      # programs trace again, as patched
    try:
        return fn()
    finally:
        engine_mod._JIT_CACHE.clear()


def _run(seed):
    return _patched_away(lambda: harness.run_cell(
        _spec(), seed, 6.0, False, time.perf_counter(), lambda m: None,
        peaks=None))


def test_program_passes_and_control_fails():
    """The program's tokens pass the comparison a run makes; the tokens
    the int8 control puts first, at the same positions, fail it."""
    spec, seed = _spec(), 2 ** 31 + 101
    served = _patched_away(lambda: harness.serve(
        spec, seed, 6.0, False, time.perf_counter(), lambda m: None))
    g = harness.reference_gaps(spec, served, seed, lambda m: None,
                               quant=True)
    assert g["tokens"] >= 100
    assert harness.judge(g["widest_gap"], g["tokens"], spec.limits)[0], g
    assert not harness.judge(g["control_gap"], g["tokens"], spec.limits)[0]


def test_altered_token_fails(monkeypatch):
    from repro.serving import sampler
    orig = sampler.sample

    def off_by_one(logits, keys, counters, temp):
        return (orig(logits, keys, counters, temp) + 1) % 2048

    monkeypatch.setattr(sampler, "sample", off_by_one)
    res = _run(2 ** 31 + 102)
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > LIMIT


def test_state_left_unchanged_fails(monkeypatch):
    """The chunk and packed steps return the cache they were given: no
    K/V is ever written, so decoding reads no context."""
    from repro.models.transformer import DenseTransformer
    chunk, packed = DenseTransformer.prefill_chunk, \
        DenseTransformer.prefill_packed

    def keep(fn):
        def step(self, params, tokens, cache, **kw):
            out = fn(self, params, tokens, cache, **kw)
            return (cache,) + tuple(out[1:])
        return step

    monkeypatch.setattr(DenseTransformer, "prefill_chunk", keep(chunk))
    monkeypatch.setattr(DenseTransformer, "prefill_packed", keep(packed))
    res = _run(2 ** 31 + 103)
    assert not res["correct"]


def test_reference_matches_served_model_logits():
    """Prefill and decode of the served model, in float32 at ``highest``,
    against the reference on the same seeded weights."""
    from repro.configs import get_config
    from repro.models import build_model

    from bench.reference import dense

    cfg = get_config("tiny").replace(dtype=jnp.float32,
                                     param_dtype=jnp.float32)
    model = build_model(cfg)
    shapes = jax.eval_shape(lambda k: model.init_params(k)[0],
                            jax.random.key(0))
    params = dense.make_params(shapes, 2 ** 33 + 5)
    spec = {"hidden_size": 64, "num_attention_heads": 4,
            "num_key_value_heads": 2, "intermediate_size": 128,
            "vocab_size": 512, "num_hidden_layers": 2,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps}
    toks = np.random.default_rng(0).integers(1, 511, 48).astype(np.int32)
    P = 40
    with jax.default_matmul_precision("highest"):
        cache, _ = model.init_cache(1, 64)
        cache, last = model.prefill(params, jnp.asarray(toks[None, :P]),
                                    cache)
        got = [np.asarray(last[0])]
        for t in toks[P:]:
            cache, lg = model.decode_step(params, jnp.asarray([t]), cache)
            got.append(np.asarray(lg[0]))
    ref = dense.logits_at(params, spec, toks, np.arange(P - 1, len(toks)))
    np.testing.assert_allclose(np.stack(got)[:, :512], ref, atol=1e-4,
                               rtol=1e-4)
    # the control differs from the reference beyond float32 rounding
    ctl = dense.logits_at(params, spec, toks, np.arange(P - 1, len(toks)),
                          quant=True)
    assert np.abs(ctl - ref).max() > 1e-2
