"""Training substrate: optimizer semantics, checkpoint atomicity/resume,
grad-accumulation equivalence, gradient compression, fault tolerance."""
import os
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.configs import get_config
from repro.models import build_model
from repro.training import (AdamW, CheckpointManager, StragglerMonitor,
                            SyntheticLM, TrainConfig, Trainer,
                            make_train_step, retry_with_backoff)
from repro.training.compression import compressed_psum, plain_psum_mean
from repro.training.optimizer import Adafactor, warmup_cosine


class TestOptimizer:
    def test_adamw_converges_quadratic(self):
        opt = AdamW(lr=lambda s: 0.1, weight_decay=0.0, clip_norm=1e9)
        params = {"w": jnp.array([3.0, -2.0])}
        state = opt.init(params)
        for _ in range(200):
            grads = {"w": 2 * params["w"]}   # d/dw ||w||^2
            params, state, _ = opt.update(grads, state, params)
        assert float(jnp.max(jnp.abs(params["w"]))) < 1e-2

    def test_adafactor_converges(self):
        opt = Adafactor(lr=lambda s: 0.05, clip_norm=1e9)
        params = {"w": jnp.ones((4, 4)) * 3.0}
        state = opt.init(params)
        for _ in range(300):
            params, state, _ = opt.update({"w": 2 * params["w"]}, state, params)
        assert float(jnp.max(jnp.abs(params["w"]))) < 0.05

    def test_grad_clip(self):
        opt = AdamW(lr=lambda s: 0.0, clip_norm=1.0)
        params = {"w": jnp.zeros(3)}
        state = opt.init(params)
        _, _, gnorm = opt.update({"w": jnp.full(3, 100.0)}, state, params)
        assert float(gnorm) > 1.0   # reported norm is pre-clip

    def test_warmup_cosine_shape(self):
        lr = warmup_cosine(1.0, warmup=10, total=100, min_ratio=0.1)
        assert float(lr(0)) == 0.0
        assert abs(float(lr(10)) - 1.0) < 1e-6
        assert float(lr(100)) == pytest.approx(0.1, abs=1e-3)

    def test_bf16_moments(self):
        opt = AdamW(moment_dtype=jnp.bfloat16)
        state = opt.init({"w": jnp.zeros((4,), jnp.bfloat16)})
        assert state["mu"]["w"].dtype == jnp.bfloat16


class TestAccumEquivalence:
    def test_accum_matches_full_batch(self):
        cfg = get_config("tiny").replace(dtype=jnp.float32,
                                         param_dtype=jnp.float32)
        model = build_model(cfg)
        params, _ = model.init_params(jax.random.key(0))
        opt = AdamW(lr=lambda s: 1e-2)
        batch = next(iter(SyntheticLM(cfg.vocab, 8, 32, seed=1)))
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        outs = {}
        for accum in (1, 4):
            step = jax.jit(make_train_step(model, opt, accum=accum))
            p2, _, m = step(params, opt.init(params), batch)
            outs[accum] = (float(m["loss"]),
                           np.asarray(jax.tree.leaves(p2)[0], np.float32))
        assert outs[1][0] == pytest.approx(outs[4][0], rel=1e-5)
        np.testing.assert_allclose(outs[1][1], outs[4][1], atol=1e-5)


class TestCheckpoint:
    def test_roundtrip_exact(self, tmp_path):
        cm = CheckpointManager(str(tmp_path), async_save=False)
        tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
                "b": {"c": jnp.ones((4,), jnp.bfloat16) * 1.5,
                      "d": jnp.array(7, jnp.int32)}}
        cm.save(3, tree)
        restored, step = cm.restore(tree)
        assert step == 3
        for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
            assert x.dtype == y.dtype
            np.testing.assert_array_equal(np.asarray(x, np.float32),
                                          np.asarray(y, np.float32))

    def test_retention_keeps_latest(self, tmp_path):
        cm = CheckpointManager(str(tmp_path), keep=2, async_save=False)
        for s in (1, 2, 3, 4):
            cm.save(s, {"x": jnp.zeros(2)})
        assert cm.list_steps() == [3, 4]

    def test_async_save_then_restore(self, tmp_path):
        cm = CheckpointManager(str(tmp_path), async_save=True)
        cm.save(5, {"x": jnp.full((8,), 2.5)})
        cm.wait()
        restored, step = cm.restore({"x": jnp.zeros(8)})
        assert step == 5 and float(restored["x"][0]) == 2.5

    def test_no_partial_checkpoints_visible(self, tmp_path):
        cm = CheckpointManager(str(tmp_path), async_save=False)
        cm.save(1, {"x": jnp.zeros(4)})
        entries = [d for d in os.listdir(tmp_path) if d.startswith(".tmp")]
        assert not entries   # atomic publish leaves no temp dirs


class TestTrainerEndToEnd:
    def test_loss_falls_and_resume(self, tmp_path):
        cfg = get_config("tiny")
        tc = TrainConfig(global_batch=8, seq_len=64, steps=24,
                         ckpt_dir=str(tmp_path), ckpt_every=12, lr=1e-2,
                         warmup=4, log_every=1000)
        tr = Trainer(cfg, tc, log=lambda m: None)
        out = tr.run()
        assert out["last_loss"] < out["first_loss"]
        tr2 = Trainer(cfg, tc, log=lambda m: None)
        assert tr2.maybe_resume() == 24


class TestCompression:
    def test_int8_psum_roundtrip(self):
        mesh = jax.make_mesh((1,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        g = {"w": jnp.linspace(-1, 1, 64).reshape(8, 8)}

        def f(grads):
            return compressed_psum(grads, ("data",))
        out = jax.shard_map(f, mesh=mesh, in_specs=(jax.sharding.PartitionSpec(),),
                            out_specs=jax.sharding.PartitionSpec())(g)
        err = float(jnp.max(jnp.abs(out["w"] - g["w"])))
        assert err < 1.0 / 127 + 1e-6   # one quantization step

    def test_plain_psum_mean_identity_on_single_device(self):
        mesh = jax.make_mesh((1,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        g = {"w": jnp.arange(4.0)}
        out = jax.shard_map(lambda x: plain_psum_mean(x, ("data",)), mesh=mesh,
                            in_specs=(jax.sharding.PartitionSpec(),),
                            out_specs=jax.sharding.PartitionSpec())(g)
        np.testing.assert_allclose(out["w"], g["w"], rtol=1e-6)


class TestFaultTolerance:
    def test_retry_with_backoff(self):
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] < 3:
                raise RuntimeError("transient")
            return "ok"
        assert retry_with_backoff(flaky, retries=3, base_delay=0.001)() == "ok"
        assert calls["n"] == 3

    def test_retry_exhausts(self):
        def always():
            raise RuntimeError("dead")
        with pytest.raises(RuntimeError):
            retry_with_backoff(always, retries=1, base_delay=0.001)()

    def test_straggler_monitor_fires(self):
        fired = []
        mon = StragglerMonitor(0.02, fired.append)
        mon.arm(step=7)
        time.sleep(0.08)
        assert fired and fired[0]["step"] == 7
        mon.disarm()

    def test_straggler_monitor_disarm(self):
        fired = []
        mon = StragglerMonitor(0.05, fired.append)
        mon.arm(step=1)
        mon.disarm()
        time.sleep(0.1)
        assert not fired
