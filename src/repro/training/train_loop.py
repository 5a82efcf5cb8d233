"""Distributed training loop: pjit-sharded train_step (DP x TP x optional
FSDP + microbatch gradient accumulation), checkpoint/resume, straggler
watchdog, retryable steps. ``make_train_step`` is shared with the multi-pod
dry-run (launch/dryrun.py lowers exactly this function).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.distributed.sharding import logical_to_spec, rules_for, spec_tree
from repro.launch.mesh import make_local_mesh
from repro.models import build_model
from repro.models.api import abstract_init
from repro.training.checkpoint import CheckpointManager
from repro.training.data import Prefetcher, SyntheticLM
from repro.training.fault_tolerance import StragglerMonitor, retry_with_backoff
from repro.training.optimizer import AdamW, make_optimizer


def make_train_step(model, optimizer, *, accum: int = 1,
                    batch_pspecs=None) -> Callable:
    """(params, opt_state, batch) -> (params, opt_state, metrics).
    accum > 1 scans over microbatches, accumulating fp32 grads.

    batch_pspecs: optional pytree of PartitionSpec matching `batch`. Without
    an explicit constraint GSPMD replicates the reshaped [accum, B/accum, ...]
    microbatches across the data axis (a silent accum-x flops blowup)."""

    def grads_of(params, batch):
        return jax.value_and_grad(model.loss_fn)(params, batch)

    def train_step(params, opt_state, batch):
        if accum == 1:
            loss, grads = grads_of(params, batch)
        else:
            def split(x):
                return x.reshape(accum, x.shape[0] // accum, *x.shape[1:])
            micro = jax.tree.map(split, batch)
            if batch_pspecs is not None:
                from jax.sharding import PartitionSpec as _P
                micro = jax.tree.map(
                    lambda x, s: jax.lax.with_sharding_constraint(
                        x, _P(*((None,) + tuple(s)))),
                    micro, batch_pspecs)
            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

            def body(carry, mb):
                lsum, gsum = carry
                loss, g = grads_of(params, mb)
                gsum = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), gsum, g)
                return (lsum + loss, gsum), None

            from repro.models import layers as _L
            (lsum, gsum), _ = _L.xscan(body, (jnp.zeros(()), g0), micro)
            loss = lsum / accum
            grads = jax.tree.map(
                lambda g, p: (g / accum).astype(p.dtype), gsum, params)
        params, opt_state, gnorm = optimizer.update(grads, opt_state, params)
        return params, opt_state, {"loss": loss, "grad_norm": gnorm}

    return train_step


@dataclasses.dataclass
class TrainConfig:
    global_batch: int = 8
    seq_len: int = 128
    steps: int = 100
    accum: int = 1
    optimizer: str = "adamw"
    lr: float = 3e-4
    warmup: int = 20
    moment_dtype: Any = jnp.float32
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    keep_ckpts: int = 3
    step_deadline_s: float = 600.0
    seed: int = 0
    log_every: int = 10


class Trainer:
    def __init__(self, cfg, tc: TrainConfig, mesh=None, data=None,
                 log: Callable[[str], None] = print):
        self.cfg = cfg
        self.tc = tc
        self.log = log
        self.mesh = mesh if mesh is not None else make_local_mesh()
        self.model = build_model(cfg)
        from repro.training.optimizer import warmup_cosine
        opt_kw = {"lr": warmup_cosine(tc.lr, tc.warmup, tc.steps)}
        if tc.optimizer == "adamw":
            opt_kw["moment_dtype"] = tc.moment_dtype
        self.optimizer = make_optimizer(tc.optimizer, **opt_kw)
        self.rules = rules_for(cfg, self.mesh)

        # shardings from logical axes
        shapes, logical = abstract_init(self.model)
        pspecs = spec_tree(logical, self.rules)
        self.param_sharding = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), pspecs,
            is_leaf=lambda x: isinstance(x, P))
        self.batch_spec = NamedSharding(
            self.mesh, logical_to_spec(("batch", "seq"), self.rules))

        with jax.set_mesh(self.mesh):
            init_fn = jax.jit(
                lambda k: self.model.init_params(k)[0],
                out_shardings=self.param_sharding)
            self.params = init_fn(jax.random.key(tc.seed))
            opt_sharding = self._opt_sharding()
            self.opt_state = jax.jit(
                self.optimizer.init, out_shardings=opt_sharding)(self.params)
            bps = {k: logical_to_spec(("batch", "seq"), self.rules)
                   for k in ("tokens", "labels")}
            self.train_step = jax.jit(
                make_train_step(self.model, self.optimizer, accum=tc.accum,
                                batch_pspecs=bps if tc.accum > 1 else None),
                in_shardings=(self.param_sharding, opt_sharding,
                              self.batch_spec),
                out_shardings=(self.param_sharding, opt_sharding, None),
                donate_argnums=(0, 1))

        self.data = data if data is not None else SyntheticLM(
            cfg.vocab, tc.global_batch, tc.seq_len, seed=tc.seed)
        self.ckpt = CheckpointManager(tc.ckpt_dir, keep=tc.keep_ckpts) \
            if tc.ckpt_dir else None
        self.monitor = StragglerMonitor(tc.step_deadline_s,
                                        lambda info: log(f"straggler: {info}"))
        self.start_step = 0
        self.history: list = []

    def _opt_sharding(self):
        def mirror(state_tmpl):
            # mu/nu mirror param shardings; scalars replicated
            rep = NamedSharding(self.mesh, P())
            if isinstance(state_tmpl, dict):
                out = {}
                for k, v in state_tmpl.items():
                    if k in ("mu", "nu", "vr", "vc"):
                        out[k] = self.param_sharding if k in ("mu", "nu") else \
                            jax.tree.map(lambda _: rep, v)
                    else:
                        out[k] = rep
                return out
            return rep
        tmpl = jax.eval_shape(self.optimizer.init, self.params)
        if "mu" in tmpl:
            return {"mu": self.param_sharding, "nu": self.param_sharding,
                    "step": NamedSharding(self.mesh, P())}
        rep = NamedSharding(self.mesh, P())
        return jax.tree.map(lambda _: rep, tmpl)

    # -- resume ---------------------------------------------------------------------
    def maybe_resume(self) -> int:
        if self.ckpt is None or self.ckpt.latest_step() is None:
            return 0
        state = {"params": self.params, "opt": self.opt_state}
        shardings = {"params": self.param_sharding,
                     "opt": self._opt_sharding()}
        restored, step = self.ckpt.restore(state, shardings=shardings)
        self.params, self.opt_state = restored["params"], restored["opt"]
        self.start_step = step
        self.log(f"resumed from checkpoint step {step}")
        return step

    # -- run -------------------------------------------------------------------------
    def run(self, steps: Optional[int] = None) -> Dict[str, Any]:
        steps = steps if steps is not None else self.tc.steps
        it = Prefetcher(self.data)
        step_fn = retry_with_backoff(self._one_step, retries=2,
                                     on_retry=lambda a, e: self.log(
                                         f"step retry {a}: {e}"))
        t0 = time.time()
        with jax.set_mesh(self.mesh):
            for step in range(self.start_step, steps):
                batch = next(it)
                self.monitor.arm(step)
                metrics = step_fn(batch)
                self.monitor.disarm()
                self.history.append(metrics)
                if step % self.tc.log_every == 0:
                    self.log(f"step {step:5d} loss {metrics['loss']:.4f} "
                             f"gnorm {metrics['grad_norm']:.3f}")
                if self.ckpt and (step + 1) % self.tc.ckpt_every == 0:
                    self.ckpt.save(step + 1, {"params": self.params,
                                              "opt": self.opt_state})
        it.close()
        if self.ckpt:
            self.ckpt.save(steps, {"params": self.params,
                                   "opt": self.opt_state}, blocking=True)
        dt = time.time() - t0
        losses = [m["loss"] for m in self.history]
        return {"steps": len(self.history), "seconds": dt,
                "first_loss": losses[0] if losses else None,
                "last_loss": losses[-1] if losses else None}

    def _one_step(self, batch) -> Dict[str, float]:
        batch = {k: jax.device_put(v, self.batch_spec)
                 for k, v in batch.items()}
        self.params, self.opt_state, metrics = self.train_step(
            self.params, self.opt_state, batch)
        return {k: float(v) for k, v in metrics.items()}
