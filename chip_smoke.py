#!/usr/bin/env python3
"""Smoke test of the serving path on one TPU chip, at full model width.

    python chip_smoke.py [--seed N]

Boots ``AIOSKernel`` (scheduler "batched", one core, 8 slots, max_len 2048)
over ``configs/yi_6b.py`` at its published width and depth (32 layers,
d=4096, 32 heads, GQA kv=4, d_ff=11008, vocab 64000), with random weights
drawn from ``--seed``. In one process it then:

1. checks the four serving attention kernels (chunk, decode, packed, flash)
   against ``kernels/ref.py`` at serving shapes;
2. serves LLM syscalls through the kernel's submit path -- mixed prompt
   lengths (one over 256 tokens, so chunked and packed prefill run), a
   prompt served twice and once extended (prefix-cache hits), greedy
   decoding of 32+ new tokens each -- and fails if any syscall fails;
3. checks every served token against a plain float32 reference: the same
   weights, teacher-forced through ``model.forward`` on the jnp attention
   path at ``highest`` matmul precision;
4. confirms that the compiled serving programs hold Pallas kernels
   (``tpu_custom_call``).

It prints what it measured, then, as its last line, one JSON object
``{"ok": true, "device": {...}}``. It exits non-zero, printing no such line,
when JAX finds no TPU, when the repository's ``src`` is missing, or when any
phase fails.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

SLOTS, MAX_LEN = 8, 2048
# kernel vs kernels/ref.py: |out - ref| <= KERNEL_TOL * (1 + |ref|), the
# bf16 tolerance of tests/test_kernels.py (bf16 inputs and outputs, float32
# softmax and accumulation in both)
KERNEL_TOL = 2e-2
# served token vs float32 reference: a served token must be the reference
# argmax, unless the reference's top-2 logit gap at that position is below
# GAP_TOL standard deviations of the reference logits there. The served
# path runs bf16 activations; at d=512 on the CPU its logits differ from
# the float32 reference by up to 0.1 std, and a near-tie within that can
# flip the argmax.
GAP_TOL = 0.25
REF_LEN = 512            # teacher-forced reference length (causal: padding
                         # past a request's tokens changes none of its logits)


class SmokeFailure(RuntimeError):
    pass


def _check(cond: bool, what: str):
    if not cond:
        raise SmokeFailure(what)


def memory_line(dev, phase: str) -> str:
    """Device bytes in use now, and the process's peak so far."""
    st = dev.memory_stats() or {}
    return (f"memory after {phase}: bytes_in_use {st.get('bytes_in_use')} "
            f"peak_bytes_in_use {st.get('peak_bytes_in_use')}")


class CompileClock:
    """Sums JAX's compile-phase durations (trace, lowering, backend compile)
    reported through ``jax.monitoring``."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name.startswith("/jax/core/compile/"):
            self.seconds += secs
            if name.endswith("backend_compile_duration"):
                self.programs += 1


# ---------------------------------------------------------------------------
# phase 1: the four serving kernels against kernels/ref.py
# ---------------------------------------------------------------------------
def check_kernels(cfg, seed: int, log) -> dict:
    """Each kernel at the engine's serving shapes (B=SLOTS rows, S=MAX_LEN
    positions, the config's heads) against its reference, computed at
    ``highest`` precision. Packed and flash use fewer query tokens than a
    full dispatch: their references materialize [Np, S, H, hd] and
    [B, H, Sq, Skv] float32 tensors."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    B, S, dt = SLOTS, MAX_LEN, cfg.dtype
    ks = iter(jax.random.split(jax.random.key(seed + 101), 16))

    def rnd(*shape):
        return jax.random.normal(next(ks), shape, jnp.float32).astype(dt)

    def err(out, want, valid=None):
        o = np.asarray(out, np.float32)
        w = np.asarray(want, np.float32)
        if valid is not None:
            o, w = o[valid], w[valid]
        e = np.abs(o - w)
        return float(e.max()), bool(np.all(e <= KERNEL_TOL * (1 + np.abs(w))))

    def reference(fn, *a, **kw):
        with jax.default_matmul_precision("highest"):
            return jax.jit(lambda *x: fn(*x, **kw))(*a)

    kc, vc = rnd(B, S, K, hd), rnd(B, S, K, hd)
    out = {}

    # chunked prefill rows: full chunks, decode rows (q_len 1), idle rows
    C = min(256, S)
    q = rnd(B, C, H, hd)
    offs = jnp.array([0, S // 20, S - C, S // 4, S // 2, 0, 3 * S // 4,
                      S // 2], jnp.int32)
    qlens = jnp.array([C, 1, 0, 200, C, 17, 1, C], jnp.int32)
    got = ops.chunk_attention(q, kc, vc, offs, qlens)
    want = reference(ref.chunk_attention_ref, q, kc, vc, offs, qlens)
    valid = np.arange(C)[None, :] < np.asarray(qlens)[:, None]
    out["chunk_attention"] = err(got, want, valid)

    # decode: one token per slot at contexts from 1 to S
    q1 = rnd(B, H, hd)
    lens = jnp.array([1, 37, S, S // 7, S // 2, 5, S // 3, 3 * S // 4],
                     jnp.int32)
    got = ops.decode_attention(q1, kc, vc, lens)
    want = reference(ref.decode_attention_ref, q1, kc, vc, lens)
    out["decode_attention"] = err(got, want)

    # packed: rows of 16, 1, 0, 7, 1, 8, 3, 1 tokens, starts aligned to the
    # kernel's q block
    plens = np.array([16, 1, 0, 7, 1, 8, 3, 1], np.int32)
    al = ops.packed_row_align()
    starts = np.concatenate([[0], np.cumsum(-(-plens // al) * al)[:-1]])
    Np = int(starts[-1] + -(-plens[-1] // al) * al)
    qp = rnd(Np, H, hd)
    poffs = jnp.array([0, 64, S - 48, S // 7, S - 1, S // 2, 7, 3 * S // 4],
                      jnp.int32)
    st, pl = jnp.asarray(starts, jnp.int32), jnp.asarray(plens)
    got = ops.packed_chunk_attention(qp, kc, vc, st, poffs, pl)
    want = reference(ref.packed_chunk_attention_ref, qp, kc, vc, st, poffs,
                     pl)
    row = np.searchsorted(starts, np.arange(Np), side="right") - 1
    pvalid = (np.arange(Np) - starts[row]) < plens[row]
    out["packed_chunk_attention"] = err(got, want, pvalid)

    # flash (serial prefill): two sequences of S, one with a shorter kv_len
    qf, kf, vf = rnd(2, S, H, hd), rnd(2, S, K, hd), rnd(2, S, K, hd)
    klens = jnp.array([S, 3 * S // 5], jnp.int32)
    zero = jnp.zeros((2,), jnp.int32)
    got = ops.flash_attention(qf, kf, vf, q_offsets=zero, kv_lens=klens)
    want = reference(ref.flash_attention_ref, qf, kf, vf, q_offsets=zero,
                     kv_lens=klens)
    out["flash_attention"] = err(got, want)

    for name, (e, ok) in out.items():
        log(f"kernel {name}: max|kernel-ref| {e!r} "
            f"(tol {KERNEL_TOL} * (1 + |ref|)) {'ok' if ok else 'FAIL'}")
    del kc, vc, q, q1, qp, qf, kf, vf, got, want
    gc.collect()
    return out


# ---------------------------------------------------------------------------
# phase 2: serve through the kernel
# ---------------------------------------------------------------------------
def serve(kernel, cfg, seed: int, log):
    """Four waves through ``kernel.submit``; returns the settled syscalls:
    1. seven prompts of mixed lengths (16..300 tokens), cold -- every
       serving program compiles here;
    2. one 96-token prompt P;
    3. P again (exact prefix-cache hit) and P plus 40 tokens (the cached P
       is restored, only the suffix prefills);
    4. fresh prompts of wave 1's lengths, warm."""
    import jax
    from repro.sdk.query import LLMQuery

    rng = np.random.default_rng(seed)

    def prompt(n):
        return rng.integers(1, cfg.vocab, n).astype(np.int32).tolist()

    lengths = [300, 17, 45, 130, 64, 200, 96]
    max_new = [32, 40, 32, 48, 32, 36, 32]
    P = prompt(96)
    waves = [
        ("cold mixed", [(prompt(n), m) for n, m in zip(lengths, max_new)]),
        ("prefix seed", [(P, 32)]),
        ("prefix hits", [(P, 32), (P + prompt(40), 32)]),
        ("warm mixed", [(prompt(n), m) for n, m in zip(lengths, max_new)]),
    ]
    engine = kernel.pool.cores[0].engine
    settled = []
    for name, reqs in waves:
        hits0 = engine.stats["prefix_hits"]
        t0 = time.perf_counter()
        scs = []
        for i, (p, m) in enumerate(reqs):
            sc = LLMQuery(prompt=p, max_new_tokens=m,
                          temperature=0.0).to_syscall(f"agent{len(settled) + i}")
            kernel.submit(sc)
            scs.append(sc)
        for sc in scs:
            sc.join(timeout=900)
        wall = time.perf_counter() - t0
        for sc in scs:
            _check(sc.status == "done", f"syscall {sc.pid} {sc.status}: "
                   f"{sc.error}")
            got = len(sc.response["tokens"])
            want = sc.request_data["max_new_tokens"]
            _check(got == want, f"syscall {sc.pid}: {got} of {want} tokens")
        lat = [sc.end_time - sc.created_time for sc in scs]
        log(memory_line(jax.devices()[0], f"wave {name}"))
        hits = engine.stats["prefix_hits"] - hits0
        log(f"wave {name}: {len(scs)} syscalls, "
            f"{sum(len(sc.response['tokens']) for sc in scs)} tokens, "
            f"wall {wall!r} s, per request mean {float(np.mean(lat))!r} s "
            f"max {float(np.max(lat))!r} s, prefix hits {hits}")
        if name == "prefix hits":
            _check(hits >= 2, f"prefix-cache hits {hits} < 2 in wave {name}")
            used = [sc.response["usage"]["prompt_tokens"] for sc in scs]
            log(f"prefix hits: prompt tokens prefilled {used} "
                f"(of {[len(sc.request_data['prompt']) for sc in scs]})")
            same = scs[0].response["tokens"] == settled[-1].response["tokens"]
            log(f"prefix hits: exact-hit tokens equal the first serving of "
                f"P: {same}")
        settled.extend(scs)
    return settled


# ---------------------------------------------------------------------------
# phase 3: served tokens against the float32 reference
# ---------------------------------------------------------------------------
def check_against_reference(cfg, params, settled, log) -> bool:
    """Teacher-force each request's prompt + served tokens through
    ``model.forward`` with float32 activations (bf16 weights as served) on
    the jnp attention path, and compare each served token with the
    reference argmax at its position."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.models import build_model

    ref_model = build_model(cfg.replace(dtype=jnp.float32))

    def fwd(p, t):
        with jax.default_matmul_precision("highest"):
            return ref_model.forward(p, t)

    served_backend = ops.default_backend()
    ops.set_backend("jnp")
    try:
        ref_fwd = jax.jit(fwd)
        checked = 0
        gaps = []               # top-2 gap, in std, where a token differs
        for sc in settled:
            p = np.asarray(sc.request_data["prompt"], np.int32)
            gen = np.asarray(sc.response["tokens"], np.int32)
            seq = np.concatenate([p, gen[:-1]])
            _check(len(seq) <= REF_LEN, f"reference length {len(seq)}")
            buf = np.zeros((1, REF_LEN), np.int32)
            buf[0, :len(seq)] = seq
            lg = np.asarray(ref_fwd(params, jnp.asarray(buf))[0],
                            np.float32)
            lg = lg[len(p) - 1:len(seq), :cfg.vocab]
            top2 = np.sort(lg, axis=-1)[:, -2:]
            gap = (top2[:, 1] - top2[:, 0]) / lg.std(axis=-1)
            bad = np.nonzero(lg.argmax(-1) != gen)[0]
            checked += len(gen)
            gaps += [float(g) for g in gap[bad]]
    finally:
        ops.set_backend(served_backend)
    ok = all(g < GAP_TOL for g in gaps)
    log(f"reference: {checked} served tokens checked, {len(gaps)} differ "
        f"from the float32 argmax, at top-2 gaps {sorted(gaps)} std "
        f"(tol {GAP_TOL} std) {'ok' if ok else 'FAIL'}")
    return ok


# ---------------------------------------------------------------------------
# phase 4: the compiled serving programs hold Pallas kernels
# ---------------------------------------------------------------------------
def serving_programs_use_kernels(engine, marker: str, log) -> bool:
    """Lower and compile each serving program family of the engine (packed
    dispatch, padded chunk dispatch, pure-decode tick, serial prefill) and
    look for the Pallas kernel's custom call in the compiled text."""
    import jax.numpy as jnp

    js, n = engine._jits, engine.max_slots
    i32 = lambda *s: jnp.zeros(s, jnp.int32)                    # noqa: E731
    progs = {
        "prefill_packed": lambda: js.prefill_packed.lower(
            engine.params, i32(64), engine.cache, i32(n), i32(n), i32(n),
            kv=engine.max_len, chunk=64),
        "prefill_chunk": lambda: js.prefill_chunk.lower(
            engine.params, i32(n, 32), engine.cache, i32(n), i32(n),
            kv=engine.max_len),
        "mixed_decode": lambda: js.mixed_decode.lower(
            engine.params, i32(n), engine.cache, jnp.ones((n,), bool),
            kv=engine.max_len),
        "prefill": lambda: js.prefill.lower(
            engine.params, i32(1, 64), engine._cache_b1, i32(1)),
    }
    ok = True
    for name, lower in progs.items():
        has = marker in lower().compile().as_text()
        ok &= has
        log(f"program {name}: {marker} {'present' if has else 'MISSING'}")
    return ok


def run(cfg, seed: int, log, *, marker: str = "tpu_custom_call") -> None:
    """All phases; raises SmokeFailure (or any error) on the first fault."""
    import jax
    from repro.core import AIOSKernel

    clock = CompileClock()
    dev = jax.devices()[0]
    kres = check_kernels(cfg, seed, log)
    _check(all(ok for _, ok in kres.values()), "kernel outside tolerance")
    log(memory_line(dev, "kernel checks"))

    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as root:
        t0 = time.perf_counter()
        kernel = AIOSKernel(arch=cfg, scheduler="batched", num_cores=1,
                            root_dir=root,
                            engine_kw={"max_slots": SLOTS,
                                       "max_len": MAX_LEN,
                                       "rng_seed": seed})
        engine = kernel.pool.cores[0].engine
        jax.block_until_ready(engine.params)
        boot = time.perf_counter() - t0
        leaves = jax.tree.leaves(engine.params)
        log(f"config {cfg.name}: layers {cfg.num_layers} d_model "
            f"{cfg.d_model} heads {cfg.n_heads} kv_heads {cfg.n_kv_heads} "
            f"head_dim {cfg.head_dim} d_ff {cfg.d_ff} vocab {cfg.vocab}; "
            f"params {sum(x.size for x in leaves)} "
            f"({sum(x.nbytes for x in leaves)} bytes); kv cache "
            f"{sum(x.nbytes for x in jax.tree.leaves(engine.cache))} bytes "
            f"({SLOTS} slots x {MAX_LEN}); boot {boot!r} s")
        log(memory_line(dev, "boot"))
        c0 = clock.seconds
        with kernel:
            t0 = time.perf_counter()
            settled = serve(kernel, cfg, seed, log)
            wall = time.perf_counter() - t0
        n_tok = sum(len(sc.response["tokens"]) for sc in settled)
        log(f"served {len(settled)} syscalls, {n_tok} tokens, 0 failed, in "
            f"{wall!r} s; compile during serving {clock.seconds - c0!r} s")
        log("engine stats: " + json.dumps(
            {k: engine.stats[k] for k in (
                "model_dispatches", "packed_dispatches", "prefill_chunks",
                "prefix_hits", "prefix_saved_tokens", "decode_steps")}))
        log(memory_line(dev, "serving"))
        _check(check_against_reference(cfg, engine.params, settled, log),
               "served tokens differ from the reference")
        log(memory_line(dev, "reference"))
        _check(serving_programs_use_kernels(engine, marker, log),
               f"a serving program has no {marker}")
    stats = dev.memory_stats() or {}
    peak, limit = stats.get("peak_bytes_in_use"), stats.get("bytes_limit")
    log(f"device memory: peak_bytes_in_use {peak} of bytes_limit {limit}")
    _check(peak is None or limit is None or peak < limit,
           "peak device memory at the limit")
    log(f"compile: {clock.seconds!r} s over {clock.programs} programs")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, prompts and kernel "
                         "inputs")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repository source at {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    from repro.configs import get_config

    def log(msg):
        print(msg, flush=True)

    t0 = time.perf_counter()
    try:
        run(get_config("yi-6b"), args.seed, log)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"total wall {time.perf_counter() - t0!r} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
