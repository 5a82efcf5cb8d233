"""One run of one cell: boot the kernel, warm up, drive the traffic through
the measured window, check the served tokens against the reference, and
reduce what was recorded to the cell's metrics.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by the names in ``BENCHMARK.json``:

    bench/configs/<config>.json     sizes, source, the program's base arch
    bench/traffic/<mix>.json        a generator kind and its parameters
    bench/traffic/<kind>.py         the generator
    bench/warmup/<kind>.py          the warm-up the mix names
    bench/metrics/<metric>.py       one reader per per-layer metric
    bench/kernels/<kernel>.py       operations and bytes of one kernel
    bench/reference/<family>.py     the plain reference
    bench/limits/<cell>.json        the correctness limit of one cell
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import tempfile
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class BenchError(RuntimeError):
    pass


def load_json(rel: str) -> Any:
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def load_module(rel: str):
    path = os.path.join(ROOT, rel)
    if not os.path.exists(path):
        raise BenchError(f"no file {rel}")
    name = "bench_" + rel.replace("/", "_").replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class CellSpec:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def cell_spec(name: str, bench: Optional[Dict[str, Any]] = None) -> CellSpec:
    bench = bench or load_json("BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = []
    for m in bench["per_layer"]:
        if name in m["workloads"] if "workloads" in m \
                else m["moves"] in reported:
            per_layer.append(m)
    return CellSpec(name=name, chips=int(w["chips"]),
                    config=load_json(cfg["file"]),
                    traffic=load_json(f"bench/traffic/{w['traffic']}.json"),
                    limits=load_json(f"bench/limits/{name}.json"),
                    end_to_end=e2e, per_layer=per_layer)


def program_config(config: Dict[str, Any]):
    """The program's ModelConfig for a configuration file, checked against
    the file's published sizes."""
    from repro.configs import get_config
    prog = config["program"]
    cfg = get_config(prog["base_arch"]).replace(
        name=config["name"], **prog.get("overrides", {}))
    want = {"d_model": "hidden_size", "n_heads": "num_attention_heads",
            "n_kv_heads": "num_key_value_heads", "d_ff": "intermediate_size",
            "vocab": "vocab_size", "num_layers": "num_hidden_layers",
            "rope_theta": "rope_theta", "norm_eps": "rms_norm_eps"}
    for attr, key in want.items():
        if float(getattr(cfg, attr)) != float(config[key]):
            raise BenchError(f"{config['name']}: the program runs {attr}="
                             f"{getattr(cfg, attr)}, the file states "
                             f"{key}={config[key]}")
    if cfg.head_dim != config["hidden_size"] // config["num_attention_heads"]:
        raise BenchError(f"{config['name']}: head_dim {cfg.head_dim}")
    return cfg


class CompileClock:
    """Counts JAX's compile events (lowering, backend compile, persistent
    cache reads) reported through ``jax.monitoring``."""

    def __init__(self):
        import jax
        self.events = 0
        self.seconds = 0.0
        self.on = True
        self.names: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def watch(self, on: bool):
        """While on, keep the names of what compiles (JAX's compile log)."""
        import logging

        import jax
        jax.config.update("jax_log_compiles", on)
        lg = logging.getLogger("jax")
        if on:
            clock = self

            class _Keep(logging.Handler):
                def emit(self, rec):
                    msg = rec.getMessage()
                    if msg.startswith("Compiling"):
                        clock.names.append(msg[:160])

            self._handler = _Keep()
            lg.addHandler(self._handler)
        elif getattr(self, "_handler", None) is not None:
            lg.removeHandler(self._handler)
            self._handler = None

    def _duration(self, name, secs, **_):
        if not self.on:
            return
        if name.startswith("/jax/core/compile/backend_compile") or \
                name.startswith("/jax/compilation_cache/cache_retrieval"):
            self.events += 1
        if name.startswith("/jax/core/compile/"):
            self.seconds += secs


@dataclasses.dataclass
class Window:
    start: float                  # host monotonic clock
    end: float
    tick0: int                    # engine tick count at start and end
    tick1: int


@dataclasses.dataclass
class RunData:
    """What the readers of per-layer metrics get."""
    spec: CellSpec
    dims: tuple                   # reference.dims of the configuration
    requests: List[Any]
    window: Window
    ticks: Dict[str, np.ndarray]  # per-tick profiler fields, by tick index
    prefill_chunks: tuple
    stats0: Dict[str, int]        # engine counters at the window's start
    stats1: Dict[str, int]        # ... and end
    peaks: Optional[Dict[str, Any]]
    trace: Any = None             # bench.trace.Trace of the traced window
    trace_window: Optional[Window] = None
    _rows: Any = None

    def tick_rows(self):
        """Per tick, the rows it ran, reconstructed (bench.ticks)."""
        if self._rows is None:
            from bench import ticks
            self._rows = ticks.reconstruct(self)
        return self._rows


def percentile(values, q: float) -> Optional[float]:
    v = np.asarray(values, float)
    return float(np.percentile(v, q)) if len(v) else None


# ---------------------------------------------------------------------------
# end-to-end metrics, from the stamps
# ---------------------------------------------------------------------------
def in_window(reqs, w: Window):
    return [r for r in reqs if w.start <= r.due < w.end]


def ttft_ms(reqs, w: Window) -> List[float]:
    out = []
    for r in in_window(reqs, w):
        first = r.stamps[0][0] if r.stamps else None
        if first is None or first >= w.end:
            first = w.end
        out.append((first - r.due) * 1e3)
    return out


def itl_ms(reqs, w: Window) -> List[float]:
    out = []
    for r in reqs:
        ts = [t for t, _ in r.stamps]
        out += [(b - a) * 1e3 for a, b in zip(ts, ts[1:])
                if w.start <= b < w.end]
    return out


def tokens_in(reqs, w: Window) -> int:
    return sum(1 for r in reqs for t, _ in r.stamps if w.start <= t < w.end)


def end_to_end(reqs, w: Window, setup_s: float) -> Dict[str, float]:
    out = {"setup_s": setup_s}
    v = percentile(ttft_ms(reqs, w), 90)
    if v is not None:
        out["ttft_p90_ms"] = v
    itl = itl_ms(reqs, w)
    for q in (50, 95):
        v = percentile(itl, q)
        if v is not None:
            out[f"itl_p{q}_ms"] = v
    n = tokens_in(reqs, w)
    if n:
        out["output_tokens_per_s"] = n / (w.end - w.start)
    return out


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------
def check_units(reqs) -> List[Dict[str, Any]]:
    """What one forward pass of the reference can check: a request's
    prompt and served tokens, with every earlier finished turn of the same
    conversation whose prompt and reply it extends (its served tokens lie
    inside the later prompt, so the same pass reads them)."""
    done = [r for r in reqs if r.status == "done" and r.response
            and r.response.get("tokens")]
    by_sid: Dict[int, List[Any]] = {}
    for r in sorted(done, key=lambda r: (r.sid, r.turn)):
        by_sid.setdefault(r.sid, []).append(r)
    units = []
    for chain in by_sid.values():
        group: List[Any] = []
        for r in chain:
            if group:
                last = group[-1]
                head = np.concatenate([last.prompt_np, np.asarray(
                    last.response["tokens"], np.int32)])
                if not (len(r.prompt_np) >= len(head) and np.array_equal(
                        r.prompt_np[:len(head)], head)):
                    units.append(group)
                    group = []
            group.append(r)
        if group:
            units.append(group)
    out = []
    for group in units:
        last = group[-1]
        served = np.asarray(last.response["tokens"], np.int32)
        seq = np.concatenate([last.prompt_np, served[:-1]])
        pos, tok = [], []
        for r in group:
            t = np.asarray(r.response["tokens"], np.int32)
            P = len(r.prompt_np)
            pos.append(np.arange(P - 1, P - 1 + len(t)))
            tok.append(t)
        out.append({"seq": seq, "pos": np.concatenate(pos),
                    "served": np.concatenate(tok), "requests": len(group)})
    return out


def pick_sample(reqs, seed: int, tokens: int, most: int) -> List[Any]:
    """The unit with the longest context, then others drawn from the seed
    until ``tokens`` served tokens or ``most`` units."""
    units = check_units(reqs)
    if not units:
        return []
    units.sort(key=lambda u: (-len(u["seq"]), -len(u["served"])))
    longest, rest = units[0], units[1:]
    rng = np.random.default_rng((int(seed) * 7919 + 17) % (2 ** 63))
    rest = [rest[i] for i in rng.permutation(len(rest))]
    out = [longest]
    n = len(longest["served"])
    for u in rest:
        if n >= tokens or len(out) >= most:
            break
        out.append(u)
        n += len(u["served"])
    return out


def served_gaps(ref_mod, params, config, sample, *,
                quant: bool = False) -> Dict[str, Any]:
    """Widest gap between the reference's best logit and its logit for the
    served token over the sample; with ``quant``, the same for the token the
    int8 control puts first (the control's reading)."""
    widest, n, flips, reqs = 0.0, 0, 0, 0
    ctrl = 0.0
    for u in sample:
        ref = ref_mod.logits_at(params, config, u["seq"], u["pos"])
        g = ref_mod.gaps_of(ref, u["served"])
        widest = max(widest, float(g.max()))
        flips += int((g > 0).sum())
        n += len(u["served"])
        reqs += u["requests"]
        if quant:
            lq = ref_mod.logits_at(params, config, u["seq"], u["pos"],
                                   quant=True)
            gq = ref_mod.gaps_of(ref, lq.argmax(-1))
            ctrl = max(ctrl, float(gq.max()))
    out = {"widest_gap": widest, "tokens": n, "requests": reqs,
           "passes": len(sample), "not_argmax": flips}
    if quant:
        out["control_gap"] = ctrl
    return out


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Served:
    """What one pass of the traffic left: the requests with their stamps,
    the windows, the engine's record, and the set-up time."""
    requests: List[Any]
    window: Window
    trace_window: Optional[Window]
    trace_dir: Optional[str]
    setup_s: float
    compiles_in_window: int
    memory_peak_bytes: Optional[int]
    failed: int
    ticks: Dict[str, np.ndarray]
    prefill_chunks: tuple
    stats0: Dict[str, int]
    stats1: Dict[str, int]
    shapes: Any                   # the served parameters' shapes and dtypes


def serve(spec: CellSpec, seed: int, seconds: float, trace: bool,
          t_start: float, log: Callable[[str], None]) -> Served:
    """Boots the kernel on weights made from ``seed``, warms up, drives the
    traffic for its lead-in and ``seconds`` measured seconds (tracing part
    of them with ``trace``), settles every request and frees the program's
    state."""
    import jax
    from repro.core import AIOSKernel
    from repro.core.syscall import LLMSyscall
    from repro.models import build_model
    from repro.obs import TickProfiler

    from bench import load

    dev = jax.devices()[0]
    config, traffic = spec.config, spec.traffic
    serving = config["serving"]
    cfg = program_config(config)
    ref_mod = load_module(f"bench/reference/{config['family']}.py")
    gen = load_module(f"bench/traffic/{traffic['kind']}.py")
    warmup = load_module(f"bench/warmup/{traffic['warmup']}.py")
    clock = CompileClock()

    model = build_model(cfg)
    shapes = jax.eval_shape(lambda k: model.init_params(k)[0],
                            jax.random.key(0))
    params = ref_mod.make_params(shapes, seed)
    jax.block_until_ready(params)
    log(f"weights made: {sum(x.nbytes for x in jax.tree.leaves(params))} "
        f"bytes, {time.perf_counter() - t_start:.3f} s after start; "
        f"{_memory(dev)}")

    store = tempfile.mkdtemp(prefix="bench-store-")
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        kernel = AIOSKernel(
            arch=cfg, scheduler="batched", num_cores=1, root_dir=store,
            shared_params=params,
            engine_kw={"max_slots": int(serving["max_slots"]),
                       "max_len": int(serving["max_len"]),
                       "rng_seed": int(seed) % (2 ** 31)})
        del params
        engine = kernel.pool.cores[0].engine
        log(f"kernel booted: {_memory(dev)}")
        info = warmup.warm(engine, min_context=gen.min_context(traffic),
                           vocab=cfg.vocab)
        jax.block_until_ready(engine.cache)
        log(f"warm-up: {info}, compile events {clock.events}, "
            f"{clock.seconds:.3f} s compiling, "
            f"{time.perf_counter() - t_start:.3f} s after start; "
            f"{_memory(dev)}")
        prof = engine.profiler = TickProfiler(cap=1 << 17)
        Request = load.request_class(LLMSyscall, lambda: prof.n)
        lead = float(traffic["lead_s"])
        plan = gen.sessions(traffic, seed, lead + seconds, cfg.vocab)

        kernel.start()
        t0 = time.monotonic()
        setup_s = time.perf_counter() - t_start
        ws, we = t0 + lead, t0 + lead + seconds
        client = load.Client(kernel, Request, plan, t0, we)
        client.start()
        _sleep_until(ws)
        clock.watch(True)
        c0, n0, stats0 = clock.events, prof.n, dict(engine.stats)
        tw = None
        if trace:
            span = min(float(traffic.get("trace_s", 8.0)), seconds)
            _sleep_until(ws + (seconds - span) / 2)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            ta, na = time.monotonic(), prof.n
            _sleep_until(ta + span)
            nb = prof.n
            jax.profiler.stop_trace()
            tw = Window(ta, time.monotonic(), na, nb)
        _sleep_until(we)
        c1, n1, stats1 = clock.events, prof.n, dict(engine.stats)
        clock.watch(False)
        window = Window(ws, we, n0, n1)
        for name in clock.names[:20]:
            log(f"compiled in the window: {name}")
        client.stop()
        clock.on = False
        _settle(kernel, client, log)
        peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
        ticks = _tick_fields(prof)
        chunks = tuple(engine.prefill_chunks)
        reqs = client.requests
        failed = [r for r in in_window(reqs, window)
                  if r.status == "error" and not r.ended_by_window]
        if client.errors:
            log(f"errors: {client.errors[:5]}")
        lateness = [(r.sent - r.due) * 1e3 for r in in_window(reqs, window)
                    if r.sent is not None]
        client.kernel = None
        del kernel, engine, client
        gc.collect()
        live = sum(x.nbytes for x in jax.live_arrays())
        log(f"program state freed: {live} bytes of device arrays remain")
    finally:
        shutil.rmtree(store, ignore_errors=True)
    log(f"window: {len(in_window(reqs, window))} requests due, "
        f"{tokens_in(reqs, window)} tokens streamed, ticks {n1 - n0}, "
        f"compile events in window {c1 - c0}, generator lateness p50 "
        f"{percentile(lateness, 50)} ms max "
        f"{max(lateness) if lateness else None} ms")
    return Served(requests=reqs, window=window, trace_window=tw,
                  trace_dir=trace_dir, setup_s=setup_s,
                  compiles_in_window=c1 - c0, memory_peak_bytes=peak,
                  failed=len(failed), ticks=ticks, prefill_chunks=chunks,
                  stats0=stats0, stats1=stats1, shapes=shapes)


def reference_gaps(spec: CellSpec, served: Served, seed: int,
                   log: Callable[[str], None], *,
                   quant: bool = False) -> Dict[str, Any]:
    """The reference, its weights made again from ``seed``, over a sample
    (drawn from the seed) of what the window finished; see served_gaps."""
    t = time.perf_counter()
    lim = spec.limits
    ref_mod = load_module(f"bench/reference/{spec.config['family']}.py")
    sample = pick_sample(served.requests, seed, int(lim["sample_tokens"]),
                         int(lim["sample_passes"]))
    params = ref_mod.make_params(served.shapes, seed)
    gaps = served_gaps(ref_mod, params, spec.config, sample, quant=quant)
    del params
    gc.collect()
    log(f"reference: {gaps['passes']} passes over {gaps['requests']} "
        f"requests, {gaps['tokens']} served tokens, {gaps['not_argmax']} "
        f"not the reference argmax, in {time.perf_counter() - t:.3f} s")
    return gaps


def judge(gap: float, tokens: int, limits: Dict[str, Any]):
    """The comparison that decides ``correct``: the widest logit gap of the
    compared tokens within its limit, and enough tokens compared. Returns
    (correct, checks), each check a number beside its limit."""
    checks = {
        "logit_gap": {"value": gap, "limit": float(limits["logit_gap"])},
        "tokens_compared": {"value": tokens,
                            "limit": int(limits["min_tokens"])},
    }
    ok = (gap <= float(limits["logit_gap"])
          and tokens >= int(limits["min_tokens"]))
    return bool(ok), checks


def run_cell(spec: CellSpec, seed: int, seconds: float, trace: bool,
             t_start: float, log: Callable[[str], None], *,
             peaks: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    """Runs the cell once and returns the result fields (see run_cell.py)."""
    served = serve(spec, seed, seconds, trace, t_start, log)
    reqs, window = served.requests, served.window
    e2e = end_to_end(reqs, window, served.setup_s)
    gaps = reference_gaps(spec, served, seed, log)
    correct, checks = judge(gaps["widest_gap"], gaps["tokens"], spec.limits)
    result: Dict[str, Any] = {
        "correct": correct, "attempted": len(in_window(reqs, window)),
        "failed": served.failed,
        "compile_events_in_window": served.compiles_in_window,
        "memory_peak_bytes": served.memory_peak_bytes, "gaps": gaps,
        "checks": checks,
    }
    units = {m["name"]: m["unit"] for m in spec.end_to_end + spec.per_layer}
    if not trace:
        result["metrics"] = {k: {"value": v, "unit": units[k]}
                             for k, v in e2e.items() if k in units}
        return result

    from bench.trace import Trace
    tr = Trace.from_dir(served.trace_dir)
    shutil.rmtree(served.trace_dir, ignore_errors=True)
    ref_mod = load_module(f"bench/reference/{spec.config['family']}.py")
    data = RunData(spec=spec, dims=ref_mod.dims(spec.config), requests=reqs,
                   window=window, ticks=served.ticks,
                   prefill_chunks=served.prefill_chunks,
                   stats0=served.stats0, stats1=served.stats1, peaks=peaks,
                   trace=tr, trace_window=served.trace_window)
    metrics = {}
    for m in spec.per_layer:
        reader = load_module(f"bench/metrics/{m['name']}.py")
        v = reader.read(data)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        log(f"per-layer {m['name']}: {v}")
    result["metrics"] = metrics
    if tr is not None:
        tw = served.trace_window
        result["busy_s"] = tr.busy_seconds()
        result["window_s"] = tw.end - tw.start
        result["breakdown"] = {"device_ops": tr.top_ops(10),
                               "idle_gaps": tr.idle_gaps(10)}
    return result


def _memory(dev) -> str:
    st = dev.memory_stats() or {}
    return (f"bytes_in_use {st.get('bytes_in_use')} peak_bytes_in_use "
            f"{st.get('peak_bytes_in_use')} bytes_limit "
            f"{st.get('bytes_limit')}")


def _sleep_until(t: float):
    while True:
        d = t - time.monotonic()
        if d <= 0:
            return
        time.sleep(min(d, 0.05))


def _tick_fields(prof) -> Dict[str, np.ndarray]:
    """The profiler's per-tick fields, by tick index (ticks the ring has
    overwritten are absent: ``first`` says where the record starts)."""
    cap = prof.cap
    first = max(0, prof.n - cap)
    idx = np.arange(first, prof.n) % cap
    out = {"first": np.array(first)}
    for f in ("kind", "rows", "kb", "chunk", "kv", "tokens", "padded",
              "build", "wall"):
        out[f] = np.asarray(getattr(prof, "_" + f))[idx].copy()
    return out


def _settle(kernel, client, log):
    """Close the window: cancel what is still in flight, wait until every
    request settles, stop the kernel and wait for its threads."""
    for r in client.requests:
        if not r.event.is_set():
            r.ended_by_window = True
            r.cancel()
    for r in client.requests:
        if not r.event.wait(timeout=120):
            raise BenchError(f"request {r.pid} did not settle")
    threads = [t for t in threading.enumerate()
               if t.name.startswith("aios-")]
    kernel.stop()
    for t in threads:
        t.join(timeout=120)
        if t.is_alive():
            raise BenchError(f"kernel thread {t.name} did not stop")
    if client.alive():
        raise BenchError("load client did not stop")
