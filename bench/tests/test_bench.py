"""CPU self-tests of the benchmark's yardstick: the trace reduction, the
traffic, the roofline work, the peak table and the reference."""
import json
import os

import numpy as np
import pytest

from bench import ticks
from bench.trace import Trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


# -- trace reduction -----------------------------------------------------------
@pytest.fixture(scope="module")
def excerpt():
    return Trace.from_json(os.path.join(DATA, "trace_excerpt.json"))


def test_trace_busy_and_idle(excerpt):
    # 0.6 s of a traced agent-sessions run on a v5e: two pure-decode ticks
    # and one packed tick around a 0.45 s idle gap while the host copied a
    # prefix-cache entry off the device
    assert excerpt.busy_seconds() == pytest.approx(0.146136422, rel=1e-9)
    name, gap = excerpt.idle_gaps(1)[0]
    assert name == "Linearize"
    assert gap == pytest.approx(0.44832591, rel=1e-9)


def test_trace_programs_and_kernels(excerpt):
    assert len(excerpt.module_instances("mixed_decode")) == 2
    assert len(excerpt.module_instances("prefill_packed")) == 1
    from bench.harness import load_module
    dec = load_module("bench/kernels/decode_attention.py")
    pre = load_module("bench/kernels/prefill_attention.py")
    assert excerpt.op_seconds(dec.OPS, dec.MODULES) == pytest.approx(
        0.021531068, rel=1e-9)
    assert excerpt.op_seconds(pre.OPS, pre.MODULES) == pytest.approx(
        0.038367828, rel=1e-9)


def test_top_ops_count_self_time():
    # a loop holding two ops: the loop's own time excludes its body
    tr = Trace([(0, 100, "while.1"), (10, 30, "fusion.2"),
                (40, 90, "chunk_attention.3")],
               [(0, 100, "jit_mixed_decode(1)")], [])
    top = dict(tr.top_ops())
    assert top["jit_mixed_decode:while"] == pytest.approx(30e-9)
    assert top["jit_mixed_decode:chunk_attention"] == pytest.approx(50e-9)
    assert tr.busy_seconds() == pytest.approx(100e-9)


# -- traffic -------------------------------------------------------------------
def _mix(name):
    with open(os.path.join(os.path.dirname(DATA), "..", "traffic",
                           name + ".json")) as f:
        return json.load(f)


def _flat(sessions):
    return [(s.due_s, s.prefix.tolist(),
             [(t.new.tolist(), t.max_new, t.think_s, t.reset)
              for t in s.turns]) for s in sessions]


@pytest.mark.parametrize("mix", ["agent-sessions", "long-decode"])
def test_traffic_same_seed_same_inputs(mix):
    from bench.harness import load_module
    p = _mix(mix)
    gen = load_module(f"bench/traffic/{p['kind']}.py")
    a = gen.sessions(p, 2 ** 31 + 11, 60.0, 64000)
    b = gen.sessions(p, 2 ** 31 + 11, 60.0, 64000)
    c = gen.sessions(p, 2 ** 31 + 12, 60.0, 64000)
    assert _flat(a) == _flat(b)
    assert _flat(a) != _flat(c)
    # another seed serves the same population of sizes, in another order
    sizes = lambda ss: sorted((len(t.new), t.max_new)             # noqa
                              for s in ss for t in s.turns)
    assert sizes(a) == sizes(c)
    # ... each session or client keeping its own sequence of them
    rows = lambda ss: sorted(tuple((len(t.new), t.max_new)        # noqa
                                   for t in s.turns) for s in ss)
    assert rows(a) == rows(c)
    # ... at the same start times, every one inside the horizon
    starts = lambda ss: sorted(s.due_s for s in ss)               # noqa
    assert starts(a) == starts(c)
    assert all(0.0 <= t < 60.0 for t in starts(a))


# -- end-to-end and window readings ---------------------------------------------
def test_window_readings_count_only_the_window():
    from bench import harness
    from bench.harness import load_module

    class Req:
        def __init__(self, ts):
            self.stamps = [(t, 0) for t in ts]
            self.due = ts[0]

    # gaps of 0.1 s, and one of 2 s that crosses into the window
    reqs = [Req([0.5, 0.6, 0.7, 2.7, 2.8, 2.9, 3.0]), Req([5.5, 5.6])]
    w = harness.Window(1.0, 5.0, 0, 0)
    e2e = harness.end_to_end(reqs, w, 7.0)
    assert e2e["setup_s"] == 7.0
    assert e2e["itl_p50_ms"] == pytest.approx(100.0)
    assert e2e["itl_p95_ms"] == pytest.approx(100.0 + 0.85 * 1900.0)
    assert e2e["output_tokens_per_s"] == pytest.approx(4 / 4.0)

    class Run:
        requests, window = reqs, w

    rate = load_module("bench/metrics/window_tokens_per_s.py").read(Run)
    assert rate == pytest.approx(1.0)
    tail = load_module("bench/metrics/itl_tail_p95_ms.py").read(Run)
    assert tail == pytest.approx(e2e["itl_p95_ms"])
    Run.window = harness.Window(10.0, 12.0, 0, 0)
    assert load_module("bench/metrics/window_tokens_per_s.py").read(Run) \
        is None


# -- roofline work -------------------------------------------------------------
def test_work_ignores_the_kv_bucket():
    """One tick of two decode rows and a prompt chunk, recorded once at kv
    bucket 256 and once at 4096: the engine's record differs, the work the
    live tokens need does not."""
    from bench.harness import load_module
    dims = (32, 4096, 32, 4, 128, 11008, 64000, 5e6, 1e-5)

    class Req:
        def __init__(self, P, admits, stamps, prefilled):
            self.prompt_np = np.zeros(P, np.int32)
            self.admits = admits
            self.stamps = stamps
            self.response = {"usage": {"prompt_tokens": prefilled}}

    reqs = [Req(100, [(0.0, 0)], [(1.0, 1), (1.1, 2)], 100),
            Req(120, [(0.0, 0)], [(1.0, 1), (1.1, 2)], 120),
            Req(150, [(0.0, 2)], [(2.0, 3)], 150)]
    out = []
    for kv in (256, 4096):
        rec = {"first": np.array(0),
               "kind": np.array([2, 0, 1, 0]),
               "rows": np.array([2, 2, 3, 1]),
               "chunk": np.array([128, 1, 256, 1]),
               "kv": np.array([kv] * 4),
               "tokens": np.array([220, 2, 152, 1])}

        class Run:
            ticks, requests, prefill_chunks = rec, reqs, (32, 64, 128, 256)

        rows = ticks.reconstruct(Run)
        assert rows is not None
        dec = load_module("bench/kernels/decode_attention.py")
        pre = load_module("bench/kernels/prefill_attention.py")
        out.append((dec.work(rows[1], dims), pre.work(rows[2], dims)))
    assert out[0] == out[1]
    # the packed tick: a 150-token prompt from 0 and decode rows at 101, 121
    f, _ = out[0][1]
    want = 4.0 * 32 * 128 * (150 * 151 / 2 + 101 + 1 + 121 + 1)
    assert f == pytest.approx(want)


def test_reconstruction_refuses_a_wrong_record():
    class Req:
        prompt_np = np.zeros(10, np.int32)
        admits = [(0.0, 0)]
        stamps = [(1.0, 1)]
        response = {"usage": {"prompt_tokens": 10}}

    class Run:
        ticks = {"first": np.array(0), "kind": np.array([1, 0]),
                 "rows": np.array([1, 1]), "chunk": np.array([32, 1]),
                 "tokens": np.array([9, 1])}
        requests = [Req()]
        prefill_chunks = (32, 64)

    assert ticks.reconstruct(Run) is None


# -- peaks ---------------------------------------------------------------------
def test_peaks_known_and_unknown():
    from bench.peaks import peaks_for
    p = peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks_for("TPU v99")
