"""The readers of the program's ``aios.*`` host spans, on a hand-made trace
(``data/spans_excerpt.json``) and on a trace from a program without the
spans (``data/trace_excerpt.json``)."""
import os

import pytest

from bench import harness, spans
from bench.trace import Trace

DATA = os.path.join(os.path.dirname(__file__), "data")


class Run:
    def __init__(self, trace):
        self.trace = trace
        self.trace_window = harness.Window(5.0, 5.1, 0, 0)


def _read(metric, trace):
    return harness.load_module(f"bench/metrics/{metric}.py").read(Run(trace))


@pytest.fixture(scope="module")
def excerpt():
    return Trace.from_json(os.path.join(DATA, "spans_excerpt.json"))


@pytest.mark.parametrize("metric,want", [
    ("loop_stall_max_ms", 32.0),       # 47 ms -> 79 ms, not 22 -> 23
    ("prefix_store_ms", 28.0),
    ("tick_idle_share", 800 / 99),     # 2 + 2 + 2 + 1 + 1 ms of the 99
                                       # recorded, not of the 100 ms window
])
def test_span_readers(excerpt, metric, want):
    assert _read(metric, excerpt) == pytest.approx(want)


def _without(trace, drop):
    return Trace(trace.ops, trace.modules,
                 [h for h in trace.host if not drop(h)])


def test_no_store_in_the_window_reads_none(excerpt):
    bare = _without(excerpt, lambda h: h[2] == "aios.prefix.store")
    assert _read("prefix_store_ms", bare) is None
    assert _read("loop_stall_max_ms", bare) == pytest.approx(32.0)


@pytest.mark.parametrize("ticks_end_by,want", [
    (0, 32.0),          # every tick kept: the stall between two ticks
    (47e6, 79.0),       # the first two gone: the trace begins in the stall
])
def test_a_stall_at_the_trace_edge_is_read(excerpt, ticks_end_by, want):
    late = _without(excerpt, lambda h: h[2].startswith("aios.engine.")
                    and h[1] <= ticks_end_by)
    assert _read("loop_stall_max_ms", late) == pytest.approx(want)


@pytest.mark.parametrize("metric", ["loop_stall_max_ms", "prefix_store_ms",
                                    "tick_idle_share"])
def test_a_program_without_spans_reads_none(metric):
    tr = Trace.from_json(os.path.join(DATA, "trace_excerpt.json"))
    assert _read(metric, tr) is None
    assert _read(metric, None) is None


def test_idle_by_innermost_span(excerpt):
    split = spans.idle_by_span(excerpt, 0.0, 100e6)
    want = {"aios.engine.readback": 0.5, "aios.engine.build": 0.5,
            "aios.engine.serve_step": 7.0, "aios.core.finish": 2.0,
            "aios.prefix.store": 8.0, "aios.pagestore.put": 20.0,
            spans.UNNAMED: 7.0}
    assert set(split) == set(want)
    for name, ms in want.items():
        assert split[name] * 1e3 == pytest.approx(ms), name
    idle = sum(e - s for s, e in spans.device_idle(excerpt, 0.0, 100e6))
    assert sum(split.values()) == pytest.approx(idle * 1e-9)


@pytest.mark.parametrize("a,b,want", [
    ([(0, 10)], [(5, 20)], 5),
    ([(0, 10), (20, 30)], [(5, 25)], 10),
    ([(0, 10)], [(10, 20)], 0),
    ([], [(0, 1)], 0),
])
def test_overlap(a, b, want):
    assert spans.overlap_ns(a, b) == want
