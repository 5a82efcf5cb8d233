"""The program's own host spans in a trace, and the device's idle time
measured against them.

The serving path marks its layer boundaries with ``aios.*`` spans
(``repro.obs.span``: a ``jax.profiler.TraceAnnotation``), which land on
the host plane of the trace beside the device's ops, on the same clock.
A trace that holds none (a program without the spans) gives empty lists
here, and the readers built on them read nothing.

Intervals are ``(start_ns, end_ns)`` on the trace's clock, sorted.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

PREFIX = "aios."
UNNAMED = "(no aios span)"

Span = Tuple[float, float]


def named(trace, name: str) -> List[Span]:
    """The intervals of the host spans called ``name``."""
    return [(s, e) for s, e, n in trace.host if n == name]


def union(intervals: Sequence[Span]) -> List[Span]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap_ns(a: Sequence[Span], b: Sequence[Span]) -> float:
    """Total length of the intersection of two merged interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def extent(trace) -> Span:
    """The stretch the profiler recorded: from the first host event's start
    to the last one's end. A span still open when the session started or
    stopped is not in the trace, so the edges are read against this."""
    return (min(s for s, _, _ in trace.host),
            max(e for _, e, _ in trace.host))


def gaps(intervals: Sequence[Span]) -> List[Span]:
    """The holes between consecutive intervals of a merged list."""
    return [(e0, s1) for (_, e0), (s1, _) in zip(intervals, intervals[1:])]


def device_idle(trace, w0: float, w1: float) -> List[Span]:
    """Where in [w0, w1] no operation ran on the device."""
    out, t = [], w0
    for s, e in trace.busy_intervals():
        if e <= t:
            continue
        if s >= w1:
            break
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < w1:
        out.append((t, w1))
    return out


def idle_by_span(trace, w0: float, w1: float) -> Dict[str, float]:
    """Device idle seconds in [w0, w1], each instant given to the innermost
    ``aios.*`` span open on the host then (the one that began last), and
    to ``UNNAMED`` where none was open."""
    points = []
    for k, (s, e, n) in enumerate(trace.host):
        if n.startswith(PREFIX) and e > w0 and s < w1:
            points += [(max(s, w0), 1, k), (min(e, w1), 0, k)]
    for s, e in device_idle(trace, w0, w1):
        points += [(s, 3, -1), (e, 2, -1)]
    points.sort()
    out: Dict[str, float] = {}
    open_spans: Dict[int, float] = {}
    idle, t = False, w0
    for p, kind, k in points:
        if idle and p > t:
            name = (trace.host[max(open_spans, key=open_spans.get)][2]
                    if open_spans else UNNAMED)
            out[name] = out.get(name, 0.0) + (p - t) * 1e-9
        t = p
        if kind == 1:
            open_spans[k] = trace.host[k][0]
        elif kind == 0:
            open_spans.pop(k, None)
        else:
            idle = kind == 3
    return out
