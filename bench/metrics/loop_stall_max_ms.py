"""Longest time in the traced window with no serving tick running (ms):
the largest gap between the end of one ``aios.engine.serve_step`` span
and the start of the next, counting as gaps the stretches from the start
of the recorded trace to the first tick and from the last tick to the
trace's end, so that a stall the trace begins or ends in is read too."""
from bench import spans


def read(run):
    if run.trace is None:
        return None
    ticks = spans.union(spans.named(run.trace, "aios.engine.serve_step"))
    if not ticks:
        return None
    t0, t1 = spans.extent(run.trace)
    holes = spans.gaps([(t0, t0)] + ticks + [(t1, t1)])
    return max(e - s for s, e in holes) * 1e-6
