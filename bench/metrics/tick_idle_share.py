"""Share of the recorded trace in which the device is idle while a serving
tick runs on the host (%): device-idle time inside the
``aios.engine.serve_step`` spans over the stretch the profiler recorded
(``spans.extent``, as ``loop_stall_max_ms`` reads it). This is the idle
the tick's own host work causes (batch build, token readback,
bookkeeping); idle between ticks is the loop's, read by
``loop_stall_max_ms``."""
from bench import spans


def read(run):
    if run.trace is None:
        return None
    ticks = spans.union(spans.named(run.trace, "aios.engine.serve_step"))
    if not ticks:
        return None
    t0, t1 = spans.extent(run.trace)
    if t1 <= t0:
        return None
    inside = sum(e - s for s, e in ticks)
    busy = spans.overlap_ns(ticks, run.trace.busy_intervals())
    return 100.0 * (inside - busy) / (t1 - t0)
