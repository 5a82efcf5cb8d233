"""Time a request waits in the scheduler before a core admits it (ms), the
90th percentile over the requests due in the window: the syscall's start
stamp less its queue stamp; one not admitted by the window's end counts to
the end."""
from bench.harness import in_window, percentile


def read(run):
    w = run.window
    waits = []
    for r in in_window(run.requests, w):
        if r.queued_time is None:
            continue
        start = r.start_time if r.start_time is not None else w.end
        waits.append((min(start, w.end) - r.queued_time) * 1e3)
    return percentile(waits, 90)
