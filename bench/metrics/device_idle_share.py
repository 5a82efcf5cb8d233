"""Share of the traced window with no operation on the device (%): one less
the union of the device's op intervals over the window."""


def read(run):
    if run.trace is None or run.trace_window is None:
        return None
    w = run.trace_window.end - run.trace_window.start
    busy = run.trace.busy_seconds()
    if busy <= 0 or w <= 0:
        return None
    return 100.0 * (1.0 - busy / w)
