"""Tokens streamed to the clients in the measured window, over the window
(tokens/s). A cell whose serving loop stalls for seconds at a time reports
the rate here, beside its end-to-end metrics, where the stalls spread it
too widely between runs to hold it to a bound."""


def read(run):
    w = run.window
    n = sum(1 for r in run.requests for t, _ in r.stamps
            if w.start <= t < w.end)
    return n / (w.end - w.start) if n else None
