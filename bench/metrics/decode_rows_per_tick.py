"""Decode rows per engine tick over the window: tokens fed by decoding
requests, over the ticks the engine ran."""


def read(run):
    w = run.window
    n = w.tick1 - w.tick0
    if n <= 0:
        return None
    fed = sum(1 for r in run.requests for _, k in r.stamps
              if w.tick0 <= k < w.tick1)
    return fed / n
