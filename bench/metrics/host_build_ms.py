"""Host time the engine spends building a tick's batch before its dispatch
(ms), the mean over the window's ticks (the engine's TickProfiler)."""
import numpy as np


def read(run):
    t, w = run.ticks, run.window
    first = int(t["first"])
    a, b = max(w.tick0, first) - first, w.tick1 - first
    if b <= a:
        return None
    return float(np.mean(t["build"][a:b])) * 1e3
