"""Share of the prompt tokens admitted in the window that the prefix cache
served (%): the engine's prefix_saved_tokens over the window, over the
prompt tokens of the requests admitted in it."""
from bench.ticks import _admitted_at


def read(run):
    w = run.window
    admitted = sum(len(r.prompt_np) for r in run.requests
                   if (a := _admitted_at(r)) is not None
                   and w.tick0 <= a < w.tick1)
    if admitted == 0:
        return None
    saved = run.stats1["prefix_saved_tokens"] - run.stats0["prefix_saved_tokens"]
    return 100.0 * saved / admitted
