"""Whole-step model FLOP utilization (%): the model operations every tick of
the traced window needs, over the window times the chip's bf16 peak.

Per token fed: 2 operations per weight of the layers' products; per row and
layer, attention at live lengths (bench/ticks.attention_work); and the
output head for each row whose logits are used (decode rows and prompts
that finish). Padding, buckets and recomputation do not count."""
from bench import ticks


def read(run):
    tr, tw, peaks = run.trace, run.trace_window, run.peaks
    if tw is None or not peaks:
        return None
    rows = run.tick_rows()
    if rows is None:
        return None
    L, d, H, K, hd, dff, V = ticks.dims_np(run.dims)
    per_token = 2.0 * L * (2 * d * H * hd + 2 * d * K * hd + 3 * d * dff)
    total = 0.0
    for t in ticks.ticks_between(rows, tw.tick0, tw.tick1):
        n = sum(c for _, c in t.prefill) + len(t.decode)
        f, _ = ticks.attention_work(
            list(t.prefill) + [(o, 1) for o in t.decode], run.dims)
        total += n * per_token + L * f + 2.0 * d * V * (len(t.decode)
                                                          + t.finish)
    if total <= 0:
        return None
    return 100.0 * total / ((tw.end - tw.start)
                            * float(peaks["bf16_flops_per_s"]))
