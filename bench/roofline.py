"""A kernel's share of its roofline over the traced window.

The least time the chip could take for a tick's calls is, per layer, the
larger of the operations over the peak rate and the bytes over the peak
bandwidth, with operations and bytes counted at live lengths by the
kernel's file under ``bench/kernels``. The share is the sum of those least
times over the kernel's device time in the trace. Ticks are matched to the
window by the engine's tick count; the least time is scaled by the number
of program instances the trace holds over the number of ticks the host
dispatched in it, so a tick cut at either edge is not counted twice.
"""
from __future__ import annotations

import sys
from typing import Optional

from bench import ticks


def share(run, kernel, name: str) -> Optional[float]:
    tr, tw, peaks = run.trace, run.trace_window, run.peaks
    if tr is None or tw is None or not peaks:
        return None
    rows = run.tick_rows()
    if rows is None:
        return None
    host = ticks.ticks_between(rows, tw.tick0, tw.tick1, kernel.TICK_KINDS)
    inst = tr.module_instances(kernel.MODULES)
    secs = tr.op_seconds(kernel.OPS, kernel.MODULES)
    if not host or not inst or secs <= 0:
        return None
    layers = run.dims[0]
    fl, bw = float(peaks["bf16_flops_per_s"]), float(peaks["hbm_bytes_per_s"])
    t_min = mem = 0.0
    for t in host:
        f, b = kernel.work(t, run.dims)
        t_min += layers * max(f / fl, b / bw)
        mem += layers * b / bw if b / bw >= f / fl else 0.0
    t_min *= len(inst) / len(host)
    bound = "memory" if mem >= 0.5 * t_min * len(host) / len(inst) \
        else "compute"
    print(f"{name}: {len(host)} ticks, {len(inst)} program instances, "
          f"kernel {secs!r} s, least time {t_min!r} s, bound by {bound}",
          file=sys.stderr)
    return 100.0 * t_min / secs
