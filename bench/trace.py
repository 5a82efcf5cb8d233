"""From a profiler trace to device intervals, and from those to busy time,
program instances and idle gaps.

A trace is reduced to three lists, each of ``(start_ns, end_ns, name)``
on the trace's own clock:

* ``ops``: operations that ran on the first device (the "XLA Ops" line of
  its plane), each with the program it ran in (``op_module``);
* ``modules``: program executions on that device ("XLA Modules");
* ``host``: host events (every line of the host planes), which name what
  the host was doing when the device sat idle.

Everything here works on those lists, so a small recorded trace kept as
JSON tests it without a device.
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float, str]


class Trace:
    def __init__(self, ops: List[Interval], modules: List[Interval],
                 host: List[Interval]):
        self.ops = sorted(ops)
        self.modules = sorted(modules)
        self.host = sorted(host)
        self.op_module = self._assign_modules()

    # -- loading ---------------------------------------------------------------
    @classmethod
    def from_xplane(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(path)
        ops, modules, host = [], [], []
        device = None
        for plane in pd.planes:
            name = plane.name
            if name.startswith("/device:") and "TPU" in name.upper():
                if device is None or name < device:
                    device = name
        for plane in pd.planes:
            if plane.name == device:
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        ops += [(e.start_ns, e.end_ns, op_name(e.name))
                                for e in line.events]
                    elif line.name == "XLA Modules":
                        modules += [(e.start_ns, e.end_ns, e.name)
                                    for e in line.events]
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    host += [(e.start_ns, e.end_ns, e.name)
                             for e in line.events if e.duration_ns > 0]
        return cls(ops, modules, host)

    @classmethod
    def from_dir(cls, log_dir: str) -> Optional["Trace"]:
        files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                          recursive=True)
        return cls.from_xplane(sorted(files)[-1]) if files else None

    @classmethod
    def from_json(cls, path: str) -> "Trace":
        with open(path) as f:
            d = json.load(f)
        return cls([tuple(x) for x in d["ops"]],
                   [tuple(x) for x in d["modules"]],
                   [tuple(x) for x in d["host"]])

    # -- programs --------------------------------------------------------------
    def _assign_modules(self) -> List[str]:
        """Name of the program execution each op starts in ('' if none)."""
        starts = [m[0] for m in self.modules]
        out = []
        for s, _, _ in self.ops:
            i = bisect.bisect_right(starts, s) - 1
            out.append(self.modules[i][2]
                       if i >= 0 and self.modules[i][1] >= s else "")
        return out

    def module_instances(self, pattern: str) -> List[Interval]:
        rx = re.compile(pattern)
        return [m for m in self.modules if rx.search(m[2])]

    def op_seconds(self, op_pattern: str, module_pattern: str) -> float:
        """Device seconds of ops matching ``op_pattern`` that ran inside a
        program matching ``module_pattern``."""
        ro, rm = re.compile(op_pattern), re.compile(module_pattern)
        return sum(e - s for (s, e, n), m in zip(self.ops, self.op_module)
                   if ro.search(n) and rm.search(m)) * 1e-9

    # -- busy and idle -----------------------------------------------------------
    def busy_intervals(self) -> List[Tuple[float, float]]:
        merged: List[List[float]] = []
        for s, e, _ in self.ops:
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], e)
            else:
                merged.append([s, e])
        return [(s, e) for s, e in merged]

    def busy_seconds(self) -> float:
        return sum(e - s for s, e in self.busy_intervals()) * 1e-9

    def idle_gaps(self, top: int = 10) -> List[Tuple[str, float]]:
        """The longest gaps between device work, each named by the last
        host event that began inside it (what launched the next work)."""
        busy = self.busy_intervals()
        hstarts = [h[0] for h in self.host]
        gaps = []
        for (_, e0), (s1, _) in zip(busy, busy[1:]):
            i = bisect.bisect_right(hstarts, s1) - 1
            name = (self.host[i][2] if i >= 0 and self.host[i][0] >= e0
                    else "no host event began in the gap")
            gaps.append((name, (s1 - e0) * 1e-9))
        gaps.sort(key=lambda g: -g[1])
        return gaps[:top]

    def top_ops(self, top: int = 10) -> List[Tuple[str, float]]:
        """Device self time (an op's time less that of the ops nested in
        it, as a loop holds its body) by program and op, op names without
        their numeric suffix, largest first."""
        tot: Dict[str, float] = {}
        stack: List[list] = []          # [end, key, self time]

        def close(upto):
            while stack and stack[-1][0] <= upto:
                end, key, own = stack.pop()
                tot[key] = tot.get(key, 0.0) + own * 1e-9

        for (s, e, n), m in zip(self.ops, self.op_module):
            close(s)
            if stack:
                stack[-1][2] -= e - s
            key = f"{_short(m)}:{re.sub(r'[.0-9]+$', '', n)}"
            stack.append([e, key, e - s])
        close(float("inf"))
        return sorted(tot.items(), key=lambda kv: -kv[1])[:top]


def op_name(text: str) -> str:
    """An op's instruction name (``chunk_attention.5``) from the HLO text
    the device line carries (``%chunk_attention.5 = bf16[...] ...``)."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def _short(module: str) -> str:
    return re.sub(r"\(.*$", "", module) or "none"
