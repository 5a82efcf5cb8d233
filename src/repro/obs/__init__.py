"""Kernel-wide observability (repro.obs): the structured view through the
kernel that ROADMAP item 5 (trace record/replay) builds on.

Three cooperating pieces, each usable alone:

  * ``Tracer`` / ``SyscallTrace`` (trace.py) -- syscall-lifecycle spans and
    kernel point events in a bounded ring, exported as Chrome-trace /
    Perfetto JSON (``tracer.export(path)`` then ui.perfetto.dev);
    ``span`` marks the serving path's layer boundaries in any
    ``jax.profiler`` trace, and in the tracer when one is given;
  * ``MetricsRegistry`` (registry.py) -- typed counters/gauges/histograms
    with labels, legacy ``metrics()`` dict providers re-registered as a
    view, and a Prometheus text exporter (``serve_metrics`` for a live
    endpoint);
  * ``TickProfiler`` (profiler.py) -- per-tick engine samples (dispatch
    kind, bucket shape, occupancy, packed savings, wall time) in
    preallocated ring buffers feeding p50/p90 tick histograms.

Everything but the spans is opt-in and costs ~0 when off: call sites guard
on a single attribute (``sc.trace is None`` / ``engine.profiler is None``)
and the hot decode path allocates nothing per token. The spans are always
there, a few per tick, each one native call outside a profiler session.
"""
from repro.obs.profiler import TickProfiler
from repro.obs.registry import MetricsRegistry, serve_metrics
from repro.obs.trace import (PID_ENGINE, PID_MEMORY, PID_SPANS, PID_SYSCALLS,
                             Tracer, now_ns, span)

__all__ = ["Tracer", "MetricsRegistry", "TickProfiler", "serve_metrics",
           "span", "now_ns", "PID_SYSCALLS", "PID_ENGINE", "PID_MEMORY",
           "PID_SPANS"]
