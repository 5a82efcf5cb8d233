"""Syscall-lifecycle tracing with Chrome-trace / Perfetto JSON export.

Every syscall submitted to a tracing kernel carries a ``SyscallTrace``: a
root span opened at ``BaseScheduler.submit`` and closed EXACTLY ONCE on
every settle path (complete / fail / shed / cancel) via the syscall's
done-callback -- the same exactly-once hook quota release rides on. Between
submit and settle the trace is a phase state machine whose child spans TILE
the root with no gaps (each phase closes at the instant the next opens):

    submit -> admit -> queue -> run -> [requeue -> run]* -> settle

plus point events (suspend, dispatch, preempt, migrate, first_token,
prefix_hit, page demote/promote/quantize, cancel_requested, quota_reject).

Export is the Chrome trace-event format Perfetto loads directly: one
"process" lane per subsystem (syscalls / engines / memory), one "thread"
per syscall pid or engine id, "X" complete events for spans and "i"
instants for point events, timestamps in microseconds from the tracer's
start. Events live in a bounded ring (oldest dropped first, counted) so a
long-running kernel cannot grow without bound.

The serving path's layer boundaries are ``span``s: each is always a
``jax.profiler.TraceAnnotation`` (in a profiler session it lands on the
host plane of the same trace as the device's ops), and with a tracer also
an "X" event here. Both stamp the profiler's host clock (``now_ns``, the
realtime clock); the export records the tracer's origin on it, so a
Chrome trace and a profiler trace of the same seconds line up after one
fixed shift.

Cost model: a span is one dict append under a lock -- microseconds, paid
per lifecycle transition or per engine tick, never per token. A kernel
without a tracer pays a single ``is None`` attribute check at each site;
a ``span`` outside a profiler session costs one native call more.
"""
from __future__ import annotations

import json
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

# Chrome-trace "process" lanes: one per subsystem so Perfetto groups the
# timeline as syscalls / engine ticks / memory-tier traffic / the host
# threads' serving-path spans.
PID_SYSCALLS = 1
PID_ENGINE = 2
PID_MEMORY = 3
PID_SPANS = 4

_PROCESS_NAMES = {PID_SYSCALLS: "syscalls", PID_ENGINE: "engines",
                  PID_MEMORY: "kv-pages", PID_SPANS: "host spans"}

# The clock the profiler stamps host events with (CLOCK_REALTIME, in ns):
# jax.profiler.ProfileData reports them relative to the session's
# ``profile_start_time`` on the same clock.
now_ns = time.time_ns


class Tracer:
    """Bounded ring of Chrome-trace events; thread-safe; µs timestamps
    from ``origin_ns`` on the profiler's host clock (``now_ns``)."""

    def __init__(self, *, cap: int = 262144, enabled: bool = True):
        self.enabled = enabled
        self.origin_ns = now_ns()
        self._buf: deque = deque(maxlen=max(1, int(cap)))
        self._lock = threading.Lock()
        self._named = set()          # (pid, tid) lanes already labelled
        self.dropped = 0             # events evicted by the ring cap
        self.roots_opened = 0
        self.roots_closed = 0

    # -- clock / low-level emit --------------------------------------------------
    def now_us(self) -> float:
        return self.us(now_ns())

    def us(self, t_ns: int) -> float:
        """A ``now_ns`` stamp as this tracer's µs timestamp."""
        return (t_ns - self.origin_ns) * 1e-3

    def _emit(self, ev: Dict[str, Any]) -> None:
        if not self.enabled:
            return
        with self._lock:
            if len(self._buf) == self._buf.maxlen:
                self.dropped += 1
            self._buf.append(ev)

    def name_track(self, pid: int, tid: int, name: str) -> None:
        """Label a (process, thread) lane once -- Perfetto shows the name
        instead of raw ids."""
        key = (pid, tid)
        with self._lock:
            if key in self._named:
                return
            self._named.add(key)
        proc = _PROCESS_NAMES.get(pid, f"pid{pid}")
        self._emit({"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
                    "args": {"name": proc}})
        self._emit({"name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
                    "args": {"name": name}})

    # -- span / instant primitives -----------------------------------------------
    def complete(self, name: str, pid: int, tid: int, ts_us: float,
                 dur_us: float, args: Optional[Dict[str, Any]] = None) -> None:
        ev = {"name": name, "ph": "X", "pid": pid, "tid": tid,
              "ts": ts_us, "dur": max(0.0, dur_us)}
        if args:
            ev["args"] = args
        self._emit(ev)

    def instant(self, name: str, pid: int, tid: int,
                args: Optional[Dict[str, Any]] = None,
                ts_us: Optional[float] = None) -> None:
        ev = {"name": name, "ph": "i", "s": "t", "pid": pid, "tid": tid,
              "ts": self.now_us() if ts_us is None else ts_us}
        if args:
            ev["args"] = args
        self._emit(ev)

    # -- syscall lifecycle --------------------------------------------------------
    def attach(self, sc) -> "SyscallTrace":
        """Open a root span for ``sc`` and arm the exactly-once close on its
        done-callback. Idempotent per syscall (re-submission after a fault
        retry reuses the existing trace)."""
        st = getattr(sc, "trace", None)
        if st is not None:
            return st
        st = SyscallTrace(self, sc)
        sc.trace = st
        self.roots_opened += 1
        sc.add_done_callback(st._on_settle)
        return st

    # -- export -------------------------------------------------------------------
    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._buf)

    def chrome_trace(self) -> Dict[str, Any]:
        """The events, with the origin of their timestamps: an event at
        ``ts`` µs sits at ``origin_ns + 1000 * ts`` on the realtime clock,
        which is a profiler trace's ``profile_start_time`` plus its
        event's start."""
        return {"traceEvents": self.events(), "displayTimeUnit": "ms",
                "otherData": {"clock": "realtime",
                              "origin_ns": self.origin_ns}}

    def export(self, path: str) -> int:
        """Write Perfetto-loadable JSON; returns the event count."""
        trace = self.chrome_trace()
        with open(path, "w") as f:
            json.dump(trace, f)
        return len(trace["traceEvents"])

    def metrics(self) -> Dict[str, int]:
        with self._lock:
            n = len(self._buf)
        return {"events": n, "dropped": self.dropped,
                "roots_opened": self.roots_opened,
                "roots_closed": self.roots_closed}


class span:
    """One serving-path boundary, as a context manager::

        with span("aios.core.finish", tracer=tr, pid=sc.pid) as sp:
            ...
            sp.set(tokens=n)

    Always a ``jax.profiler.TraceAnnotation`` named ``name`` with the args
    as its metadata: JAX records it only inside a profiler session, on the
    host plane of the device's trace. With ``tracer`` the same interval is
    also an "X" event on the "host spans" lane of the calling thread, so
    spans nest there as they do in the profiler. ``pid`` is
    the syscall the span serves and rides in both as an arg; ``args`` are
    counts, and ``set`` adds those known only at the end."""

    __slots__ = ("name", "args", "_tracer", "_ann", "_t0")

    def __init__(self, name: str, *, tracer: Optional[Tracer] = None,
                 pid: Optional[int] = None, **args: Any):
        if pid is not None:
            args["pid"] = pid
        self.name = name
        self.args = args
        self._tracer = tracer

    def __enter__(self) -> "span":
        self._ann = TraceAnnotation(self.name, **self.args)
        self._ann.__enter__()
        if self._tracer is not None:
            self._t0 = now_ns()
        return self

    def set(self, **args: Any) -> None:
        self.args.update(args)
        self._ann.set_metadata(**args)

    def __exit__(self, *exc) -> None:
        tr = self._tracer
        if tr is not None:
            t1 = now_ns()
            tid = threading.get_native_id()
            tr.name_track(PID_SPANS, tid, threading.current_thread().name)
            tr.complete(self.name, PID_SPANS, tid, tr.us(self._t0),
                        (t1 - self._t0) * 1e-3, dict(self.args))
        self._ann.__exit__(*exc)


class SyscallTrace:
    """Per-syscall trace context: a root span + tiling phase child spans +
    point events, all on the syscall's own Perfetto lane (tid = pid)."""

    __slots__ = ("tracer", "tid", "meta", "_t_root", "_phase", "_t_phase",
                 "_closed", "_lock")

    def __init__(self, tracer: Tracer, sc):
        self.tracer = tracer
        self.tid = sc.pid
        self.meta = {"syscall": sc.pid, "agent": sc.agent_name,
                     "tenant": sc.tenant_id, "category": sc.category}
        self._t_root = tracer.now_us()
        self._phase = "submit"
        self._t_phase = self._t_root
        self._closed = False
        self._lock = threading.Lock()
        tracer.name_track(PID_SYSCALLS, self.tid,
                          f"pid {sc.pid} {sc.agent_name} [{sc.tenant_id}]")

    def _close_phase(self, now_us: float,
                     args: Optional[Dict[str, Any]] = None) -> None:
        # caller holds self._lock
        self.tracer.complete(self._phase, PID_SYSCALLS, self.tid,
                             self._t_phase, now_us - self._t_phase, args)

    def phase(self, name: str, **args: Any) -> None:
        """Enter a new lifecycle phase: the previous phase span closes at
        the same instant this one opens, so phases tile the root span."""
        with self._lock:
            if self._closed:
                return
            now = self.tracer.now_us()
            self._close_phase(now)
            self._phase = name
            self._t_phase = now
        if args:
            self.tracer.instant(f"{name}_enter", PID_SYSCALLS, self.tid,
                                args, ts_us=now)

    def event(self, name: str, **args: Any) -> None:
        """Point event on this syscall's lane (never opens/closes spans, so
        it is safe from any thread at any lifecycle stage)."""
        self.tracer.instant(name, PID_SYSCALLS, self.tid, args or None)

    def _on_settle(self, sc) -> None:
        self.finish(status=sc.status, error=sc.error)

    def finish(self, status: str, error: Optional[str] = None) -> None:
        """Close the open phase and the root span. Runs exactly once (the
        done-callback fires once per syscall; re-entry is a no-op)."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            now = self.tracer.now_us()
            self._close_phase(now)
            args = dict(self.meta, status=status)
            if error:
                args["error"] = str(error)[:200]
            self.tracer.complete("syscall", PID_SYSCALLS, self.tid,
                                 self._t_root, now - self._t_root, args)
        self.tracer.roots_closed += 1
