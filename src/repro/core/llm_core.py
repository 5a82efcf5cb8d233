"""LLM core abstraction (paper §3.2, Appendix A.2): each core wraps one model
replica (a ServingEngine on a mesh slice) behind a unified syscall interface.
The pool routes syscalls across cores (sequential / round-robin / least-loaded
-- the paper's RouterStrategy).
"""
from __future__ import annotations

import itertools
import threading
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from repro.core.syscall import LLMSyscall, SyscallCancelled
from repro.obs import span
from repro.serving.engine import ServingEngine


class LLMCore:
    """One LLM instance. execute_llm_syscall implements the paper's
    generate_response_with_interruption: run at most `quantum` decode steps,
    snapshot + suspend if unfinished."""

    def __init__(self, engine: ServingEngine, context_manager, core_id: int = 0):
        self.engine = engine
        self.ctx = context_manager
        self.core_id = core_id
        self._lock = threading.Lock()   # exclusive-mode serialization
        self.executed = 0
        self.migrations_out = 0          # contexts handed to another core
        self.migrations_in = 0           # contexts restored from another core
        self.harvest_errors = 0          # prefix persists lost to storage faults

    # -- occupancy ------------------------------------------------------------------
    def free_capacity(self) -> Tuple[int, int]:
        """Real occupancy for pool routing: (free decode slots, free HBM
        pages). Bigger is less loaded."""
        return (self.engine.free_slot_count(), self.engine.pager.free_pages)

    # -- telemetry (published to the control plane's bus) -----------------------------
    def telemetry(self) -> Dict[str, float]:
        """One gauge sample of this core's instantaneous state -- the ONLY
        gauge source (ControlPlane.publish consumes it verbatim): what the
        rebalancer and the SLO policy act on."""
        eng = self.engine
        free = eng.free_slot_count()
        return {
            "free_slots": free,
            "free_pages": eng.pager.free_pages,
            "page_size": eng.pager.page_size,
            "prefill_debt": eng.prefill_debt(),
            "running": eng.max_slots - free,
            # page-table byte view of this core's live contexts (every
            # pager reservation is slot-owned): what the rebalancer's
            # victim cost model totals slot-by-slot
            "resident_kv_bytes": eng.pager.used_bytes(),
            "migrations_out": self.migrations_out,
            "migrations_in": self.migrations_in,
        }

    # -- admission ------------------------------------------------------------------
    def admit(self, sc: LLMSyscall, eager: bool = True) -> int:
        """Place a syscall into a decode slot (restore if it was suspended).
        With ``eager=False`` a fresh prompt only joins the engine's
        chunked-prefill queue; the caller interleaves ``prefill_step()`` with
        decode steps, so a burst routed to this core shares one batched
        chunk dispatch instead of one prefill per sequence."""
        rd = sc.request_data
        # streamed syscalls re-wire their token channel on every (re)admit,
        # so the channel survives suspends and cross-core migrations
        sink = sc.token_sink() if isinstance(sc, LLMSyscall) else None
        if sc.context_id is not None:
            snap = self.ctx.load(sc.context_id)
            slot = self.engine.restore(snap, seq_id=sc.pid, eager=eager,
                                       sink=sink)
            self.ctx.clear(sc.context_id)
            sc.context_id = None
            if getattr(sc, "_migrated_from", None) is not None:
                if sc._migrated_from != self.core_id:
                    self.migrations_in += 1   # restore-on-arrival completed
                sc._migrated_from = None
        else:
            slot = self.engine.add_sequence(
                np.asarray(rd["prompt"], np.int32), seq_id=sc.pid,
                max_new=rd.get("max_new_tokens", 32),
                eos_id=rd.get("eos_id", -1),
                image_embeds=rd.get("image_embeds"),
                eager=eager, sink=sink)
            # actual prefill cost of this admission (prefix-cache hits
            # subtract): settled against the tenant's token budget at finish
            sc._prefill_tokens = int(self.engine.slots[slot].prefilled)
        return slot

    def _finish(self, sc: LLMSyscall, slot: int) -> Dict[str, Any]:
        tokens = self.engine.result(slot)
        prompt_tokens = getattr(sc, "_prefill_tokens", None)
        if prompt_tokens is None:
            prompt_tokens = len(self.engine.slots[slot].prompt)
        with span("aios.core.finish", tracer=self.engine.tracer, pid=sc.pid,
                  tokens=len(tokens)):
            try:
                # grown resubmissions extend, not re-prefill
                self.engine.harvest_prefix(slot)
            except Exception:  # noqa: BLE001 -- caching is best-effort: a
                # storage-tier fault during the persist must not fail (or
                # retry) a generation that already FINISHED
                self.harvest_errors += 1
            self.engine.free(slot)
        return {"tokens": tokens, "finished": True,
                "usage": {"new_tokens": len(tokens),
                          "prompt_tokens": int(prompt_tokens)}}

    def _suspend(self, sc: LLMSyscall, slot: int, *,
                 pinned: bool = False) -> str:
        """Snapshot `slot` into the shared ContextManager. ``pinned`` is the
        migration hand-off path: the snapshot is kept in host RAM (never
        spilled to disk) until the receiving core restores it, so a
        cross-core migration costs one host round-trip, not two plus disk."""
        ctx_id = f"ctx-{sc.pid}"
        with span("aios.core.suspend", tracer=self.engine.tracer,
                  pid=sc.pid):
            snap = self.engine.snapshot(slot, kind=self.ctx.mode)
            self.ctx.save(ctx_id, snap, pinned=pinned)
        if pinned:
            self.migrations_out += 1
            sc._migrated_from = self.core_id
        return ctx_id

    # -- exclusive (paper-faithful: one prompt at a time) -----------------------------
    def execute_llm_syscall(self, sc: LLMSyscall,
                            quantum: Optional[int] = None
                            ) -> Tuple[bool, Any]:
        with self._lock:
            slot = self.admit(sc)
            try:
                steps = 0
                while not self.engine.is_done(slot):
                    if sc.cancelled:
                        raise SyscallCancelled(f"pid={sc.pid}")
                    if quantum is not None and steps >= quantum:
                        ctx_id = self._suspend(sc, slot)
                        return False, ctx_id
                    self.engine.step()
                    steps += 1
                resp = self._finish(sc, slot)
            except Exception:
                # fault (or cancel) mid-decode: the slot and its HBM pages
                # must not leak with the dying syscall. free() is
                # idempotent, so the suspend path (whose snapshot already
                # freed the slot) never double-releases.
                try:
                    self.engine.free(slot)
                except Exception:  # noqa: BLE001
                    pass
                raise
        self.executed += 1
        return True, resp

    # -- trial-and-error baseline (paper §1/§4.3 "without AIOS") ----------------------
    def unmanaged_try_load(self, sc: LLMSyscall) -> Optional[int]:
        """Speculatively load (prefill) without admission control. When the
        device is full this burns a real prefill's worth of work and fails --
        the GPU trial-and-error cost, reproduced honestly."""
        rd = sc.request_data
        prompt = np.asarray(rd["prompt"], np.int32)
        if not self.engine.can_admit(len(prompt), rd.get("max_new_tokens", 32)):
            # the wasted tensor-load: a prefill that hits the memory wall
            self.engine.probe_failed_load(prompt)
            return None
        return self.admit(sc)


class LLMCorePool:
    def __init__(self, cores: List[LLMCore], strategy: str = "round_robin"):
        assert cores
        self.cores = cores
        self.strategy = strategy
        self._rr = itertools.cycle(range(len(cores)))

    @property
    def num_cores(self) -> int:
        return len(self.cores)

    def route(self) -> LLMCore:
        if self.strategy == "sequential":
            return self.cores[0]
        if self.strategy == "least_loaded":
            return max(self.cores, key=lambda c: c.free_capacity())
        return self.cores[next(self._rr)]
