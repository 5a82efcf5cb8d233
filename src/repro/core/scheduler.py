"""AIOS scheduler (paper §3.3, Appendix A.3): centralized queues for all
modules; worker threads per module; FIFO / Round-Robin (time-sliced via the
context-interrupt mechanism) / priority strategies for the LLM queue.

RR quantum is measured in decode steps (token-level time slicing) -- the
TPU-native unit of LLM work -- rather than wall-clock Python slicing.
"""
from __future__ import annotations

import heapq
import queue
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from repro.core.syscall import Syscall, SyscallCancelled
from repro.obs import span


class _PriorityQueue:
    """heapq wrapper with the same interface subset as queue.Queue."""

    def __init__(self):
        self._h: List = []
        self._cv = threading.Condition()
        self._count = 0

    def put(self, item):
        with self._cv:
            self._count += 1
            heapq.heappush(self._h, (-item.priority, self._count, item))
            self._cv.notify()

    def get(self, timeout: Optional[float] = None):
        with self._cv:
            if not self._h and not self._cv.wait_for(lambda: bool(self._h),
                                                     timeout):
                raise queue.Empty
            return heapq.heappop(self._h)[2]

    def qsize(self):
        with self._cv:
            return len(self._h)


class BaseScheduler:
    """Owns every module queue (centralization per paper §3.3) and the worker
    threads that drain them. Subclasses set the LLM strategy knobs."""

    name = "base"
    llm_quantum: Optional[int] = None   # decode steps per slice; None = to completion

    def __init__(self, llm_core_pool, memory_manager, storage_manager,
                 tool_manager, *, log: Optional[Callable[[str], None]] = None,
                 access=None, tracer=None, recorder=None):
        self.pool = llm_core_pool
        self.memory = memory_manager
        self.storage = storage_manager
        self.tools = tool_manager
        self.access = access      # tenant front door (quotas + cross-agent ACL)
        self.tracer = tracer      # repro.obs.Tracer or None (tracing off)
        self.recorder = recorder  # repro.replay.WorkloadRecorder or None
        self.log = log or (lambda m: None)
        self.llm_queue = self._make_queue()
        self.mem_queue: "queue.Queue" = queue.Queue()
        self.sto_queue: "queue.Queue" = queue.Queue()
        self.tool_queue: "queue.Queue" = queue.Queue()
        self._threads: List[threading.Thread] = []
        self._stop = threading.Event()
        self.completed: List[Syscall] = []
        self._completed_lock = threading.Lock()

    def _make_queue(self):
        return queue.Queue()

    # -- submission -----------------------------------------------------------------
    def _quota_demand(self, sc: Syscall):
        """(tokens, KV pages) a syscall will hold while in flight -- the
        amounts the tenant quota gate charges at admission. Only LLM syscalls
        consume either; pages use core 0's page geometry (pools are
        homogeneous)."""
        if sc.category != "llm":
            return 0, 0
        rd = sc.request_data
        # a tenant's token budget meters BOTH directions of the context:
        # prompt tokens are prefill work (reserved up front, settled at the
        # actual prefilled count -- prefix-cache hits refund the difference)
        # and max_new bounds the decode side
        tokens = len(rd["prompt"]) + rd.get("max_new_tokens", 32)
        pager = self.pool.cores[0].engine.pager
        return tokens, pager.pages_for(tokens)

    def _front_door_admit(self, sc: Syscall) -> bool:
        """Tenant quota gate (paper §3.8): every submission passes through
        the access manager before touching a queue. Over-quota tenants get a
        fast structured rejection naming the binding quota; charged usage is
        released by the syscall's done-callback on any settle path.

        This is also where a tracing kernel opens the syscall's root span:
        every later lifecycle hop (queue/run/requeue phases, settle) lands
        on the trace attached here, and the done-callback armed by
        ``Tracer.attach`` closes the root exactly once on ANY settle path --
        including the quota rejection a few lines down.

        A recording kernel (``record=True``) logs the submission FIRST --
        before the quota gate -- so a replayed trace reproduces the whole
        input stream, rejected arrivals included."""
        if self.recorder is not None:
            self.recorder.record_submit(sc)
        if self.tracer is not None:
            self.tracer.attach(sc).phase("admit")
        if self.access is None:
            return True
        tokens, pages = self._quota_demand(sc)
        reason = self.access.admit_syscall(sc, tokens_needed=tokens,
                                           pages_needed=pages)
        if reason is not None:
            if sc.trace is not None:
                sc.trace.event("quota_reject", reason=reason[:120])
            sc.mark_queued()
            sc.fail(reason)
            self._record(sc)
            return False
        return True

    def _enqueue(self, syscall: Syscall):
        syscall.mark_queued()
        q = {"llm": self.llm_queue, "memory": self.mem_queue,
             "storage": self.sto_queue, "tool": self.tool_queue}[syscall.category]
        q.put(syscall)

    def submit(self, syscall: Syscall):
        if not self._front_door_admit(syscall):
            return
        self._enqueue(syscall)

    # -- lifecycle -------------------------------------------------------------------
    def start(self):
        self._stop.clear()
        workers = [("mem", self._mem_worker), ("sto", self._sto_worker),
                   ("tool", self._tool_worker)]
        for i in range(self.pool.num_cores):
            workers.append((f"llm{i}", lambda idx=i: self._llm_worker(idx)))
        for name, fn in workers:
            t = threading.Thread(target=fn, name=f"aios-{self.name}-{name}",
                                 daemon=True)
            t.start()
            self._threads.append(t)

    def stop(self):
        self._stop.set()
        for t in self._threads:
            t.join(timeout=5)
        self._threads.clear()

    def _record(self, sc: Syscall):
        with self._completed_lock:
            self.completed.append(sc)

    def _finish_cancelled(self, sc: Syscall):
        """Settle a cancelled syscall observed at a queue hop: release its
        suspended context (pages) if it holds one, then fail it. The done-
        callbacks installed at admission release quota charges."""
        if sc.context_id is not None:
            try:
                self.pool.cores[0].ctx.clear(sc.context_id)
            except Exception:  # noqa: BLE001 -- context may already be gone
                pass
            sc.context_id = None
        sc.fail("cancelled")
        self._record(sc)

    def _fail_final(self, sc: Syscall, reason: str):
        """Terminal failure: settle the syscall AND release any suspended
        context it still holds -- a retry-exhausted or infeasible syscall
        that was ever suspended owns pinned host pages, and failing it
        without clearing the context would leak them until process exit."""
        if sc.context_id is not None:
            try:
                self.pool.cores[0].ctx.clear(sc.context_id)
            except Exception:  # noqa: BLE001 -- context may already be gone
                pass
            sc.context_id = None
        sc.fail(reason)
        self._record(sc)

    def _acl_denial(self, sc: Syscall) -> Optional[Dict[str, Any]]:
        """Cross-agent access gate for memory/storage syscalls naming a
        ``target_agent``/``target_tenant``: the access manager's privilege
        groups decide; cross-tenant is always denied."""
        rd = sc.request_data or {}
        target = rd.get("target_agent")
        target_tenant = rd.get("target_tenant")
        if self.access is None or (target is None and target_tenant is None):
            return None
        target = target or sc.agent_name
        if self.access.check_access(sc.agent_name, target,
                                    tenant=sc.tenant_id,
                                    target_tenant=target_tenant):
            return None
        scope = (f" of tenant '{target_tenant}'"
                 if target_tenant and target_tenant != sc.tenant_id else "")
        return {"success": False,
                "error": f"access denied: agent '{sc.agent_name}' (tenant "
                         f"'{sc.tenant_id}') may not access resources of "
                         f"'{target}'{scope}"}

    # -- module workers ---------------------------------------------------------------
    def _drain(self, q, handler):
        while not self._stop.is_set():
            try:
                sc = q.get(timeout=0.05)
            except queue.Empty:
                continue
            if sc.cancelled:
                self._finish_cancelled(sc)
                continue
            sc.mark_running()
            try:
                resp = self._acl_denial(sc) or handler(sc)
            except Exception as e:  # noqa: BLE001 -- kernel isolates agent errors
                sc.fail(str(e))
            else:
                if sc.cancelled:
                    # cancelled while the handler ran (e.g. a timed-out
                    # join during a storage stall): the caller is gone --
                    # settle as cancelled, not with a response nobody reads
                    sc.fail("cancelled")
                else:
                    sc.complete(resp)
            self._record(sc)

    def _mem_worker(self):
        self._drain(self.mem_queue, self.memory.execute_memory_syscall)

    def _sto_worker(self):
        self._drain(self.sto_queue, self.storage.execute_storage_syscall)

    def _tool_worker(self):
        """Tool conflicts: skip conflicting calls and advance to the next
        conflict-free candidate (paper §3.7)."""
        backlog: List[Syscall] = []
        while not self._stop.is_set():
            sc = None
            for i, cand in enumerate(backlog):
                if not self.tools.has_conflict(cand.request_data["tool_name"]):
                    sc = backlog.pop(i)
                    break
            if sc is None:
                try:
                    cand = self.tool_queue.get(timeout=0.05)
                except queue.Empty:
                    continue
                if self.tools.has_conflict(cand.request_data["tool_name"]):
                    backlog.append(cand)
                    continue
                sc = cand
            if sc.cancelled:
                self._finish_cancelled(sc)
                continue
            sc.mark_running()
            try:
                resp = self.tools.execute_tool_syscall(sc)
            except Exception as e:  # noqa: BLE001
                sc.fail(str(e))
            else:
                if sc.cancelled:    # handler outlived a timed-out join
                    sc.fail("cancelled")
                else:
                    sc.complete(resp)
            self._record(sc)

    llm_retries = 2   # fault tolerance: failed cores lose at most one quantum

    def _retry_or_fail(self, sc: Syscall, err: Exception, core_idx: int):
        """Core fault: requeue so another core (or a recovered one) picks it
        up; the context snapshot bounds lost work to one quantum (DESIGN.md
        §5). Fail only after llm_retries."""
        if isinstance(err, SyscallCancelled) or sc.cancelled:
            self._finish_cancelled(sc)
            return
        retries = getattr(sc, "_retries", 0)
        if retries < self.llm_retries:
            sc._retries = retries + 1
            self.log(f"llm syscall pid={sc.pid} retry {sc._retries} after "
                     f"core{core_idx} fault: {err}")
            self.llm_queue.put(sc)
        else:
            self._fail_final(sc, str(err))

    def _llm_worker(self, core_idx: int):
        core = self.pool.cores[core_idx]
        while not self._stop.is_set():
            try:
                sc = self.llm_queue.get(timeout=0.05)
            except queue.Empty:
                continue
            if sc.cancelled:
                self._finish_cancelled(sc)
                continue
            sc.mark_running()
            try:
                finished, resp = core.execute_llm_syscall(
                    sc, quantum=self.llm_quantum)
            except Exception as e:  # noqa: BLE001
                self._retry_or_fail(sc, e, core_idx)
                continue
            if finished:
                sc.complete(resp)
                self._record(sc)
            else:
                # context interrupt: requeue at the tail (RR)
                sc.suspend(resp)          # resp = context id
                self.llm_queue.put(sc)

    # -- metrics -----------------------------------------------------------------------
    def metrics(self) -> Dict[str, float]:
        with self._completed_lock:
            done = [s for s in self.completed if s.status == "done"]
        waits = sorted(s.waiting_time for s in done)
        n = len(waits)
        return {
            "completed": n,
            "avg_wait": sum(waits) / n if n else 0.0,
            "p50_wait": waits[int(0.5 * (n - 1))] if n else 0.0,
            "p90_wait": waits[int(0.9 * (n - 1))] if n else 0.0,
        }


class FIFOScheduler(BaseScheduler):
    name = "fifo"
    llm_quantum = None          # run to completion in arrival order


class RRScheduler(BaseScheduler):
    name = "rr"

    def __init__(self, *args, quantum: int = 16, **kw):
        super().__init__(*args, **kw)
        self.llm_quantum = quantum


class PriorityScheduler(BaseScheduler):
    """Beyond-paper strategy: priority-ordered LLM queue (preemptive at
    quantum boundaries when a quantum is set)."""
    name = "priority"

    def __init__(self, *args, quantum: Optional[int] = None, **kw):
        super().__init__(*args, **kw)
        self.llm_quantum = quantum

    def _make_queue(self):
        return _PriorityQueue()


class BatchedScheduler(BaseScheduler):
    """Beyond-paper strategy (DESIGN.md §2): POOL-WIDE token-level continuous
    batching. A central dispatcher thread owns admission: it pops the shared
    LLM queue and routes syscalls to the least-loaded core by *real*
    occupancy (free decode slots, then free HBM pages -- not blind
    round-robin), applying backpressure when every core is saturated. An
    admission burst is routed as a GROUP (up to the core's free slots and a
    fair share of the backlog), so the core's engine prefills the whole
    burst through shared chunked-prefill dispatches; each core's worker
    keeps its decode batch full from its private run queue, and each worker
    tick is ONE unified engine dispatch (``serve_step``) carrying the
    burst's prefill chunk rows and every running slot's decode token
    together, so long prompts never stall running generations.

    Fairness is cross-core: a quantum-expired syscall is suspended and
    requeued on the CENTRAL queue, so it resumes on whichever core has
    capacity (context snapshots are host-side and core-agnostic). The same
    path gives fault tolerance: a core fault requeues its in-flight syscalls
    centrally (up to ``llm_retries`` each) so healthy cores absorb them, and
    no core idles while another has a backlog.

    With a ``ControlPlane`` attached (repro.control), four more behaviours
    switch on -- all bit-exact (the plane moves work, never changes tokens):
      * the central queue orders by SLO class (interactive > batch >
        best_effort), FIFO within a class;
      * the dispatcher adds prefix-affinity to placement (prefer the core
        whose engine already holds the prompt's prefix pages);
      * an about-to-miss interactive syscall may preempt a best-effort slot
        MID-quantum (today's boundary preemption stays as the fairness
        backstop);
      * a plane thread ticks the rebalancer, which migrates running contexts
        from hot cores to idle ones (snapshot -> pinned hand-off ->
        restore)."""
    name = "batched"

    def __init__(self, *args, quantum: Optional[int] = 64, control=None, **kw):
        self.control = control     # before super(): _make_queue consults it
        super().__init__(*args, **kw)
        self.llm_quantum = quantum
        self._core_queues: List["queue.Queue"] = []
        self._inflight: List[int] = []        # dispatched-not-finished per core
        self._inflight_lock = threading.Lock()
        self._dispatcher_held = 0             # 1 while the dispatcher holds a
                                              # syscall it cannot yet place

    def _make_queue(self):
        if self.control is not None:
            return self.control.make_queue()     # SLO-class-ordered
        return queue.Queue()

    def submit(self, syscall: Syscall):
        """Central-queue submission behind the two-stage admission
        controller: the tenant quota gate first (an over-quota tenant is
        rejected before it can load the pool at all), then the SLO shed --
        while interactive traffic is missing its wait target, incoming
        best_effort LLM syscalls are shed at the door (fail fast, naming the
        reason) instead of deepening a queue the misses prove is saturated."""
        if not self._front_door_admit(syscall):
            return
        if (self.control is not None and syscall.category == "llm"
                and self.control.should_shed(syscall)):
            syscall.mark_queued()
            rate = getattr(syscall, "_shed_rate", 1.0)   # the deciding value
            if syscall.trace is not None:
                syscall.trace.event("shed", miss_rate=round(rate, 3))
            syscall.fail("admission controller: best_effort load shed "
                         f"(interactive SLO miss rate {rate:.2f} >= "
                         f"{self.control.admission_miss_rate:.2f})")
            self._record(syscall)
            return
        self._enqueue(syscall)

    # -- lifecycle ------------------------------------------------------------------
    def start(self):
        n = self.pool.num_cores
        self._core_queues = [queue.Queue() for _ in range(n)]
        self._inflight = [0] * n
        self._dispatcher_held = 0
        super().start()
        t = threading.Thread(target=self._dispatcher,
                             name=f"aios-{self.name}-dispatch", daemon=True)
        t.start()
        self._threads.append(t)
        if self.control is not None and self.control.rebalancer is not None:
            tp = threading.Thread(
                target=self.control.run_loop,
                args=(self._stop, self._backlog),
                name=f"aios-{self.name}-plane", daemon=True)
            tp.start()
            self._threads.append(tp)

    # -- central dispatcher (control plane) -------------------------------------------
    def _required_tokens(self, sc: Syscall) -> int:
        rd = sc.request_data
        # suspended syscalls need seq_len + remaining <= prompt + max_new,
        # so this bound covers both fresh and resumed admissions
        return len(rd["prompt"]) + rd.get("max_new_tokens", 32)

    def _pick_core(self, sc: Syscall) -> Optional[int]:
        """Least-loaded core that can actually hold `sc`: most free decode
        slots (net of syscalls already dispatched there), pages as the
        tie-break. None when the whole pool is saturated. Cores `sc` already
        faulted on are avoided (a dead core has zero inflight and free pages,
        so it would otherwise look least-loaded and attract its own retries);
        they become candidates again only when every core has faulted.

        With the control plane's affinity router, a fresh prompt whose prefix
        is already resident on some core's engine prefers that core (affinity
        pages lead the key) -- re-prefill saved outweighs a small occupancy
        gap, and the bound is one admission burst: a core with no free slot
        is never picked on affinity alone."""
        need = self._required_tokens(sc)
        best, best_key, best_res = None, None, None
        residency = None
        router = self.control.affinity if self.control is not None else None
        if router is not None and sc.context_id is None:
            residency = router.probe(sc.request_data.get("prompt"))
        with self._inflight_lock:
            inflight = list(self._inflight)
        avoid = getattr(sc, "_faulted_cores", None)
        candidates = list(range(self.pool.num_cores))
        if avoid:
            healthy = [i for i in candidates if i not in avoid]
            candidates = healthy or candidates
        for idx in candidates:
            engine = self.pool.cores[idx].engine
            free_slots = engine.max_slots - inflight[idx]
            if free_slots <= 0:
                continue
            if not engine.pager.can_admit(need):
                continue
            aff = 0
            if router is not None:
                aff = router.affinity_pages(idx, residency,
                                            engine.pager.page_size)
            key = (aff, free_slots, engine.pager.free_pages)
            if best_key is None or key > best_key:
                best, best_key, best_res = idx, key, residency
        if best is not None and router is not None:
            router.note_routed(best, best_res)
        return best

    def _dispatch(self, core_idx: int, sc: Syscall):
        with self._inflight_lock:
            self._inflight[core_idx] += 1
        sc._core_idx = core_idx      # placement trace (benchmarks/telemetry)
        if sc.trace is not None:
            sc.trace.event("dispatch", core=core_idx)
        self._core_queues[core_idx].put(sc)

    def _undispatch(self, core_idx: int, sc: Syscall):
        """Hand a syscall back to the central queue (capacity race or
        cross-core preemption): any core may pick it up next. The SLO-queue
        arrival stamp is cleared -- a syscall coming back through here goes
        to the TAIL of its class (fair cycling among peers), unlike the
        dispatcher's backpressure requeue which keeps its place."""
        with self._inflight_lock:
            self._inflight[core_idx] -= 1
        if getattr(sc, "_slo_seq", None) is not None:
            sc._slo_seq = None
        self.llm_queue.put(sc)

    def _backlog(self) -> int:
        return self.llm_queue.qsize() + self._dispatcher_held

    def _infeasible_reason(self, sc: Syscall) -> Optional[str]:
        """Non-None when NO core could ever admit `sc` (context longer than
        max_len / more pages than exist): such a syscall must fail fast, not
        ping-pong between dispatcher and workers forever. The message names
        the limiting resource -- decode slots (max_len) vs HBM pages -- so
        operators know which knob to turn."""
        need = self._required_tokens(sc)
        slots_fit = pages_fit = False
        for core in self.pool.cores:
            eng = core.engine
            s_ok = need <= eng.max_len
            p_ok = eng.pager.pages_for(need) <= eng.pager.num_pages
            if s_ok and p_ok:
                return None
            slots_fit |= s_ok
            pages_fit |= p_ok
        if not slots_fit:
            biggest = max(c.engine.max_len for c in self.pool.cores)
            return (f"context {need} tokens exceeds every core's capacity: "
                    f"longest decode slot holds {biggest} tokens "
                    f"(limiting resource: slots)")
        if not pages_fit:
            worst = max((c.engine.pager.num_pages * c.engine.pager.page_size)
                        for c in self.pool.cores)
            return (f"context {need} tokens exceeds every core's capacity: "
                    f"largest HBM page budget holds {worst} tokens "
                    f"(limiting resource: pages)")
        return (f"context {need} tokens exceeds every core's capacity "
                f"(limiting resource: slots on some cores, pages on others)")

    def _dispatcher(self):
        pending: Optional[Syscall] = None
        while not self._stop.is_set():
            if pending is None:
                try:
                    pending = self.llm_queue.get(timeout=0.05)
                    self._dispatcher_held = 1
                except queue.Empty:
                    continue
                if pending.cancelled:
                    self._finish_cancelled(pending)
                    pending = None
                    self._dispatcher_held = 0
                    continue
                reason = self._infeasible_reason(pending)
                if reason is not None:
                    self._fail_final(pending, reason)
                    pending = None
                    self._dispatcher_held = 0
                    continue
                # burst admission: wait one batching window so the rest of a
                # burst (agents submitting together) lands on the queue, then
                # place the whole burst in one dispatch cycle -- each core
                # receives its share as a contiguous group, which its engine
                # prefills through shared chunked-prefill dispatches.
                # Resumed syscalls skip the window (they arrive alone).
                if pending.context_id is None and self.llm_queue.qsize() == 0:
                    time.sleep(0.001)
            idx = self._pick_core(pending)
            if idx is None:
                # admission backpressure: pool saturated. With the control
                # plane: escalate an about-to-miss syscall into a mid-quantum
                # preemption request, and hand the held syscall back to the
                # SLO queue so a more urgent later arrival can take the head
                # (a plain FIFO held slot would pin the dispatcher to it).
                if self.control is not None:
                    self.control.consider_preempt(pending)
                    self.llm_queue.put(pending)
                    pending = None
                    self._dispatcher_held = 0
                time.sleep(0.001)
                continue
            self._dispatch(idx, pending)
            pending = None
            self._dispatcher_held = 0
            # drain the rest of the burst: least-loaded placement per syscall
            # (inflight accounting updates as we go, so a burst spreads
            # evenly and lands on every core as one group)
            while True:
                try:
                    sc = self.llm_queue.get_nowait()
                except queue.Empty:
                    break
                if sc.cancelled:
                    self._finish_cancelled(sc)
                    continue
                reason = self._infeasible_reason(sc)
                if reason is not None:
                    self._fail_final(sc, reason)
                    continue
                idx = self._pick_core(sc)
                if idx is None:
                    pending = sc           # pool saturated: hold + backoff
                    self._dispatcher_held = 1
                    break
                self._dispatch(idx, sc)
        if pending is not None:        # stop(): don't strand the held syscall
            self.llm_queue.put(pending)
            self._dispatcher_held = 0

    # -- per-core fault path ------------------------------------------------------------
    def _retry_or_fail(self, sc: Syscall, err: Exception, core_idx: int):
        """Base retry semantics + inflight accounting; the faulting core is
        remembered so the central requeue lands on a healthy core."""
        with self._inflight_lock:
            self._inflight[core_idx] -= 1
        faulted = getattr(sc, "_faulted_cores", None) or set()
        faulted.add(core_idx)
        sc._faulted_cores = faulted
        super()._retry_or_fail(sc, err, core_idx)

    def _fault_slot(self, core_idx: int, core, slot: int, sc: Syscall,
                    err: Exception, running: Dict[int, Syscall],
                    used: Dict[int, int]):
        """Settle a slot whose finish/suspend hand-off raised (e.g. the
        storage tier failing under a context save): free the slot (the
        allocator release is idempotent), exit the control plane, and
        requeue the syscall through the retry path. Without this backstop
        the exception killed the worker thread itself -- wedging every
        other running syscall on the core forever."""
        try:
            core.engine.free(slot)
        except Exception:  # noqa: BLE001
            pass
        if self.control is not None:
            self.control.on_exit(core_idx, sc, "fault")
        self._retry_or_fail(sc, err, core_idx)
        running.pop(slot, None)
        used.pop(slot, None)

    # -- control-plane actions executed on the worker thread ----------------------------
    def _preempt_victim(self, running: Dict[int, Syscall], engine,
                        below_rank: int) -> Optional[int]:
        """Slot of the least latency-sensitive running sequence with class
        rank strictly greater than ``below_rank``. Ties break toward the
        tenant hogging this core (most running slots -- the offending tenant
        pays for the pressure it creates), then by the rebalancer's migration
        cost model: CHEAPEST resident-bytes-per-remaining-token first, the
        same ordering migrations use, since a preempted context makes the
        identical snapshot -> restore round-trip. None when nothing is
        eligible (mid-prefill and finishing slots are not)."""
        from repro.control.rebalancer import migration_cost
        tenant_load: Dict[str, int] = {}
        for sc in running.values():
            tenant_load[sc.tenant_id] = tenant_load.get(sc.tenant_id, 0) + 1
        best, best_key = None, None
        for slot, sc in running.items():
            if engine.is_prefilling(slot) or engine.is_done(slot):
                continue
            rank = self.control.policy.rank(sc)
            if rank <= below_rank:
                continue
            s = engine.slots[slot]
            remaining = s.max_new - len(s.generated)
            cost = migration_cost(engine.resident_bytes(slot), remaining)
            key = (rank, tenant_load[sc.tenant_id], -cost, remaining)
            if best_key is None or key > best_key:
                best, best_key = slot, key
        return best

    def _migration_victim(self, running: Dict[int, Syscall], engine):
        """Victim choice for rebalancing: least latency-sensitive SLO class
        first, then the page table's cost model -- resident KV page bytes
        per expected remaining token (``repro.control.rebalancer.
        migration_cost``) -- so the cheapest context with the longest tail
        moves first. Returns (slot, cost) or (None, None)."""
        from repro.control.rebalancer import pick_migration_victim
        candidates = []
        for slot, sc in running.items():
            if engine.is_prefilling(slot) or engine.is_done(slot):
                continue
            s = engine.slots[slot]
            candidates.append((slot, self.control.policy.rank(sc),
                               engine.resident_bytes(slot),
                               s.max_new - len(s.generated)))
        return pick_migration_victim(candidates)

    def _run_migrations(self, core_idx: int, core, engine,
                        running: Dict[int, Syscall], used: Dict[int, int]):
        """Execute a rebalancer request: suspend up to ``count`` running
        sequences (cost-model victim order) and hand their contexts to
        the target core -- snapshot on this thread, pinned in the shared
        ContextManager, restored by the target's worker on arrival."""
        req = self.control.take_migration(core_idx)
        if req is None:
            return
        dst, count = req
        teng = self.pool.cores[dst].engine
        for _ in range(count):
            victim, cost = self._migration_victim(running, engine)
            if victim is None:
                return
            sc = running[victim]
            with self._inflight_lock:
                room = teng.max_slots - self._inflight[dst]
            if room <= 0 or not teng.pager.can_admit(
                    self._required_tokens(sc)):
                return               # target filled up since the plan tick
            try:
                ctx_id = core._suspend(sc, victim, pinned=True)
            except Exception as e:  # noqa: BLE001 -- hand-off fault: the
                # snapshot may be gone; requeue as a fresh retry so the
                # generation re-runs instead of completing partial
                self._fault_slot(core_idx, core, victim, sc, e,
                                 running, used)
                return
            sc.suspend(ctx_id)
            if sc.trace is not None:
                sc.trace.event("migrate", src=core_idx, dst=dst,
                               cost=round(float(cost), 1))
            self.control.on_exit(core_idx, sc, "migrated")
            with self._inflight_lock:
                self._inflight[core_idx] -= 1
            self._dispatch(dst, sc)
            self.control.note_migrated(core_idx, dst, sc, cost=cost)
            del running[victim], used[victim]

    # -- per-core worker (data plane) ----------------------------------------------------
    def _llm_worker(self, core_idx: int):
        """Keeps the decode batch full AND advances prefill with decode in
        ONE engine tick (`serve_step`): in the engine's default mixed mode a
        tick is a SINGLE model dispatch that carries this burst's prompt
        chunk rows and every active slot's decode token (a length-1 chunk
        row) together -- so a burst of long prompts admits as batched
        chunked prefill, never stalls running generations, and costs one
        XLA dispatch per tick instead of the legacy chunk-then-decode pair.

        With the control plane attached the loop additionally publishes
        telemetry each iteration and executes the plane's preemption /
        migration requests -- always on this thread, the engine's only
        owner."""
        core = self.pool.cores[core_idx]
        engine = core.engine
        myq = self._core_queues[core_idx]
        running: Dict[int, Syscall] = {}      # slot -> syscall
        used: Dict[int, int] = {}             # slot -> decode steps this quantum
        while not self._stop.is_set():
            # admit everything the dispatcher routed here; fresh prompts only
            # JOIN the chunked-prefill queue (eager=False) so the whole burst
            # shares each chunk dispatch below
            while engine.free_slot_count() > 0:
                busy = bool(running) or engine.prefill_pending() > 0
                try:
                    sc = myq.get(timeout=0.0 if busy else 0.05)
                except queue.Empty:
                    break
                if sc.cancelled:
                    with self._inflight_lock:
                        self._inflight[core_idx] -= 1
                    self._finish_cancelled(sc)
                    continue
                sc.mark_running()
                try:
                    with span("aios.sched.admit", tracer=self.tracer,
                              pid=sc.pid, prompt_tokens=len(
                                  sc.request_data.get("prompt", ()))):
                        slot = core.admit(sc, eager=False)
                except RuntimeError:
                    # lost the capacity race (slots/pages went to another
                    # admission); hand back for re-dispatch
                    self._undispatch(core_idx, sc)
                    break
                except Exception as e:  # noqa: BLE001
                    self._retry_or_fail(sc, e, core_idx)
                    continue
                running[slot] = sc
                used[slot] = 0
                if self.control is not None:
                    self.control.on_admit(core_idx, sc)
            if self.control is not None:
                self.control.publish(core_idx, core, myq.qsize())
                # always consume the flag (a core that drained naturally must
                # not preempt its NEXT occupant on a stale request), but only
                # act while something preemptible is running
                rank = self.control.take_preempt(core_idx)
                if rank is not None and running:
                    # mid-quantum preemption: an about-to-miss interactive
                    # syscall asked for a slot; yield the least-sensitive
                    # running sequence NOW, not at the quantum boundary
                    victim = self._preempt_victim(running, engine, rank)
                    if victim is not None:
                        vsc = running[victim]
                        try:
                            ctx_id = core._suspend(vsc, victim)
                        except Exception as e:  # noqa: BLE001
                            self._fault_slot(core_idx, core, victim, vsc, e,
                                             running, used)
                            continue
                        vsc.suspend(ctx_id)
                        if vsc.trace is not None:
                            vsc.trace.event("preempt", core=core_idx,
                                            below_rank=rank)
                        self.control.note_preempted(core_idx, vsc)
                        self.control.on_exit(core_idx, vsc, "suspended")
                        self._undispatch(core_idx, vsc)
                        del running[victim], used[victim]
                if running:
                    self._run_migrations(core_idx, core, engine, running,
                                         used)
            # cancellation sweep: a timed-out join (or explicit cancel())
            # must free the slot + pages NOW, not at generation end
            for slot, sc in list(running.items()):
                if not sc.cancelled:
                    continue
                try:
                    engine.free(slot)
                except Exception:  # noqa: BLE001
                    pass
                if self.control is not None:
                    self.control.on_exit(core_idx, sc, "cancelled")
                with self._inflight_lock:
                    self._inflight[core_idx] -= 1
                self._finish_cancelled(sc)
                del running[slot], used[slot]
            if not running:
                time.sleep(0.001)
                continue
            try:
                # one tick: prefill chunks + decode tokens (ONE dispatch in
                # mixed mode; the interleaved pair in legacy mode)
                emitted = engine.serve_step()
            except Exception as e:  # noqa: BLE001
                # core fault mid-decode: every in-flight syscall loses at most
                # this quantum; requeue centrally so healthy cores absorb them
                for slot, sc in list(running.items()):
                    try:
                        engine.free(slot)
                    except Exception:  # noqa: BLE001
                        pass
                    if self.control is not None:
                        self.control.on_exit(core_idx, sc, "fault")
                    self._retry_or_fail(sc, e, core_idx)
                running.clear()
                used.clear()
                continue
            # token-accurate quantum accounting: a speculative tick commits
            # several tokens for one slot in one dispatch -- charge them all,
            # or a spec-accelerated stream outruns its fair-share quantum
            commits = getattr(engine, "last_tick_commits", None) or {}
            for slot in list(running):
                sc = running[slot]
                if slot in emitted:
                    used[slot] += commits.get(slot, 1)
                if engine.is_done(slot):
                    try:
                        resp = core._finish(sc, slot)
                    except Exception as e:  # noqa: BLE001 -- finish hand-off
                        # died (engine fault while reading the result)
                        self._fault_slot(core_idx, core, slot, sc, e,
                                         running, used)
                        continue
                    sc.complete(resp)
                    self._record(sc)
                    if self.control is not None:
                        self.control.on_exit(core_idx, sc, "finished")
                    with self._inflight_lock:
                        self._inflight[core_idx] -= 1
                    del running[slot], used[slot]
                elif self.llm_quantum and used[slot] >= self.llm_quantum and \
                        not engine.is_prefilling(slot) and \
                        (self._backlog() > 0 or myq.qsize() > 0):
                    # quantum expired AND someone is waiting anywhere in the
                    # pool: yield the slot; the dispatcher may resume this
                    # generation on a different core
                    try:
                        ctx_id = core._suspend(sc, slot)
                    except Exception as e:  # noqa: BLE001 -- snapshot/save
                        # fault: requeue as a fresh retry, don't die
                        self._fault_slot(core_idx, core, slot, sc, e,
                                         running, used)
                        continue
                    sc.suspend(ctx_id)
                    if self.control is not None:
                        self.control.on_exit(core_idx, sc, "suspended")
                    self._undispatch(core_idx, sc)
                    del running[slot], used[slot]
        # drain on stop: finish whatever is still running (mid-prefill slots
        # report the tokens they have, i.e. none -- same as mid-decode)
        for slot, sc in running.items():
            try:
                resp = core._finish(sc, slot)
                sc.complete(resp)
            except Exception as e:  # noqa: BLE001
                sc.fail(str(e))
            self._record(sc)
