"""Scheduler behaviour: FIFO ordering, RR preemption via context interrupt,
priority ordering, batched continuous batching -- plus the conservation
property (every submitted syscall completes exactly once)."""
import threading
import time

import numpy as np
import pytest

from repro.core import AIOSKernel, LLMSyscall
from repro.agents import register_builtin_tools
from repro.sdk.query import LLMQuery


def make_kernel(scheduler, **kw):
    kw.setdefault("engine_kw", {"max_slots": 4, "max_len": 256})
    k = AIOSKernel(arch="tiny", scheduler=scheduler, **kw)
    register_builtin_tools(k.tools)
    return k


def _llm(agent, n_prompt=8, max_new=8, priority=0):
    return LLMQuery(prompt=list(range(1, n_prompt + 1)),
                    max_new_tokens=max_new, priority=priority
                    ).to_syscall(agent)


@pytest.mark.parametrize("scheduler", ["fifo", "rr", "batched", "priority"])
def test_conservation_all_syscalls_complete_once(scheduler):
    with make_kernel(scheduler) as k:
        scs = [_llm(f"agent{i}") for i in range(6)]
        for sc in scs:
            k.submit(sc)
        outs = [sc.join(timeout=120) for sc in scs]
    assert all(o["finished"] for o in outs)
    assert all(len(o["tokens"]) == 8 for o in outs)
    done_pids = [s.pid for s in k.scheduler.completed if s.category == "llm"]
    assert sorted(done_pids) == sorted(s.pid for s in scs)  # exactly once


def test_fifo_runs_to_completion_in_order():
    with make_kernel("fifo") as k:
        scs = [_llm(f"a{i}", max_new=6) for i in range(4)]
        for sc in scs:
            k.submit(sc)
        for sc in scs:
            sc.join(timeout=120)
    ends = [sc.end_time for sc in scs]
    assert ends == sorted(ends)            # FIFO completion order
    assert all(sc.quanta_used == 0 for sc in scs)  # never preempted


def test_rr_preempts_long_generations():
    with make_kernel("rr", quantum=4) as k:
        long_sc = _llm("long", max_new=16)
        k.submit(long_sc)
        long_sc.join(timeout=120)
    assert long_sc.quanta_used >= 2        # context-interrupted repeatedly
    assert len(long_sc.response["tokens"]) == 16
    assert k.context.stats["saves"] >= 2


def test_rr_interleaves_fairly():
    """With RR, a short job submitted after a long one should not wait for
    the long job to finish (contrast with FIFO). The long job must outlast
    the pause before the short one arrives: warm, 48 tiny-model tokens
    take 30-90 ms on a CPU, against the 50 ms pause."""
    with make_kernel("rr", quantum=4) as k:
        long_sc = _llm("long", max_new=200)
        k.submit(long_sc)
        time.sleep(0.05)
        short_sc = _llm("short", max_new=4)
        k.submit(short_sc)
        short_sc.join(timeout=120)
        long_sc.join(timeout=120)
    assert short_sc.end_time < long_sc.end_time


def test_priority_order():
    with make_kernel("priority") as k:
        # stall the core briefly so all three queue together
        blocker = _llm("blocker", max_new=12)
        k.submit(blocker)
        lo = _llm("low", max_new=4, priority=0)
        hi = _llm("high", max_new=4, priority=10)
        k.submit(lo)
        k.submit(hi)
        lo.join(timeout=120)
        hi.join(timeout=120)
    assert hi.end_time < lo.end_time


def test_batched_scheduler_overlaps_and_matches_exclusive_outputs():
    """Continuous batching must produce the same tokens as exclusive FIFO
    (slot-placement independence) while running concurrently."""
    prompts = [list(range(1, 9)), list(range(3, 20, 2)), [7, 5, 3],
               list(range(2, 30, 3))]
    outs = {}
    for sched in ("fifo", "batched"):
        with make_kernel(sched) as k:
            scs = [LLMQuery(prompt=p, max_new_tokens=10).to_syscall(f"ag{i}")
                   for i, p in enumerate(prompts)]
            for sc in scs:
                k.submit(sc)
            outs[sched] = [sc.join(timeout=120)["tokens"] for sc in scs]
    assert outs["fifo"] == outs["batched"]


def test_batched_pool_dispatches_by_occupancy():
    """Pool-wide continuous batching: the central dispatcher must keep every
    core busy (no core idles while another has a backlog) and complete all
    syscalls exactly once."""
    with make_kernel("batched", num_cores=2) as k:
        scs = [_llm(f"pool{i}", max_new=12) for i in range(12)]
        for sc in scs:
            k.submit(sc)
        outs = [sc.join(timeout=300) for sc in scs]
    assert all(len(o["tokens"]) == 12 for o in outs)
    per_core = [c.engine.stats["tokens"] for c in k.pool.cores]
    assert all(t > 0 for t in per_core), per_core   # both cores did real work
    done_pids = [s.pid for s in k.scheduler.completed if s.category == "llm"]
    assert sorted(done_pids) == sorted(s.pid for s in scs)


def test_batched_pool_matches_single_core_exclusive_outputs():
    """Cross-core dispatch + shared prefix cache must not change tokens:
    2-core batched == 1-core exclusive FIFO (replicas are identical)."""
    prompts = [list(range(1, 9)), list(range(3, 20, 2)), [7, 5, 3],
               list(range(2, 30, 3)), list(range(4, 11))]
    outs = {}
    for sched, cores in (("fifo", 1), ("batched", 2)):
        with make_kernel(sched, num_cores=cores) as k:
            scs = [LLMQuery(prompt=p, max_new_tokens=10).to_syscall(f"x{i}")
                   for i, p in enumerate(prompts)]
            for sc in scs:
                k.submit(sc)
            outs[sched] = [sc.join(timeout=300)["tokens"] for sc in scs]
    assert outs["fifo"] == outs["batched"]


def test_batched_preemption_fairness_long_job_yields():
    """A long generation must yield its decode slot at the quantum boundary
    when the queue is non-empty (here: the only slot), instead of running to
    completion while the short job starves."""
    with make_kernel("batched", quantum=4,
                     engine_kw={"max_slots": 1, "max_len": 256}) as k:
        long_sc = _llm("long", max_new=40)
        k.submit(long_sc)
        deadline = time.time() + 60
        while long_sc.status != "running":   # admitted (a fixed sleep races
            time.sleep(0.005)                # warm-compile-cache decode speed)
            assert time.time() < deadline
        short_sc = _llm("short", max_new=4)
        k.submit(short_sc)
        short_sc.join(timeout=300)
        long_sc.join(timeout=300)
    assert short_sc.end_time < long_sc.end_time
    assert long_sc.quanta_used >= 1          # preempted, not run-to-completion
    assert len(long_sc.response["tokens"]) == 40
    assert len(short_sc.response["tokens"]) == 4


def test_batched_fault_requeues_centrally():
    """A core fault during batched admission must requeue the syscall on the
    central queue (llm_retries), not fail it."""
    with make_kernel("batched") as k:
        core = k.pool.cores[0]
        original = core.admit
        state = {"failed": False}

        def flaky(sc, **kw):
            if not state["failed"]:
                state["failed"] = True
                raise ValueError("injected admission fault")
            return original(sc, **kw)

        core.admit = flaky
        sc = _llm("faulty", max_new=6)
        k.submit(sc)
        out = sc.join(timeout=300)
    assert out["finished"] and len(out["tokens"]) == 6
    assert sc._retries == 1


def test_batched_step_fault_retries_inflight():
    """A core fault mid-decode requeues every in-flight syscall; they are
    absorbed on retry within llm_retries."""
    with make_kernel("batched") as k:
        eng = k.pool.cores[0].engine
        original = eng.serve_step      # the worker's per-tick entry point
        state = {"failed": False}

        def flaky_step():
            if not state["failed"]:
                state["failed"] = True
                raise ValueError("injected decode fault")
            return original()

        eng.serve_step = flaky_step
        scs = [_llm(f"f{i}", max_new=6) for i in range(3)]
        for sc in scs:
            k.submit(sc)
        outs = [sc.join(timeout=300) for sc in scs]
    assert all(len(o["tokens"]) == 6 for o in outs)
    assert any(getattr(sc, "_retries", 0) >= 1 for sc in scs)


def test_metrics_populated():
    with make_kernel("rr") as k:
        scs = [_llm(f"m{i}", max_new=4) for i in range(3)]
        for sc in scs:
            k.submit(sc)
        for sc in scs:
            sc.join(timeout=120)
        m = k.metrics()
    assert m["completed"] == 3
    assert m["avg_wait"] > 0 and m["p90_wait"] >= m["avg_wait"] * 0.5


def test_batched_infeasible_syscall_fails_fast():
    """A syscall no core could ever admit (context > max_len) must fail at
    dispatch, not spin between dispatcher and workers forever."""
    with make_kernel("batched", num_cores=2,
                     engine_kw={"max_slots": 2, "max_len": 64}) as k:
        poison = LLMQuery(prompt=list(range(1, 60)),
                          max_new_tokens=32).to_syscall("poison")
        ok = _llm("ok", max_new=4)
        k.submit(poison)
        k.submit(ok)
        assert len(ok.join(timeout=120)["tokens"]) == 4
        with pytest.raises(RuntimeError, match="capacity"):
            poison.join(timeout=120)
    assert poison.status == "error"


def test_batched_infeasible_message_names_slots():
    """The fail-fast error must say WHICH resource can never hold the
    context: here max_len (decode slots) is the binding constraint."""
    with make_kernel("batched", engine_kw={"max_slots": 2, "max_len": 64}) as k:
        poison = LLMQuery(prompt=list(range(1, 60)),
                          max_new_tokens=32).to_syscall("poison")
        k.submit(poison)
        with pytest.raises(RuntimeError, match="limiting resource: slots"):
            poison.join(timeout=120)


def test_batched_infeasible_message_names_pages():
    """Same, with the HBM page budget as the binding constraint (max_len
    would fit the context; pages cannot)."""
    with make_kernel("batched", engine_kw={"max_slots": 2, "max_len": 256,
                                           "hbm_pages": 4}) as k:
        poison = LLMQuery(prompt=list(range(1, 81)),
                          max_new_tokens=20).to_syscall("poison")
        k.submit(poison)
        with pytest.raises(RuntimeError, match="limiting resource: pages"):
            poison.join(timeout=120)


def test_batched_burst_spreads_evenly_across_cores():
    """Burst placement is least-loaded per syscall with live inflight
    accounting, so a burst splits evenly instead of piling onto one core."""
    n = 8
    with make_kernel("batched", num_cores=2,
                     engine_kw={"max_slots": 8, "max_len": 256}) as k:
        scs = [_llm(f"ev{i}", n_prompt=64, max_new=4) for i in range(n)]
        for sc in scs:
            k.submit(sc)
        for sc in scs:
            sc.join(timeout=300)
    per_core = [c.engine.stats["prefills"] for c in k.pool.cores]
    assert sum(per_core) == n
    assert min(per_core) >= 2, per_core        # neither core starved


def test_batched_burst_shares_prefill_dispatches():
    """A burst of admissions must share chunked-prefill dispatches: the pool
    runs strictly fewer chunk dispatches than sequences admitted (serial
    admission would pay one full prefill per sequence)."""
    n = 8
    rng = np.random.default_rng(11)
    prompts = [list(map(int, rng.integers(1, 500, 120))) for _ in range(n)]
    with make_kernel("batched", num_cores=2,
                     engine_kw={"max_slots": 8, "max_len": 256}) as k:
        scs = [LLMQuery(prompt=p, max_new_tokens=6).to_syscall(f"b{i}")
               for i, p in enumerate(prompts)]
        for sc in scs:
            k.submit(sc)
        outs = [sc.join(timeout=300) for sc in scs]
    assert all(len(o["tokens"]) == 6 for o in outs)
    chunks = sum(c.engine.stats["prefill_chunks"] for c in k.pool.cores)
    admitted = sum(c.engine.stats["prefills"] for c in k.pool.cores)
    assert admitted == n
    assert chunks < n, (chunks, n)


def test_batched_dead_core_does_not_attract_retries():
    """A persistently faulty core has zero inflight and all pages free, so
    naive least-loaded routing would keep feeding it its own retries until
    llm_retries is exhausted. Retried syscalls must avoid the core they
    faulted on: every syscall completes on the healthy core."""
    with make_kernel("batched", num_cores=2) as k:
        dead = k.pool.cores[1].engine

        def always_fail():
            raise ValueError("dead core")

        dead.serve_step = always_fail
        scs = [_llm(f"d{i}", max_new=6) for i in range(8)]
        for sc in scs:
            k.submit(sc)
        outs = [sc.join(timeout=300) for sc in scs]
    assert all(len(o["tokens"]) == 6 for o in outs)
    assert k.pool.cores[0].engine.stats["tokens"] > 0
