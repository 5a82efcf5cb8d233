"""End-to-end behaviour tests: the full AIOS stack (kernel + scheduler +
engine + SDK + agents) serving concurrent multi-framework agents, including
the memory-hierarchy spill path and the access-control surface."""
import threading

import numpy as np
import pytest

from repro.agents import FRAMEWORKS, register_builtin_tools
from repro.core import AIOSKernel
from repro.sdk import api
from repro.sdk.query import LLMQuery


@pytest.fixture(scope="module")
def kernel():
    k = AIOSKernel(arch="tiny", scheduler="batched", quantum=32,
                   engine_kw={"max_slots": 4, "max_len": 256})
    register_builtin_tools(k.tools)
    k.start()
    yield k
    k.stop()


TASKS = [
    {"kind": "math", "expression": "(3+4)*5", "expected": 35.0},
    {"kind": "convert", "amount": 100, "src": "USD", "dst": "EUR",
     "expected": 92.0},
    {"kind": "retrieve",
     "facts": ["the sky is blue", "paris is in france",
               "jax compiles with xla"],
     "query": "what does jax compile with", "needle_id": 2},
    {"kind": "code", "spec": "solve", "required": ["def ", "return"]},
]


@pytest.mark.parametrize("fw", list(FRAMEWORKS))
def test_framework_agents_end_to_end(kernel, fw):
    agent = FRAMEWORKS[fw](kernel, f"sys-{fw}", max_new_tokens=8)
    for task in TASKS:
        r = agent.run(task)
        assert r["success"] in (True, None), (fw, task["kind"], r)


def test_concurrent_agents_all_succeed(kernel):
    results = [None] * 8

    def one(i):
        fw = list(FRAMEWORKS)[i % len(FRAMEWORKS)]
        agent = FRAMEWORKS[fw](kernel, f"conc{i}", max_new_tokens=6)
        results[i] = agent.run(TASKS[i % 2])  # math/convert only

    ts = [threading.Thread(target=one, args=(i,)) for i in range(8)]
    [t.start() for t in ts]
    [t.join(timeout=300) for t in ts]
    assert all(r and r["success"] for r in results), results


def test_context_spill_to_disk_roundtrip():
    """Force the context manager's host pool to spill snapshots to storage
    (memory-hierarchy tier 3) and still resume exactly."""
    k = AIOSKernel(arch="tiny", scheduler="rr", quantum=4,
                   engine_kw={"max_slots": 2, "max_len": 128})
    register_builtin_tools(k.tools)
    k.context.pool.budget = 4096   # tiny host budget -> spill
    with k:
        scs = [LLMQuery(prompt=list(range(1, 9)),
                        max_new_tokens=16).to_syscall(f"sp{i}")
               for i in range(4)]
        for sc in scs:
            k.submit(sc)
        outs = [sc.join(timeout=300) for sc in scs]
    assert all(len(o["tokens"]) == 16 for o in outs)
    assert k.context.stats["spills"] > 0
    assert k.context.stats["disk_loads"] > 0
    # determinism across placements: same prompt -> same tokens
    assert outs[0]["tokens"] == outs[1]["tokens"] == outs[3]["tokens"]


def test_access_control_syscalls(kernel):
    r = api.check_access(kernel, "alice", sid="alice", tid="bob")
    assert not r["granted"]
    api.add_privilege(kernel, "bob", sid="alice", tid="bob")
    assert api.check_access(kernel, "alice", sid="alice", tid="bob")["granted"]
    # irreversible ops denied without an intervention callback
    assert not api.ask_permission(kernel, "alice", "delete")["approved"]


def test_storage_via_sdk(kernel):
    api.write_file(kernel, "w1", "notes/a.txt", "alpha beta gamma")
    api.write_file(kernel, "w1", "notes/a.txt", "alpha beta gamma delta")
    got = api.read_file(kernel, "w1", "notes/a.txt")
    assert got["content"].endswith("delta")
    api.rollback_file(kernel, "w1", "notes/a.txt", n=1)
    got = api.read_file(kernel, "w1", "notes/a.txt")
    assert got["content"] == "alpha beta gamma"
    link = api.share_file(kernel, "w1", "notes/a.txt")
    assert link["link"].startswith("aios://share/")


def test_memory_via_sdk(kernel):
    r = api.create_memory(kernel, "m1", "the moon orbits the earth")
    assert r["success"]
    hits = api.search_memories(kernel, "m1", "what orbits the earth",
                               k=1)["search_results"]
    assert hits and "moon" in hits[0]["content"]


@pytest.mark.parametrize("broken", [False, True], ids=["ok", "task_fails"])
def test_serve_launcher_exit_code(monkeypatch, broken):
    """`python -m repro.launch.serve` exits 0 only when every agent and
    every syscall succeeded."""
    from repro.agents import frameworks
    from repro.launch import serve

    if broken:
        monkeypatch.setattr(frameworks, "_check", lambda task, result: False)
    rc = serve.main(["--arch", "tiny", "--agents", "2", "--max-new", "2",
                     "--max-len", "128", "--scheduler", "batched"])
    assert rc == (1 if broken else 0)
