"""Kernel-wide observability (repro.obs): syscall-lifecycle tracing with
exactly-once root spans, the unified metrics registry (legacy dict shape
preserved as a view + Prometheus text), the per-tick engine profiler, the
bounded audit/telemetry/trace rings, and tenant-namespaced storage paths."""
import glob
import json
import time
import urllib.request

import jax
import numpy as np
import pytest

from repro.control.telemetry import TelemetryBus
from repro.core import AIOSKernel
from repro.core.access import AccessManager
from repro.core.storage import StorageManager
from repro.core.syscall import LLMSyscall, StorageSyscall
from repro.configs import get_config
from repro.obs import (MetricsRegistry, TickProfiler, Tracer, serve_metrics,
                       span)
from repro.obs.trace import PID_ENGINE, PID_SPANS, PID_SYSCALLS
from repro.serving import engine as engine_mod
from repro.sdk.api import AgentSession
from repro.sdk.query import LLMQuery, StorageQuery

PROMPT = list(range(1, 9))


@pytest.fixture(scope="module")
def tkernel():
    """Tracing kernel: batched scheduler, 2 cores."""
    k = AIOSKernel(arch="tiny", scheduler="batched", quantum=16, num_cores=2,
                   trace=True, engine_kw={"max_slots": 4, "max_len": 128})
    k.start()
    yield k
    k.stop()


def _root_spans(tracer, pid):
    return [e for e in tracer.events()
            if e.get("name") == "syscall" and e.get("tid") == pid]


def _phase_spans(tracer, pid):
    return [e for e in tracer.events()
            if e.get("ph") == "X" and e.get("tid") == pid
            and e.get("pid") == PID_SYSCALLS and e["name"] != "syscall"]


def _wait_settled(sc, timeout=30):
    assert sc.event.wait(timeout), f"syscall pid={sc.pid} never settled"


# ---------------------------------------------------------------------------
# span-lifecycle invariants: exactly one root span per settle path
# ---------------------------------------------------------------------------
class TestSpanLifecycle:
    def test_complete_path_one_root_phases_tile(self, tkernel):
        s = AgentSession(tkernel, "span-ok", tenant="obs-t1")
        sc = s.submit(LLMQuery(prompt=PROMPT, max_new_tokens=6))
        sc.join(timeout=60)
        roots = _root_spans(tkernel.tracer, sc.pid)
        assert len(roots) == 1
        root = roots[0]
        assert root["args"]["status"] == "done"
        assert root["args"]["tenant"] == "obs-t1"
        # phases tile the root span exactly: no gaps, no overlap, and they
        # account for the full submit->settle wall time
        phases = sorted(_phase_spans(tkernel.tracer, sc.pid),
                        key=lambda e: e["ts"])
        assert [p["name"] for p in phases][:3] == ["submit", "admit", "queue"]
        assert phases[0]["ts"] == root["ts"]
        end = root["ts"] + root["dur"]
        for a, b in zip(phases, phases[1:]):
            assert abs((a["ts"] + a["dur"]) - b["ts"]) < 1e-6
        last = phases[-1]
        assert abs((last["ts"] + last["dur"]) - end) < 1e-6
        assert abs(sum(p["dur"] for p in phases) - root["dur"]) < 1e-3

    def test_quota_reject_path_closes_root(self, tkernel):
        tkernel.register_tenant("obs-reject", max_concurrent=0)
        s = AgentSession(tkernel, "span-rej", tenant="obs-reject")
        sc = s.submit(LLMQuery(prompt=PROMPT, max_new_tokens=4))
        with pytest.raises(RuntimeError, match="max_concurrent"):
            sc.join(timeout=10)
        roots = _root_spans(tkernel.tracer, sc.pid)
        assert len(roots) == 1
        assert roots[0]["args"]["status"] == "error"
        assert "max_concurrent" in roots[0]["args"]["error"]
        assert any(e["name"] == "quota_reject"
                   for e in tkernel.tracer.events() if e["tid"] == sc.pid)

    def test_unknown_op_fail_path_closes_root(self, tkernel):
        s = AgentSession(tkernel, "span-unknown")
        sc = s.submit(StorageQuery("sto_frobnicate"))
        r = sc.join(timeout=30)
        assert r["success"] is False
        assert len(_root_spans(tkernel.tracer, sc.pid)) == 1

    def test_timeout_cancel_path_closes_root(self, tkernel):
        s = AgentSession(tkernel, "span-cancel")
        sc = s.submit(LLMQuery(prompt=PROMPT, max_new_tokens=120))
        with pytest.raises(TimeoutError):
            sc.join(timeout=0.0)     # immediate timeout -> cooperative cancel
        _wait_settled(sc)            # scheduler observes the flag and fails
        deadline = time.time() + 10  # settle callbacks run synchronously,
        while not _root_spans(tkernel.tracer, sc.pid) \
                and time.time() < deadline:
            time.sleep(0.01)
        roots = _root_spans(tkernel.tracer, sc.pid)
        assert len(roots) == 1 and roots[0]["args"]["status"] == "error"
        assert any(e["name"] == "cancel_requested"
                   for e in tkernel.tracer.events() if e["tid"] == sc.pid)

    def test_mid_stream_cancel_closes_root(self, tkernel):
        s = AgentSession(tkernel, "span-stream")
        sc = s.submit(LLMQuery(prompt=PROMPT, max_new_tokens=100,
                               stream=True))
        for i, _tok in enumerate(sc.stream()):
            if i == 2:
                break                # abandoning the stream cancels
        _wait_settled(sc)
        deadline = time.time() + 10
        while not _root_spans(tkernel.tracer, sc.pid) \
                and time.time() < deadline:
            time.sleep(0.01)
        assert len(_root_spans(tkernel.tracer, sc.pid)) == 1
        assert any(e["name"] == "first_token"
                   for e in tkernel.tracer.events() if e["tid"] == sc.pid)

    def test_every_root_eventually_closes(self, tkernel):
        # global invariant across everything this module submitted so far
        deadline = time.time() + 15
        tr = tkernel.tracer
        while tr.roots_closed < tr.roots_opened and time.time() < deadline:
            time.sleep(0.05)
        assert tr.roots_opened == tr.roots_closed > 0

    def test_suspend_resume_requeues_single_root(self):
        """RR kernel with a tiny quantum: the syscall suspends/restores
        mid-decode (the same lifecycle a migration rides), emitting
        suspend instants and requeue->run phase pairs -- still exactly one
        root span on settle."""
        k = AIOSKernel(arch="tiny", scheduler="rr", quantum=4, trace=True,
                       engine_kw={"max_slots": 2, "max_len": 128})
        with k:
            s = AgentSession(k, "span-rr")
            sc = s.submit(LLMQuery(prompt=PROMPT, max_new_tokens=24))
            assert len(sc.join(timeout=120)["tokens"]) == 24
        evs = [e for e in k.tracer.events() if e.get("tid") == sc.pid]
        assert sum(1 for e in evs if e["name"] == "syscall") == 1
        assert sum(1 for e in evs if e["name"] == "suspend") >= 1
        runs = [e for e in evs if e["name"] == "run"]
        requeues = [e for e in evs if e["name"] == "requeue"]
        assert len(runs) >= 2 and len(requeues) >= 1

    def test_attach_is_idempotent(self):
        tr = Tracer()
        sc = LLMSyscall("a", {"prompt": [1], "max_new_tokens": 1})
        st1 = tr.attach(sc)
        st2 = tr.attach(sc)       # fault-retry resubmission path
        assert st1 is st2 and tr.roots_opened == 1
        sc.complete({"tokens": []})
        assert tr.roots_closed == 1
        sc.trace.finish(status="done")    # re-entry is a no-op
        assert tr.roots_closed == 1
        assert len(_root_spans(tr, sc.pid)) == 1


# ---------------------------------------------------------------------------
# chrome-trace export
# ---------------------------------------------------------------------------
class TestChromeTraceExport:
    def test_export_is_schema_valid_json(self, tkernel, tmp_path):
        path = tmp_path / "trace.json"
        n = tkernel.export_trace(str(path))
        with open(path) as f:
            doc = json.load(f)           # valid JSON or this raises
        evs = doc["traceEvents"]
        assert isinstance(evs, list) and len(evs) == n > 0
        for e in evs:
            assert e["ph"] in ("X", "i", "M"), e
            assert isinstance(e["name"], str)
            assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
            if e["ph"] == "X":
                assert e["ts"] >= 0 and e["dur"] >= 0
            elif e["ph"] == "i":
                assert e["ts"] >= 0 and e["s"] == "t"
        # lane metadata present so Perfetto shows subsystem/track names
        assert any(e["ph"] == "M" and e["name"] == "process_name"
                   for e in evs)
        assert any(e["ph"] == "M" and e["name"] == "thread_name"
                   for e in evs)
        # engine tick spans landed on the engine lane
        assert any(e["name"] == "tick" for e in evs)

    def test_ring_cap_drops_oldest_and_counts(self):
        tr = Tracer(cap=4)
        for i in range(10):
            tr.instant(f"e{i}", 1, 1)
        evs = tr.events()
        assert len(evs) == 4 and tr.dropped == 6
        assert [e["name"] for e in evs] == ["e6", "e7", "e8", "e9"]

    def test_disabled_tracer_emits_nothing(self):
        tr = Tracer(enabled=False)
        tr.instant("x", 1, 1)
        assert tr.events() == []


# ---------------------------------------------------------------------------
# serving-path spans: TraceAnnotations on the profiler's clock, mirrored
# into the tracer when one is given
# ---------------------------------------------------------------------------
def _profile(log_dir, fn):
    """Run ``fn`` inside a jax.profiler session; return the session's
    ``aios.*`` events as (line, name, start_ns, end_ns, stats) and the
    session's start on the realtime clock."""
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    pd = ProfileData.from_file(glob.glob(
        str(log_dir / "**" / "*.xplane.pb"), recursive=True)[0])
    evs, start = [], None
    for plane in pd.planes:
        stats = dict(plane.stats)
        if "profile_start_time" in stats:
            start = stats["profile_start_time"]
        for line in plane.lines:
            evs += [(line.name, e.name, e.start_ns, e.end_ns, dict(e.stats))
                    for e in line.events if e.name.startswith("aios.")]
    return evs, start


class TestServingSpans:
    def test_span_without_tracer_records_nothing_there(self):
        tr = Tracer()
        with span("aios.test", pid=7, rows=2) as sp:
            sp.set(tokens=3)
        assert tr.events() == []

    def test_span_with_tracer_records_one_x_event_and_nests(self):
        tr = Tracer()
        with span("aios.outer", tracer=tr, pid=7, rows=2) as sp:
            with span("aios.inner", tracer=tr):
                time.sleep(0.002)
            sp.set(tokens=3)
        xs = {e["name"]: e for e in tr.events() if e["ph"] == "X"}
        assert set(xs) == {"aios.outer", "aios.inner"}
        out, inn = xs["aios.outer"], xs["aios.inner"]
        assert out["args"] == {"pid": 7, "rows": 2, "tokens": 3}
        assert "args" not in inn
        assert (out["pid"], out["tid"]) == (inn["pid"], inn["tid"])
        assert out["pid"] == PID_SPANS
        assert out["ts"] <= inn["ts"]
        assert inn["ts"] + inn["dur"] <= out["ts"] + out["dur"]
        assert inn["dur"] >= 2000

    @pytest.mark.parametrize("prompt_len,kind", [(6, "decode"),
                                                 (40, "packed")])
    def test_engine_tick_span_starts_at_the_tick_t0(self, monkeypatch,
                                                    prompt_len, kind):
        tr = Tracer()
        eng = engine_mod.ServingEngine(
            get_config("tiny"), max_slots=2, max_len=64, tracer=tr,
            profiler=TickProfiler(), prefill_chunk_cap=16)
        slot = eng.add_sequence(np.arange(1, 7, dtype=np.int32), max_new=8)
        if kind == "packed":
            eng.add_sequence(np.arange(1, prompt_len + 1, dtype=np.int32),
                             max_new=2, eager=False)
        eng.serve_step()                      # compile outside the stamps
        stamps = []

        def clock(base=time.time_ns()):
            stamps.append(base + 1_000_000 * len(stamps))
            return stamps[-1]

        monkeypatch.setattr(engine_mod, "now_ns", clock)
        n = len(tr.events())
        eng.serve_step()
        assert eng.slots[slot].active
        tick = [e for e in tr.events()[n:] if e["name"] == "tick"]
        assert len(tick) == 1 and tick[0]["args"]["kind"] == kind
        assert tick[0]["pid"] == PID_ENGINE
        # one stamp on the tracer's clock, at t0; the duration is the
        # profiler's wall for the same tick
        assert len(stamps) == 1
        assert tick[0]["ts"] == pytest.approx(tr.us(stamps[0]))
        wall = eng.profiler._wall[(eng.profiler.n - 1) % eng.profiler.cap]
        assert tick[0]["dur"] == pytest.approx(wall * 1e6)
        step = [e for e in tr.events()[n:]
                if e["name"] == "aios.engine.serve_step"]
        assert len(step) == 1 and step[0]["args"]["kind"] == kind

    def test_kernel_spans_land_in_a_profiler_trace(self, tkernel, tmp_path):
        def serve_two():
            scs = [AgentSession(tkernel, f"xplane{i}").submit(
                LLMQuery(prompt=[40 + i] + PROMPT, max_new_tokens=5))
                for i in range(2)]
            for sc in scs:
                sc.join(timeout=60)

        evs, start = _profile(tmp_path, serve_two)
        names = {e[1] for e in evs}
        assert {"aios.engine.serve_step", "aios.core.finish",
                "aios.prefix.store", "aios.pagestore.put"} <= names
        finishes = [e for e in evs if e[1] == "aios.core.finish"]
        assert len(finishes) >= 2
        assert all("pid" in e[4] and "tokens" in e[4] for e in finishes)
        stores = [e for e in evs if e[1] == "aios.prefix.store"]
        for line, _, s, t, _ in (e for e in evs
                                 if e[1] == "aios.pagestore.put"):
            assert any(sl == line and ss <= s and t <= st
                       for sl, _, ss, st, _ in stores)
        # the tracer saw the same spans: after the fixed shift between its
        # origin and the session's start, each lines up with its own
        shift = tkernel.tracer.origin_ns - start
        mirrored = [shift + e["ts"] * 1e3 for e in tkernel.tracer.events()
                    if e["name"] == "aios.engine.serve_step"]
        for e in evs:
            if e[1] == "aios.engine.serve_step":
                assert min(abs(m - e[2]) for m in mirrored) < 1e6


# ---------------------------------------------------------------------------
# metrics registry: legacy view + flattening + prometheus text
# ---------------------------------------------------------------------------
class TestMetricsRegistry:
    def test_legacy_view_dict_equal_to_hand_assembled(self):
        k = AIOSKernel(arch="tiny", scheduler="batched", quantum=16,
                       engine_kw={"max_slots": 2, "max_len": 128})
        with k:
            AgentSession(k, "mv").llm_chat(PROMPT, max_new_tokens=4)
        expected = dict(k.scheduler.metrics())
        expected["context"] = dict(k.context.stats)
        if k.context.prefix_cache is not None:
            expected["prefix_cache"] = dict(k.context.prefix_cache.stats)
        expected["memory"] = dict(k.memory.stats)
        expected["tools"] = dict(k.tools.stats)
        expected["engine"] = [dict(c.engine.stats) for c in k.pool.cores]
        expected["access"] = k.access.metrics()
        if k.kv_store is not None:
            expected["kv_store"] = k.kv_store.metrics()
        expected["profiler"] = k.profiler_summary()
        assert k.metrics() == expected

    def test_typed_instruments(self):
        reg = MetricsRegistry()
        c = reg.counter("aios_test_total")
        c.inc(tenant="a")
        c.inc(2, tenant="a")
        c.inc(tenant="b")
        g = reg.gauge("aios_test_depth")
        g.set(7, core="0")
        h = reg.histogram("aios_test_wait_seconds")
        h.observe(0.003)
        h.observe(2.0)
        samples = {(n, tuple(sorted(lb.items()))): v
                   for n, lb, v, _k in reg.samples()}
        assert samples[("aios_test_total", (("tenant", "a"),))] == 3
        assert samples[("aios_test_total", (("tenant", "b"),))] == 1
        assert samples[("aios_test_depth", (("core", "0"),))] == 7
        assert samples[("aios_test_wait_seconds_count", ())] == 2
        assert samples[("aios_test_wait_seconds_sum", ())] == 2.003
        with pytest.raises(TypeError):
            reg.gauge("aios_test_total")    # kind mismatch

    def test_provider_flattening_labels(self):
        reg = MetricsRegistry()
        reg.register_provider("", lambda: {
            "completed": 5, "p50_wait_interactive": 0.01,
            "tenants": {"acme": {"usage": {"inflight": 2}}}})
        reg.register_provider("engine", lambda: [{"steps": 3}, {"steps": 9}])
        got = {(n, tuple(sorted(lb.items()))): v
               for n, lb, v, _k in reg.samples()}
        assert got[("aios_scheduler_completed", ())] == 5
        assert got[("aios_scheduler_wait_seconds",
                    (("quantile", "0.50"), ("slo_class", "interactive")))] \
            == 0.01
        # tenant sub-dicts become tenant= labels, not name parts
        assert got[("aios_scheduler_usage_inflight",
                    (("tenant", "acme"),))] == 2
        # list providers label entries core=i
        assert got[("aios_engine_steps", (("core", "0"),))] == 3
        assert got[("aios_engine_steps", (("core", "1"),))] == 9

    def test_gauge_func_and_prometheus_text(self):
        reg = MetricsRegistry()
        reg.gauge_func("aios_dropped_total", lambda: 42)
        reg.counter("aios_hits_total").inc(3, kind="packed")
        txt = reg.prometheus_text()
        assert "# TYPE aios_hits_total counter" in txt
        assert 'aios_hits_total{kind="packed"} 3' in txt
        assert "aios_dropped_total 42" in txt

    def test_http_endpoint_serves_scrape(self, tkernel):
        server = serve_metrics(tkernel.registry, 0)   # ephemeral port
        try:
            port = server.server_address[1]
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
                assert r.status == 200
                assert "text/plain" in r.headers["Content-Type"]
                body = r.read().decode()
            assert "aios_scheduler_completed" in body
            assert "aios_trace_events_dropped_total" in body
        finally:
            server.shutdown()


# ---------------------------------------------------------------------------
# per-tick engine profiler
# ---------------------------------------------------------------------------
class TestTickProfiler:
    def test_ring_and_summary(self):
        p = TickProfiler(cap=8)
        for i in range(20):
            p.record(1, 0.002 + i * 1e-5, 0.001, 4, 8, 16, 128, 40, 128)
        s = p.summary()
        assert s["ticks"] == 20 and s["window"] == 8
        pk = s["kinds"]["packed"]
        assert pk["ticks"] == 8
        assert 2.0 <= pk["p50_tick_ms"] <= pk["p90_tick_ms"] <= 2.3
        assert pk["mean_rows"] == 4.0
        assert pk["token_savings"] == pytest.approx(1 - 40 / 128)
        assert pk["mean_occupancy"] == pytest.approx(40 / 128)

    def test_kernel_profiler_summary_reflects_ticks(self, tkernel):
        AgentSession(tkernel, "prof").llm_chat(PROMPT, max_new_tokens=4)
        cores = tkernel.profiler_summary()
        assert len(cores) == 2
        active = [c for c in cores if c["ticks"] > 0]
        assert active
        assert all("p50_tick_ms" in c and "p90_tick_ms" in c for c in active)
        assert any("decode" in c["kinds"] or "padded" in c["kinds"]
                   or "packed" in c["kinds"] for c in active)

    def test_profile_off_means_no_recorder(self):
        k = AIOSKernel(arch="tiny", profile=False,
                       engine_kw={"max_slots": 2, "max_len": 64})
        assert all(c.engine.profiler is None for c in k.pool.cores)
        assert "profiler" not in k.metrics()


# ---------------------------------------------------------------------------
# bounded rings: audit log + telemetry bus
# ---------------------------------------------------------------------------
class TestBoundedRings:
    def test_audit_log_ring_drops_and_counts(self):
        am = AccessManager(audit_log_cap=4)
        for i in range(10):
            am.check_access(f"a{i}", f"a{i}")
        assert len(am.audit_log) == 4
        assert am.audit_dropped == 6
        assert am.metrics()["audit_dropped"] == 6
        assert am.metrics()["audit_entries"] == 4
        # newest entries survive
        assert [e["source"] for e in am.audit_log] \
            == ["a6", "a7", "a8", "a9"]

    def test_telemetry_event_window_drop_counter(self):
        bus = TelemetryBus(1, window=4)
        for i in range(10):
            bus.record("wait", float(i))
        assert bus.series("wait") == [6.0, 7.0, 8.0, 9.0]
        assert bus.counters["events_dropped"] == 6

    def test_telemetry_series_cap(self):
        bus = TelemetryBus(1, max_series=2)
        bus.record("wait", 1.0, "interactive")
        bus.record("wait", 1.0, "batch")
        bus.record("wait", 1.0, "best_effort")   # over cap: dropped
        assert bus.counters["series_dropped"] == 1
        assert bus.series("wait", "best_effort") == []
        assert bus.series("wait", "interactive") == [1.0]

    def test_drop_counters_exported_in_registry(self, tkernel):
        names = {n for n, *_ in tkernel.registry.samples()}
        assert "aios_audit_dropped_total" in names
        assert "aios_trace_events_dropped_total" in names


# ---------------------------------------------------------------------------
# tenant-namespaced storage paths
# ---------------------------------------------------------------------------
class TestTenantStorage:
    def test_same_path_isolated_per_tenant(self, tkernel):
        a = AgentSession(tkernel, "w", tenant="sto-acme")
        b = AgentSession(tkernel, "w", tenant="sto-bravo")
        a.write_file("common/name.txt", "from acme")
        b.write_file("common/name.txt", "from bravo")
        assert a.read_file("common/name.txt")["content"] == "from acme"
        assert b.read_file("common/name.txt")["content"] == "from bravo"

    def test_paths_land_under_tenant_prefix(self, tkernel, tmp_path):
        import os
        s = AgentSession(tkernel, "w", tenant="sto-tree")
        s.write_file("dir/leaf.txt", "x")
        assert os.path.isfile(os.path.join(
            tkernel.root_dir, "tenants", "sto-tree", "dir", "leaf.txt"))

    def test_collections_namespaced_per_tenant(self, tkernel):
        a = AgentSession(tkernel, "w", tenant="vec-one")
        b = AgentSession(tkernel, "w", tenant="vec-two")
        a.write_file("k/doc.txt", "quantum computing qubits",
                     collection="kb")
        b.write_file("k/doc.txt", "cooking pasta tomatoes", collection="kb")
        ra = a.retrieve_file("kb", "quantum qubits", k=1)["results"]
        rb = b.retrieve_file("kb", "quantum qubits", k=1)["results"]
        assert ra and ra[0]["score"] > 0.5
        assert not rb or rb[0]["score"] < 0.5   # bravo's kb has no quantum

    def test_legacy_root_files_migrate_on_first_touch(self, tmp_path):
        sm = StorageManager(str(tmp_path))
        # a pre-namespacing root: files written at the top level, with
        # version history
        sm.sto_write("old/report.txt", "v1")
        sm.sto_write("old/report.txt", "v2")
        sc = StorageQuery("sto_read", {"file_path": "old/report.txt"}) \
            .to_syscall("agent", tenant_id="legacy-t")
        assert isinstance(sc, StorageSyscall)
        r = sm.execute_storage_syscall(sc)
        assert r["success"] and r["content"] == "v2"
        assert sm.stats["legacy_migrations"] == 1
        # the version history moved with the file: rollback still works
        rb = sm.execute_storage_syscall(
            StorageQuery("sto_rollback", {"file_path": "old/report.txt"})
            .to_syscall("agent", tenant_id="legacy-t"))
        assert rb["success"]
        r2 = sm.execute_storage_syscall(
            StorageQuery("sto_read", {"file_path": "old/report.txt"})
            .to_syscall("agent", tenant_id="legacy-t"))
        assert r2["content"] == "v1"
        # second touch is NOT a migration
        assert sm.stats["legacy_migrations"] == 1

    def test_target_tenant_namespaces_into_target_tree(self, tkernel):
        owner = AgentSession(tkernel, "owner", tenant="sto-share")
        owner.write_file("shared.txt", "secret")
        reader = AgentSession(tkernel, "reader", tenant="sto-share")
        denied = reader.read_file("shared.txt", target_agent="owner")
        assert not denied["success"]
        owner.add_privilege("reader", "owner")
        ok = reader.read_file("shared.txt", target_agent="owner",
                              target_tenant="sto-share")
        assert ok["success"] and ok["content"] == "secret"

    def test_sdk_usage_surface(self, tkernel):
        tkernel.register_tenant("sdk-usage", max_concurrent=4)
        s = AgentSession(tkernel, "u", tenant="sdk-usage")
        s.llm_chat(PROMPT, max_new_tokens=4)
        u = s.usage()
        assert u["admitted"] >= 1 and u["inflight"] == 0
