"""Serving launcher: boot the AIOS kernel over an architecture and run an
agent workload (production entry point; the CPU-host path runs the tiny
config end-to-end through exactly the same kernel/scheduler/engine code that
the dry-run compiles for the 512-chip mesh).

  PYTHONPATH=src python -m repro.launch.serve --arch tiny --agents 16 \
      --scheduler rr --quantum 16

Observability flags: ``--trace-out pool.json`` boots the kernel with
syscall tracing and writes a Chrome-trace/Perfetto JSON on exit;
``--metrics-port 9100`` serves the metrics registry in Prometheus text
format (GET any path) for the run's duration; ``--metrics-out m.prom``
dumps one final scrape to a file.
"""
from __future__ import annotations

import argparse
import json
import time


def run_workload(*, arch="tiny", scheduler="rr", quantum=16, num_cores=1,
                 agents=8, max_new=16, max_slots=8, max_len=256,
                 frameworks=None, trace_out=None, metrics_port=None,
                 metrics_out=None, log=print):
    from repro.agents import FRAMEWORKS, register_builtin_tools
    from repro.core import AIOSKernel
    from repro.obs import serve_metrics

    kernel = AIOSKernel(arch=arch, scheduler=scheduler, quantum=quantum,
                        num_cores=num_cores, trace=bool(trace_out),
                        engine_kw={"max_slots": max_slots, "max_len": max_len})
    register_builtin_tools(kernel.tools)
    metrics_server = None
    if metrics_port is not None:
        metrics_server = serve_metrics(kernel.registry, metrics_port)
        log(f"# metrics: http://localhost:"
            f"{metrics_server.server_address[1]}/metrics")
    fw_names = frameworks or list(FRAMEWORKS)
    tasks = [
        {"kind": "math", "expression": f"({i}+4)*5", "expected": (i + 4) * 5.0}
        for i in range(agents)
    ]
    results = []
    with kernel:
        import threading
        t0 = time.time()

        def one(i):
            cls = FRAMEWORKS[fw_names[i % len(fw_names)]]
            agent = cls(kernel, f"agent{i}", max_new_tokens=max_new)
            results.append(agent.run(tasks[i]))

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(agents)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.time() - t0
        m = kernel.metrics()
    if trace_out:
        n = kernel.export_trace(trace_out)
        log(f"# trace: {n} events -> {trace_out} (open in ui.perfetto.dev)")
    if metrics_out:
        with open(metrics_out, "w") as f:
            f.write(kernel.registry.prometheus_text())
        log(f"# metrics snapshot -> {metrics_out}")
    if metrics_server is not None:
        metrics_server.shutdown()
    sr = sum(1 for r in results if r.get("success")) / max(len(results), 1)
    # an agent fails when its run raised (no result), its task check failed
    # (success False; None marks a framework without the API), or any of
    # the syscalls the kernel settled did not finish as done
    failed_agents = (agents - len(results)) + sum(
        1 for r in results if r.get("success") is False)
    failed_syscalls = len(kernel.scheduler.completed) - m["completed"]
    out = {"agents": agents, "seconds": round(dt, 2),
           "success_rate": sr, "failed_agents": failed_agents,
           "failed_syscalls": failed_syscalls,
           "completed_syscalls": m["completed"],
           "avg_wait_s": round(m["avg_wait"], 4),
           "p90_wait_s": round(m["p90_wait"], 4),
           "throughput_syscalls_per_s": round(m["completed"] / dt, 2)}
    log(json.dumps(out, indent=1))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tiny")
    ap.add_argument("--scheduler", default="rr",
                    choices=("fifo", "rr", "priority", "batched"))
    ap.add_argument("--quantum", type=int, default=16)
    ap.add_argument("--num-cores", type=int, default=1)
    ap.add_argument("--agents", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-slots", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome-trace/Perfetto JSON here on exit "
                         "(boots the kernel with trace=True)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve Prometheus text metrics on this port "
                         "(0 = ephemeral) for the run's duration")
    ap.add_argument("--metrics-out", default=None,
                    help="write one final Prometheus text scrape here")
    args = ap.parse_args(argv)
    out = run_workload(**{k.replace("-", "_"): v
                          for k, v in vars(args).items()})
    return 1 if out["failed_agents"] or out["failed_syscalls"] else 0


if __name__ == "__main__":
    raise SystemExit(main())
