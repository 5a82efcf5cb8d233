#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run_cell.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Boots ``AIOSKernel`` on the cell's configuration with weights made on the
device from ``--seed``, warms up every program shape the cell's traffic
reaches, drives the traffic for a lead-in and then for ``--seconds``
measured seconds, and checks a sample of the served tokens against a plain
float32 reference. With ``--trace 0`` it prints the cell's end-to-end
metrics; with ``--trace 1`` it traces part of the window and prints the
per-layer metrics. The last line on standard output is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``checks``: each number compared
beside its limit); the last lines on standard error repeat the checks.

It exits 2, printing no result, when JAX finds no TPU or fewer chips than
the cell asks for, when the repository's ``src`` is missing, or when the
device has no entry in ``bench/peaks.json``; 1 when the run fails.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # the compile cache lives in the checkout, at a path that never moves
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        log(f"run_cell: no repository source at {src}")
        return 2
    for p in (src, ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import harness
    from bench.peaks import peaks_for

    spec = harness.cell_spec(args.workload)
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        log(f"run_cell: needs a TPU, JAX found {dev.platform}")
        return 2
    if len(devs) < spec.chips:
        log(f"run_cell: the cell needs {spec.chips} chips, JAX found "
            f"{len(devs)}")
        return 2
    try:
        peaks = peaks_for(dev.device_kind)
    except KeyError as e:
        log(f"run_cell: {e}")
        return 2

    res = harness.run_cell(spec, args.seed, args.seconds, bool(args.trace),
                           T_START, log, peaks=peaks)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    if args.trace:
        device["busy_s"] = res.get("busy_s")
        device["window_s"] = res.get("window_s")
    out = {"correct": res["correct"], "attempted": res["attempted"],
           "failed": res["failed"], "metrics": res["metrics"],
           "device": device}
    if "breakdown" in res:
        out["breakdown"] = res["breakdown"]
    out["compile_events_in_window"] = res["compile_events_in_window"]
    out["checks"] = res["checks"]
    for k, c in res["checks"].items():
        log(f"check {k}: {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
