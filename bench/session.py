#!/usr/bin/env python3
"""Several passes of one cell's traffic in one process, for what does not
need a process each: the sweep of offered load that fixes an open-loop
mix's rate, and the correctness readings over many seeds with the int8
control beside them.

    python3 bench/session.py --workload <name> --seeds 1,2,3 --seconds 50 \
        [--rate R[,R...]] [--control 1]

Each pass boots the cell afresh from its seed (weights, kernel, warm-up;
programs compiled by an earlier pass in the process are reused) through
``harness.serve`` and prints one JSON line. ``--rate`` overrides the mix's
``rate_per_s``, one pass per rate and seed, and skips the reference: the
line gives the offered and served load and whether the queue grew over the
window. ``--control 1`` reads the reference over the served sample and
judges both the program's tokens and the tokens the int8 control puts
first with ``harness.judge``, the comparison a run makes. It needs a TPU,
like run_cell.py.
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


def load_line(h, served, seconds):
    """Offered and served load in the window, and whether the queue grew:
    TTFT in each half of the window and requests unanswered at its end."""
    w, reqs = served.window, served.requests
    half = w.start + seconds / 2
    first = h.Window(w.start, half, 0, 0)
    second = h.Window(half, w.end, 0, 0)
    due = h.in_window(reqs, w)
    return {
        "due": len(due),
        "tokens_per_s": h.tokens_in(reqs, w) / seconds,
        "ttft_p90_ms": h.percentile(h.ttft_ms(reqs, w), 90),
        "ttft_p50_ms": h.percentile(h.ttft_ms(reqs, w), 50),
        "ttft_p90_first_half_ms": h.percentile(h.ttft_ms(reqs, first), 90),
        "ttft_p90_second_half_ms": h.percentile(h.ttft_ms(reqs, second), 90),
        "itl_p95_ms": h.percentile(h.itl_ms(reqs, w), 95),
        "unanswered_at_end": sum(1 for r in due if not r.stamps
                                 or r.stamps[0][0] >= w.end),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rate", default="")
    ap.add_argument("--control", type=int, default=0)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from bench import harness as h

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        log(f"session: needs a TPU, JAX found {dev.platform}")
        return 2
    rates = [float(r) for r in args.rate.split(",") if r] or [None]
    for rate in rates:
        for seed in [int(s) for s in args.seeds.split(",")]:
            spec = h.cell_spec(args.workload)
            if rate is not None:
                spec.traffic["rate_per_s"] = rate
            t0 = time.perf_counter()
            served = h.serve(spec, seed, args.seconds, False, t0, log)
            out = {"seed": seed, "rate": spec.traffic.get("rate_per_s"),
                   "setup_s": served.setup_s,
                   "compile_events_in_window": served.compiles_in_window,
                   "memory_peak_bytes": served.memory_peak_bytes,
                   "load": load_line(h, served, args.seconds)}
            if rate is None:
                t1 = time.perf_counter()
                gaps = h.reference_gaps(spec, served, seed, log,
                                        quant=bool(args.control))
                out["reference_s"] = time.perf_counter() - t1
                out["gaps"] = gaps
                out["correct"] = h.judge(gaps["widest_gap"], gaps["tokens"],
                                         spec.limits)[0]
                if args.control:
                    out["control_correct"] = h.judge(
                        gaps["control_gap"], gaps["tokens"], spec.limits)[0]
            out["run_s"] = time.perf_counter() - t0
            print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
