#!/usr/bin/env python3
"""Find an open-loop cell's knee once, by a sweep on the chip.

    python3 bench/sweep.py --workload qwen3b-qwen05b.code --seed 3 \
        --seconds 51 --rates 1.5 2 2.5 3

Builds the cell once and serves one window per rate (the mix's lengths,
stratified gaps at that rate). For each rate it prints one JSON line:
requests, TTFT and queue-wait percentiles, the mean queue wait of the
first, middle and last third of the arrivals, and the drain: how long
after the last arrival the last request retired. The first third holds
the start from an empty server; the knee is the highest rate whose last
third waits no more than 20% longer than its middle third (no growth
once the start has passed). The cell's mix file then fixes its rate at
about four fifths of it. Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def sweep(cell, seed: int, seconds: float, rates: list[float]) -> list:
    import dataclasses

    import numpy as np

    from bench import harness, traffic
    top = dict(cell.traffic, rate_per_s=max(rates))
    built = harness.build(dataclasses.replace(cell, traffic=top), seed,
                          seconds)
    harness.warm_up(built, cell)
    rows = []
    for rate in rates:
        mix = dict(cell.traffic, rate_per_s=rate)
        built.requests = traffic.generate(mix, seed, seconds,
                                          cell.target.vocab)
        w = harness.run_window(built, dataclasses.replace(cell, traffic=mix),
                               seconds)
        res = sorted(w.results, key=lambda r: r.request_id)
        q = [r.queue_ms for r in res]
        third = max(1, len(q) // 3)
        last_due = w.t0 + max(r.arrival_s for r in built.requests)
        rows.append({
            "rate_per_s": rate, "requests": len(built.requests),
            "retired": len(res), "window_s": w.t1 - w.t0,
            "compiles": w.compiles,
            "ttft_p50_ms": float(np.percentile([r.ttft_ms for r in res], 50)),
            "ttft_p90_ms": float(np.percentile([r.ttft_ms for r in res], 90)),
            "ttft_p95_ms": float(np.percentile([r.ttft_ms for r in res], 95)),
            "tpot_p95_ms": float(np.percentile(
                [r.tpot_ms for r in res if len(r.tokens) > 1], 95)),
            "queue_first_third_ms": float(np.mean(q[:third])),
            "queue_middle_third_ms": float(np.mean(q[third:-third])),
            "queue_last_third_ms": float(np.mean(q[-third:])),
            "drain_s": w.t1 - last_due,
            "queue_p95_ms": float(np.percentile(q, 95)),
        })
        print(json.dumps(rows[-1]), flush=True)
    return rows


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    sys.argv = sys.argv[:1]
    import run  # noqa: F401  (sets the compile cache like a run)
    from bench import spec
    cell = spec.find_cell(args.workload)
    run.enable_compile_cache()
    run.accelerator(cell.chips)
    sweep(cell, args.seed, args.seconds, args.rates)
    return 0


if __name__ == "__main__":
    sys.exit(main())
