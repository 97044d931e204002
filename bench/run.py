#!/usr/bin/env python3
"""One run of one benchmark cell on the chip it is started on.

    python3 bench/run.py --workload qwen3b-qwen05b.code --seed 7 \
        --seconds 51 --trace 0

Reads the cell from ``BENCHMARK.json`` (its configuration, traffic mix and
metrics, each found by name; see :mod:`bench.spec`), makes the weights and
requests from ``--seed``, builds the deployment and warms up its shapes
(all of that is ``setup_s``), serves the window through
``SpecDecodeServer.run`` (no compile may happen inside it), then frees the
program's state and compares a sample of the served tokens with the plain
reference (:mod:`bench.check`). ``--control fp8`` puts the control, the
reference at weight-only fp8, in the program's place in that comparison
(a run of the control comes out not correct); benchmark runs leave it
off.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` traces
the window with the profiler and reports its per-layer metrics, the
device's busy and window seconds and a breakdown. Every number compared
for ``correct`` is printed with its limit as the last lines on standard
error and under ``checks``, the last key of the result. The result is the
last line of standard output. Without an accelerator, or with fewer chips
than the cell asks for, the run exits non-zero and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

CACHE_DIR = ROOT / ".jax_cache"
OUT_DIR = ROOT / ".bench_out"


def say(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class Context:
    """What a metric reader may read."""
    cell: object
    window: object
    requests: list
    setup_s: float
    trace: object             # bench.trace.Summary, or None
    work: object              # bench.work.Work of the window's passes
    peaks: dict

    def decode_device_s(self):
        if self.trace is None:
            return None
        return sum(s for m, s in self.trace.modules.items()
                   if "insert" not in m)


def enable_compile_cache() -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def accelerator(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform not in ("tpu", "gpu"):
        raise SystemExit(f"bench: needs an accelerator, JAX found "
                         f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs


def peaks_of(kind: str) -> dict:
    table = json.loads((ROOT / "bench" / "peaks.json").read_text())
    if kind not in table:
        raise SystemExit(f"bench: no peaks for device kind {kind!r}; "
                         f"known: {sorted(table)}")
    return table[kind]


def read_metrics(entries, ctx) -> dict:
    from bench.spec import metric_reader
    out = {}
    for m in entries:
        value = metric_reader(m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(cell, seed: int, seconds: float, trace_on: bool, devs,
             peaks: dict, control: str = "none", gaps_to=None) -> dict:
    """One run of ``cell`` on ``devs``: set-up, window, metrics, check
    (of the control in the program's place, with ``control``; the
    per-token gaps read are saved to ``gaps_to`` if given). Returns the
    result line's object."""
    import numpy as np

    from bench import check, harness, spec, trace, work
    from repro.analysis.sanitize import persistent_cache_hits
    entries = spec.cell_metrics(cell.name, trace_on)
    dev = devs[0]
    hits0 = persistent_cache_hits()

    built = harness.build(cell, seed, seconds)
    t_built = time.perf_counter() - T_START
    harness.warm_up(built, cell)
    setup_s = time.perf_counter() - T_START
    say(f"set-up {setup_s:.3f} s (weights + deployment {t_built:.3f} s), "
        f"compile-cache hits {persistent_cache_hits() - hits0}")
    reqs = built.requests
    due = max(r.arrival_s for r in reqs)
    say(f"generator: {len(reqs)} requests ({cell.traffic['loop']}), "
        f"{sum(len(r.prompt) for r in reqs)} prompt and "
        f"{sum(r.max_new_tokens for r in reqs)} output tokens, due over "
        f"{due:.3f} s of the serve loop's own clock; each is timed from "
        f"its due time")

    trace_dir = None
    if trace_on:
        trace_dir = OUT_DIR / "trace"
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    window = harness.run_window(built, cell, seconds,
                                str(trace_dir) if trace_dir else None)
    if window.compiles:
        raise SystemExit(f"bench: {window.compiles} XLA compile(s) inside "
                         f"the measured window")
    due_at = {r.request_id: window.t0 + r.arrival_s for r in reqs}
    late = [t0 - due_at[rid] for rid, t0, _ in window.probe.admits]
    say(f"generator lateness: the schedule runs on the serve loop's own "
        f"clock; the earliest any request's admission began after its due "
        f"time is {min(late, default=float('nan')):.6f} s")
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))

    summary = None
    if trace_dir is not None:
        summary = trace.reduce(trace.load(trace.find_xplane(str(trace_dir))))
        shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = Context(cell=cell, window=window, requests=reqs, setup_s=setup_s,
                  trace=summary,
                  work=work.chunks_work(cell.target, cell.draft,
                                        window.probe.chunks),
                  peaks=peaks)
    metrics = read_metrics(entries, ctx)
    passes = sum(c.passes for c in window.probe.chunks)
    say(f"window {window.t1 - window.t0:.3f} s, {len(window.results)} "
        f"retired, {len(window.in_flight)} in flight, {passes} target "
        f"passes, {len(window.probe.admits)} admissions")

    t_pad = built.max_prompt + built.max_new
    n_pad = built.max_new
    harness.free(built)
    del built
    t_ref = time.perf_counter()
    numbers, attempted, failed, gaps = check.checks(
        cell, window, reqs, seed, t_pad, n_pad, control)
    say(f"reference check {time.perf_counter() - t_ref:.3f} s")
    for side, g in gaps.items():
        say(f"{side} gaps over {len(g)} tokens: mean {float(g.mean())!r}, "
            f"max {float(g.max())!r}, share above 0 "
            f"{float((g > 0).mean())!r}" if len(g) else
            f"{side} gaps: none read")
    if gaps_to is not None:
        gaps_to.parent.mkdir(exist_ok=True)
        np.savez(gaps_to, **gaps)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": check.passed(numbers), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": trace.top(summary.ops),
                               "idle_gaps": trace.top(summary.idle_gaps)}
    result["checks"] = numbers
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("none", "fp8"), default="none",
                    help="compare the fp8 control in the program's place")
    args = ap.parse_args(argv)

    from bench import spec
    cell = spec.find_cell(args.workload)
    enable_compile_cache()
    devs = accelerator(cell.chips)
    peaks = peaks_of(devs[0].device_kind)
    gaps_to = None if args.control == "none" else \
        OUT_DIR / f"gaps-{cell.name}-{args.seed}.npz"
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), devs,
                      peaks, args.control, gaps_to)
    for name, c in result["checks"].items():
        say(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
