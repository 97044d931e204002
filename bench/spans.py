#!/usr/bin/env python3
"""The program's own spans in a profiler trace, and the per-layer numbers
they give.

    python3 bench/spans.py --workload qwen3b-qwen05b.batch-link20 \
        --seed 7 --seconds 51

The program writes ``dsd.*`` host spans (``repro.tracing``) into the
profiler's trace beside the benchmark's ``bench.*`` spans
(:mod:`bench.harness`), on the device events' clock. :mod:`bench.trace`
names each idle gap by the innermost ``bench.*`` span; this module reads
the same trace with the program's spans too:

- spans: every ``bench.*`` and ``dsd.*`` host span that starts inside the
  ``bench.window`` span, with its metadata (the event's stats, or the
  ``name#k=v,…#`` form if the profiler left them in the name);
- idle gaps: the stretches inside the window where the device ran
  nothing, found as :func:`bench.trace.reduce` finds them, each charged to
  the innermost span of either prefix around its midpoint (``bench.*``
  names lose their prefix, ``dsd.*`` names keep it; ``serve_loop`` where
  only the window covers it).

From these, in % of the window: idle under a ``dsd.link.*`` span (the
link), under ``dsd.serve.idle`` (waiting for an arrival), and the rest
(host work of the decode loop); the three sum to ``1 - busy / window``.
Also the share of the queue wait of requests admitted in the window
(due = ``dsd.serve`` start + the ``dsd.admit`` span's ``arrival_s``, to
that span's start) that overlaps a ``dsd.chunk``, and ``dsd.link.control``
spans per ``dsd.round``. A trace with no ``dsd.*`` span (a program
without them) gives none of these.

Run as a script, it serves one traced window of a cell as ``bench/run.py
--trace 1`` does (set-up, warm-up, no compile in the window), keeps the
trace, and prints one JSON line: these numbers, the committed tokens per
window-second and the program spans per second, and the idle breakdown.
It does not check the served tokens.

This is a measuring tool, not the reduction to build on: ``bench/run.py``
reduces the trace with :mod:`bench.trace` alone and reports none of these
numbers. Once :mod:`bench.trace` keeps span metadata and charges gaps to
``dsd.*`` spans itself (one loader, one innermost rule), the readers
become metric files over its ``Summary`` and ``load``, ``reduce``,
``charge`` and ``main`` here go.
"""

from __future__ import annotations

import dataclasses
import heapq
import re
import sys
from pathlib import Path

if __package__ in (None, ""):
    ROOT = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace  # noqa: E402

PROGRAM = "dsd."
WINDOW = trace.SPAN_PREFIX + "window"
_META = re.compile(r"^([^#]*)#(.*)#$")


@dataclasses.dataclass
class Span:
    name: str
    start_ns: float
    dur_ns: float
    meta: dict

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Programs:
    summary: trace.Summary    # the trace as bench.trace reduces it
    spans: list               # Span, started in the window, by start
    idle_gaps: dict           # span name -> seconds (per device mean)

    @property
    def window_s(self) -> float:
        return self.summary.window_s

    @property
    def busy_s(self) -> float:
        return self.summary.busy_s


def _value(text: str):
    for cast in (int, float):
        try:
            return cast(text)
        except ValueError:
            pass
    return text


def parse_name(name: str) -> tuple[str, dict]:
    """``dsd.admit#request_id=3,arrival_s=1.5#`` -> (``dsd.admit``,
    {request_id: 3, arrival_s: 1.5}); a name without ``#…#`` is itself."""
    m = _META.match(name)
    if not m:
        return name, {}
    meta = {}
    for item in m.group(2).split(","):
        if "=" in item:
            k, v = item.split("=", 1)
            meta[k] = _value(v)
    return m.group(1), meta


def _is_span(name: str) -> bool:
    return name.startswith(trace.SPAN_PREFIX) or name.startswith(PROGRAM)


def load(path: str) -> tuple[list, list]:
    """(planes as :func:`bench.trace.load` gives them, host spans of
    either prefix with their metadata)."""
    from jax.profiler import ProfileData
    planes, spans = [], []
    for p in ProfileData.from_file(path).planes:
        lines = {}
        host = not trace.DEVICE_PLANE.match(p.name)
        for ln in p.lines:
            evs = lines.setdefault(ln.name, [])
            for e in ln.events:
                start, dur = float(e.start_ns), float(e.duration_ns)
                evs.append(trace.Event(e.name, start, dur))
                if host and _is_span(e.name):
                    name, meta = parse_name(e.name)
                    meta.update(dict(e.stats))
                    spans.append(Span(name, start, dur, meta))
        planes.append(trace.Plane(p.name, lines))
    return planes, spans


def _label(name: str) -> str:
    if name.startswith(trace.SPAN_PREFIX):
        return name[len(trace.SPAN_PREFIX):]
    return name


def charge(gaps: list[tuple[float, float]], spans: list[Span]) -> dict:
    """Seconds of each gap (a, b) charged to the innermost span around
    its midpoint by :func:`bench.trace._innermost`'s rule: the shortest,
    and of equals the first listed. A sweep over the spans by start, as
    a trace holds tens of thousands of program spans."""
    out: dict = {}
    order = sorted(((s.start_ns, k) for k, s in enumerate(spans)
                    if s.name != WINDOW))
    heap: list = []
    i = 0
    for a, b in sorted(gaps, key=lambda g: g[0] + g[1]):
        t = (a + b) / 2
        while i < len(order) and order[i][0] <= t:
            k = order[i][1]
            heapq.heappush(heap, (spans[k].dur_ns, k))
            i += 1
        while heap and spans[heap[0][1]].end_ns < t:
            heapq.heappop(heap)     # ended before t, so before every later t
        name = _label(spans[heap[0][1]].name) if heap else "serve_loop"
        out[name] = out.get(name, 0.0) + (b - a) * 1e-9
    return out


def reduce(planes: list, spans: list[Span]) -> Programs:
    summary = trace.reduce(planes)    # busy and window as bench.trace has
    win = next(s for s in spans if s.name == WINDOW)
    lo, hi = win.start_ns, win.end_ns
    gaps, n_dev = [], 0
    for dev in planes:
        if not trace.DEVICE_PLANE.match(dev.name):
            continue
        ops = [(e.start_ns, e.end_ns) for e in dev.lines.get("XLA Ops", [])
               if e.end_ns > lo and e.start_ns < hi]
        if not ops:
            continue
        n_dev += 1
        iv = trace.union(trace.clip(ops, lo, hi))
        edges = [lo] + [t for ab in iv for t in ab] + [hi]
        gaps += [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    idle = {k: v / n_dev for k, v in charge(gaps, spans).items()}
    inside = sorted((s for s in spans if lo <= s.start_ns <= hi),
                    key=lambda s: s.start_ns)
    return Programs(summary=summary, spans=inside, idle_gaps=idle)


# -- the per-layer numbers ---------------------------------------------------

def _has_program_spans(p: Programs) -> bool:
    return any(s.name.startswith(PROGRAM) for s in p.spans)


def idle_share(p: Programs, prefix: str):
    """% of the window idle under spans whose name starts with
    ``prefix``."""
    if not _has_program_spans(p) or p.window_s <= 0:
        return None
    return 100.0 * sum(v for k, v in p.idle_gaps.items()
                       if k.startswith(prefix)) / p.window_s


def idle_link_share(p: Programs):
    return idle_share(p, PROGRAM + "link.")


def idle_arrival_share(p: Programs):
    return idle_share(p, PROGRAM + "serve.idle")


def idle_host_share(p: Programs):
    """% of the window idle neither on the link nor waiting for an
    arrival."""
    link, arrival = idle_link_share(p), idle_arrival_share(p)
    if link is None:
        return None
    return 100.0 * (1.0 - p.busy_s / p.window_s) - link - arrival


def queue_wait_chunk_share(p: Programs):
    """% of the summed queue wait of the window's admissions (due →
    ``dsd.admit`` start) that overlaps a ``dsd.chunk`` span."""
    serve = [s for s in p.spans if s.name == PROGRAM + "serve"]
    admits = [s for s in p.spans if s.name == PROGRAM + "admit"
              and "arrival_s" in s.meta]
    if not serve or not admits:
        return None
    t0 = serve[0].start_ns
    chunks = trace.union((s.start_ns, s.end_ns) for s in p.spans
                         if s.name == PROGRAM + "chunk")
    waited = overlap = 0.0
    for s in admits:
        due = t0 + float(s.meta["arrival_s"]) * 1e9
        if s.start_ns <= due:
            continue
        waited += s.start_ns - due
        overlap += sum(b - a for a, b in trace.clip(chunks, due,
                                                    s.start_ns))
    return 100.0 * overlap / waited if waited > 0 else None


def control_rtt_per_round(p: Programs):
    """``dsd.link.control`` spans per ``dsd.round`` span."""
    rounds = sum(1 for s in p.spans if s.name == PROGRAM + "round")
    if not rounds:
        return None
    return sum(1 for s in p.spans
               if s.name == PROGRAM + "link.control") / rounds


READERS = {
    "idle_link_share": idle_link_share,
    "idle_host_share": idle_host_share,
    "idle_arrival_share": idle_arrival_share,
    "queue_wait_chunk_share": queue_wait_chunk_share,
    "control_rtt_per_round": control_rtt_per_round,
}


def numbers(p: Programs) -> dict:
    """Every reading above that the trace gives, by name."""
    out = {k: f(p) for k, f in READERS.items()}
    return {k: v for k, v in out.items() if v is not None}


# -- one traced window of a cell -----------------------------------------------

def main(argv=None) -> int:
    import argparse
    import json
    import shutil
    import time

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from bench import harness, run, spec, work
    cell = spec.find_cell(args.workload)
    run.enable_compile_cache()
    devs = run.accelerator(cell.chips)
    built = harness.build(cell, args.seed, args.seconds)
    harness.warm_up(built, cell)
    trace_dir = run.OUT_DIR / "spans-trace"
    shutil.rmtree(trace_dir, ignore_errors=True)
    trace_dir.mkdir(parents=True)
    window = harness.run_window(built, cell, args.seconds, str(trace_dir))
    if window.compiles:
        raise SystemExit(f"spans: {window.compiles} XLA compile(s) inside "
                         f"the measured window")
    t_red = time.perf_counter()
    planes, spans = load(trace.find_xplane(str(trace_dir)))
    p = reduce(planes, spans)
    shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = run.Context(cell=cell, window=window, requests=built.requests,
                      setup_s=0.0, trace=p.summary,
                      work=work.chunks_work(cell.target, cell.draft,
                                            window.probe.chunks),
                      peaks=run.peaks_of(devs[0].device_kind))
    tokens_per_s = spec.metric_reader("tokens_per_s").read(ctx)
    program = [s for s in p.spans if s.name.startswith(PROGRAM)]
    out = {
        "workload": cell.name, "seed": args.seed,
        "device": {"kind": devs[0].device_kind, "count": len(devs)},
        "window_s": p.window_s, "busy_s": p.busy_s,
        "device_idle_share": 100.0 * (1.0 - p.busy_s / p.window_s),
        "traced_tokens_per_s": tokens_per_s,
        "program_spans_per_s": len(program) / p.window_s,
        "reduce_s": time.perf_counter() - t_red,
        **numbers(p),
        "per_layer": run.read_metrics(spec.cell_metrics(cell.name, True),
                                      ctx),
        "idle_gaps": trace.top(p.idle_gaps, 16),
        "modules": trace.top(ctx.trace.modules, 12),
    }
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
