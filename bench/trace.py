"""Reduce a profiler trace to device busy time, per-op and per-program
device time, and idle gaps named by what the host was doing.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
A device plane is named ``/device:TPU:<n>``; its ``XLA Ops`` line holds
one event per executed HLO op and its ``XLA Modules`` line one per
program execution (``jit_step(…)``). The benchmark's host spans
(``bench.window``, ``bench.admit``, ``bench.run_chunk``, ``bench.retire``,
``bench.decide``) sit on a host plane's thread lines, on the same clock.

- busy: the union of a device's op intervals inside the ``bench.window``
  span, averaged over the devices that ran anything;
- ops: each op's self time (a ``while`` op's event encloses its body's
  ops on the same line; their time is theirs), summed by
  ``<module>/<op name> <result shape>``;
- modules: the ``XLA Modules`` executions' time inside the window, by
  program name;
- idle gaps: every stretch inside the window where the device ran
  nothing, charged to the innermost host span around its midpoint
  (``serve_loop`` where only the window span covers it).
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Plane:
    name: str
    lines: dict            # line name -> list[Event]


@dataclasses.dataclass
class Summary:
    busy_s: float
    window_s: float
    devices: int
    ops: dict              # "<module>/<op>" -> seconds (per device mean)
    modules: dict          # module name -> seconds (per device mean)
    idle_gaps: dict        # host span name -> seconds (per device mean)


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> list[Plane]:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for p in data.planes:
        lines = {}
        for ln in p.lines:
            evs = [Event(e.name, float(e.start_ns), float(e.duration_ns))
                   for e in ln.events]
            lines.setdefault(ln.name, []).extend(evs)
        planes.append(Plane(p.name, lines))
    return planes


def union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def host_spans(planes: list[Plane]) -> list[Event]:
    return [e for p in planes if not DEVICE_PLANE.match(p.name)
            for evs in p.lines.values() for e in evs
            if e.name.startswith(SPAN_PREFIX)]


def _module_name(name: str) -> str:
    return name.split("(", 1)[0]


_HLO = re.compile(r"^%?([\w.\-]+) = (\(?[a-z0-9]+\[[0-9,]*\])")


def op_label(text: str) -> str:
    """``%copy.3 = bf16[2,4]{1,0} copy(...)`` -> ``copy.3 bf16[2,4]``."""
    m = _HLO.match(text)
    if not m:
        return text[:80]
    shape = m.group(2)
    return f"{m.group(1)} {shape.lstrip('(')}{'...' if shape[0] == '(' else ''}"


def self_times(events: list[Event]) -> list[tuple[Event, float]]:
    """Each event with its duration less that of the events it encloses
    directly (events on one line nest, they do not cross)."""
    out: list[list] = []
    stack: list[list] = []
    for e in sorted(events, key=lambda e: (e.start_ns, -e.dur_ns)):
        while stack and stack[-1][0].end_ns <= e.start_ns:
            stack.pop()
        item = [e, e.dur_ns]
        if stack:
            stack[-1][1] -= e.dur_ns
        stack.append(item)
        out.append(item)
    return [(e, max(0.0, t)) for e, t in out]


def _innermost(spans: list[Event], t: float) -> str:
    best = None
    for s in spans:
        if s.start_ns <= t <= s.end_ns and s.name != SPAN_PREFIX + "window":
            if best is None or s.dur_ns < best.dur_ns:
                best = s
    return best.name[len(SPAN_PREFIX):] if best else "serve_loop"


def reduce(planes: list[Plane]) -> Summary:
    spans = host_spans(planes)
    wins = [s for s in spans if s.name == SPAN_PREFIX + "window"]
    if not wins:
        raise ValueError("the trace has no bench.window span")
    lo, hi = wins[0].start_ns, wins[0].end_ns
    devices = [p for p in planes if DEVICE_PLANE.match(p.name)]
    busy, ops, modules, gaps, n_dev = 0.0, defaultdict(float), \
        defaultdict(float), defaultdict(float), 0
    for dev in devices:
        op_evs = [e for e in dev.lines.get("XLA Ops", [])
                  if e.end_ns > lo and e.start_ns < hi]
        if not op_evs:
            continue
        n_dev += 1
        mods = sorted(dev.lines.get("XLA Modules", []),
                      key=lambda e: e.start_ns)
        iv = union(clip([(e.start_ns, e.end_ns) for e in op_evs], lo, hi))
        busy += sum(b - a for a, b in iv)
        starts = [m.start_ns for m in mods]
        for e, t in self_times(op_evs):
            i = bisect.bisect_right(starts, e.start_ns) - 1
            mod = (_module_name(mods[i].name)
                   if i >= 0 and mods[i].end_ns >= e.start_ns else "?")
            ops[f"{mod}/{op_label(e.name)}"] += t * 1e-9
        for m in mods:
            a, b = max(m.start_ns, lo), min(m.end_ns, hi)
            if b > a:
                modules[_module_name(m.name)] += (b - a) * 1e-9
        edges = [lo] + [t for ab in iv for t in ab] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps[_innermost(spans, (a + b) / 2)] += (b - a) * 1e-9
    if n_dev == 0:
        raise ValueError("no device ran an operation inside the window")

    def mean(d):
        return {k: v / n_dev for k, v in d.items()}

    return Summary(busy_s=busy * 1e-9 / n_dev, window_s=(hi - lo) * 1e-9,
                   devices=n_dev, ops=mean(ops), modules=mean(modules),
                   idle_gaps=mean(gaps))


def top(d: dict, n: int = 10) -> list[list]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
