"""The program spans' reduction (``bench/spans.py``): idle gaps charged to
the innermost span of either prefix, the span metadata in both forms, and
the per-layer numbers read from the spans."""

from pathlib import Path

import pytest

from bench import spans, trace
from bench.spans import Span
from bench.trace import Event, Plane

FIXTURE = Path(__file__).parent / "data" / "v5e_spans.xplane.pb"
MS = 1e6       # ns


def synthetic():
    """One device, a 100 ms window. The program's spans nest in the
    benchmark's: an admission 5-25 ms (insert 10-20 ms), a chunk 28-80 ms
    of two rounds (verify 30-40 and 55-65 ms, each followed by a 10 ms
    control round trip), an arrival wait 85-100 ms."""
    dev = Plane("/device:TPU:0", {
        "XLA Modules": [Event("jit_insert(1)", 10 * MS, 10 * MS),
                        Event("jit_verify_commit(2)", 30 * MS, 10 * MS),
                        Event("jit_verify_commit(2)", 55 * MS, 10 * MS)],
        "XLA Ops": [Event("fusion.1", 10 * MS, 10 * MS),
                    Event("fusion.2", 30 * MS, 10 * MS),
                    Event("fusion.2", 55 * MS, 10 * MS)],
    })
    evs = [
        ("bench.window", 0, 100), ("dsd.serve", 0, 100),
        ("bench.admit", 4, 22),
        ("dsd.admit#request_id=0,arrival_s=0.001#", 5, 20),
        ("dsd.admit.wait", 10, 14),
        ("bench.run_chunk", 28, 52), ("dsd.chunk", 28, 52),
        ("dsd.round", 29, 22), ("dsd.verify", 30, 1),
        ("dsd.link.control", 41, 10),
        ("dsd.round", 54, 22), ("dsd.verify", 55, 1),
        ("dsd.link.control", 66, 10),
        ("dsd.serve.idle", 85, 15),
    ]
    events = [Event(n, a * MS, d * MS) for n, a, d in evs]
    host = Plane("/host:CPU", {"python": events})
    sps = []
    for e in events:
        name, meta = spans.parse_name(e.name)
        sps.append(Span(name, e.start_ns, e.dur_ns, meta))
    return [host, dev], sps


def test_metadata_in_the_name_and_in_stats():
    assert spans.parse_name("dsd.admit#request_id=3,arrival_s=1.5,"
                            "tag=abc#") == \
        ("dsd.admit", {"request_id": 3, "arrival_s": 1.5, "tag": "abc"})
    assert spans.parse_name("dsd.round") == ("dsd.round", {})
    assert spans.parse_name("bench.window") == ("bench.window", {})


def test_idle_goes_to_the_innermost_program_span():
    planes, sps = synthetic()
    p = spans.reduce(planes, sps)
    # 0-10 and 20-30: midpoints 5 and 25 lie in dsd.admit (5-25), the
    # shortest span around them
    # 40-55: midpoint 47.5 -> dsd.link.control (41-51) inside the round
    # 65-100: midpoint 82.5 -> only dsd.serve covers it
    assert p.idle_gaps == pytest.approx({
        "dsd.admit": 0.020, "dsd.link.control": 0.015,
        "dsd.serve": 0.035})
    assert sum(p.idle_gaps.values()) == pytest.approx(p.window_s - p.busy_s)
    # the benchmark's own reduction still names them by bench.* spans
    s = trace.reduce(planes)
    assert s.idle_gaps == pytest.approx({"admit": 0.020, "run_chunk": 0.015,
                                         "serve_loop": 0.035})
    assert (s.busy_s, s.window_s) == (p.busy_s, p.window_s)


def test_a_bench_span_inside_no_program_span_keeps_its_name():
    planes, sps = synthetic()
    sps = [s for s in sps if not s.name.startswith("dsd.")]
    p = spans.reduce(planes, sps)
    assert p.idle_gaps == pytest.approx(trace.reduce(planes).idle_gaps)
    assert spans.numbers(p) == {}      # a program without spans


def test_charge_picks_the_span_bench_trace_would():
    """Random nested and overlapping spans, ties of length included: the
    sweep charges each gap where :func:`bench.trace._innermost` does."""
    import random
    rng = random.Random(3)
    sps = [Span(f"dsd.s{k}", rng.randrange(0, 90) * MS,
                rng.choice((1, 2, 5, 10)) * MS, {}) for k in range(60)]
    events = [Event("bench." + s.name, s.start_ns, s.dur_ns) for s in sps]
    gaps = [(a * MS, a * MS + rng.choice((0.5, 1, 2)) * MS)
            for a in rng.sample(range(100), 40)]
    want: dict = {}
    for a, b in gaps:
        name = trace._innermost(events, (a + b) / 2)
        want[name] = want.get(name, 0.0) + (b - a) * 1e-9
    assert spans.charge(gaps, sps) == pytest.approx(want)


def test_shares_sum_to_the_idle_share():
    planes, sps = synthetic()
    sps.append(Span("dsd.serve.idle", 70 * MS, 20 * MS, {}))
    p = spans.reduce(planes, sps)
    n = spans.numbers(p)
    # 65-100 now splits at the midpoint 82.5 -> dsd.serve.idle (70-90)
    assert n["idle_arrival_share"] == pytest.approx(35.0)
    assert n["idle_link_share"] == pytest.approx(15.0)
    assert n["idle_host_share"] == pytest.approx(20.0)
    assert (n["idle_link_share"] + n["idle_host_share"]
            + n["idle_arrival_share"]) == pytest.approx(
        100.0 * (1 - p.busy_s / p.window_s))


def test_control_round_trips_per_round():
    planes, sps = synthetic()
    p = spans.reduce(planes, sps)
    assert spans.control_rtt_per_round(p) == pytest.approx(1.0)
    sps.append(Span("dsd.link.control", 90 * MS, 1 * MS, {}))
    assert spans.control_rtt_per_round(spans.reduce(planes, sps)) == \
        pytest.approx(1.5)


def test_queue_wait_overlapping_chunks():
    planes, sps = synthetic()
    # a second request due at 20 ms, admitted at 90 ms: of its 70 ms wait,
    # the chunk (28-80 ms) covers 52
    sps.append(Span("dsd.admit", 90 * MS, 5 * MS,
                    {"request_id": 1, "arrival_s": 0.020}))
    p = spans.reduce(planes, sps)
    # the first waited 4 ms (due 1, admitted 5), no chunk then
    assert spans.queue_wait_chunk_share(p) == pytest.approx(
        100.0 * 52 / (4 + 70))


@pytest.mark.filterwarnings("ignore:builtin type event_stats")
def test_recorded_v5e_trace_with_program_spans():
    """A trace recorded on a TPU v5e by ``record_span_trace.py``: three
    admissions and three chunks of four fused rounds, each round's
    control round trip sleeping 2 ms, and 2 ms arrival waits."""
    planes, sps = spans.load(str(FIXTURE))
    p = spans.reduce(planes, sps)
    names = {s.name for s in p.spans}
    assert {"dsd.serve", "dsd.admit", "dsd.chunk", "dsd.round",
            "dsd.link.control", "dsd.serve.idle", "bench.run_chunk"} <= names
    admits = sorted((s for s in p.spans if s.name == "dsd.admit"),
                    key=lambda s: s.start_ns)
    assert [s.meta["request_id"] for s in admits] == [0, 1, 2]
    assert [s.meta["arrival_s"] for s in admits] == pytest.approx(
        [0.0, 0.25, 0.5])
    commits = [s for s in p.spans if s.name == "dsd.chunk.commit"]
    assert sorted(s.meta["tokens"] for s in commits) == [4, 8, 12]
    n = spans.numbers(p)
    assert sum(p.idle_gaps.values()) == pytest.approx(
        p.window_s - p.busy_s, rel=1e-6)
    assert n["idle_link_share"] > 0 and n["idle_arrival_share"] > 0
    assert n["control_rtt_per_round"] == pytest.approx(1.0)
    assert (n["idle_link_share"] + n["idle_host_share"]
            + n["idle_arrival_share"]) == pytest.approx(
        100.0 * (1 - p.busy_s / p.window_s))
    # the program's spans split what the benchmark's reduction charges to
    # each of its own spans
    g, s = p.idle_gaps, trace.reduce(planes).idle_gaps
    assert s["run_chunk"] == pytest.approx(
        g["dsd.verdict.sync"] + g["dsd.chunk.sync"] + g["dsd.link.control"])
    assert s["admit"] == pytest.approx(
        sum(v for k, v in g.items() if k.startswith("dsd.admit")))
    assert s["serve_loop"] == pytest.approx(g["dsd.serve.idle"])
