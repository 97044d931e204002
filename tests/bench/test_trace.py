"""The reduction from a profiler trace to busy time, per-op and
per-program time and idle gaps named by host span."""

from pathlib import Path

import pytest

from bench import trace
from bench.trace import Event, Plane

FIXTURE = Path(__file__).parent / "data" / "v5e_fixture.xplane.pb"
MS = 1e6       # ns


def synthetic():
    """One device, a 100 ms window: an insert program 10-30 ms, a step
    program 40-90 ms with two ops, spans around them on the host."""
    dev = Plane("/device:TPU:0", {
        "XLA Modules": [Event("jit_insert(12)", 10 * MS, 20 * MS),
                        Event("jit_step(7)", 40 * MS, 50 * MS)],
        "XLA Ops": [Event("fusion.1", 10 * MS, 20 * MS),
                    Event("copy.2", 40 * MS, 30 * MS),
                    Event("fusion.3", 70 * MS, 20 * MS),
                    Event("outside", 150 * MS, 5 * MS)],
    })
    host = Plane("/host:CPU", {"python": [
        Event("bench.window", 0, 100 * MS),
        Event("bench.admit", 5 * MS, 30 * MS),
        Event("bench.run_chunk", 35 * MS, 60 * MS),
        Event("bench.decide", 35 * MS, 4 * MS),
        Event("other", 0, 100 * MS),
    ]})
    return [host, dev]


def test_busy_window_and_programs():
    s = trace.reduce(synthetic())
    assert s.window_s == pytest.approx(0.100)
    assert s.busy_s == pytest.approx(0.070)
    assert s.devices == 1
    assert s.modules == pytest.approx({"jit_insert": 0.020, "jit_step": 0.050})
    assert s.ops["jit_step/copy.2"] == pytest.approx(0.030)
    assert not any("outside" in k for k in s.ops)   # outside the window


def test_nested_ops_count_their_self_time():
    planes = synthetic()
    dev = planes[1]
    dev.lines["XLA Ops"].append(Event("%while.9 = (s32[], bf16[4]{0}) "
                                      "while(...)", 40 * MS, 50 * MS))
    s = trace.reduce(planes)
    assert s.busy_s == pytest.approx(0.070)
    assert s.ops["jit_step/while.9 s32[]..."] == pytest.approx(0.0)
    assert s.ops["jit_step/copy.2"] == pytest.approx(0.030)
    assert sum(s.ops.values()) == pytest.approx(s.busy_s)


def test_op_labels_keep_name_and_shape():
    assert trace.op_label("%copy.234 = bf16[24,28,2602,2,64]{4,3,2,1,0} "
                          "copy(bf16[24,28,2602,2,64]{4} %x)") == \
        "copy.234 bf16[24,28,2602,2,64]"
    assert trace.op_label("fusion.1") == "fusion.1"


def test_idle_gaps_are_named_by_the_innermost_host_span():
    s = trace.reduce(synthetic())
    # 0-10 ms: inside window only until admit starts at 5 -> midpoint 5
    # 30-40 ms: midpoint 35 lies in run_chunk and decide -> decide
    # 90-100 ms: midpoint 95 lies in run_chunk
    assert s.idle_gaps == pytest.approx({"admit": 0.010, "decide": 0.010,
                                         "run_chunk": 0.010})
    assert sum(s.idle_gaps.values()) == pytest.approx(s.window_s - s.busy_s)


def test_overlapping_ops_count_once():
    assert trace.union([(0, 5), (3, 8), (10, 12)]) == [(0, 8), (10, 12)]
    assert trace.clip([(0, 5), (8, 20)], 2, 10) == [(2, 5), (8, 10)]


def test_a_trace_without_the_window_or_device_is_refused():
    planes = synthetic()
    planes[0].lines["python"] = [e for e in planes[0].lines["python"]
                                 if e.name != "bench.window"]
    with pytest.raises(ValueError):
        trace.reduce(planes)
    with pytest.raises(ValueError):
        trace.reduce([synthetic()[0]])


def test_top_orders_by_time():
    assert trace.top({"a": 1.0, "b": 3.0, "c": 2.0}, 2) == [["b", 3.0],
                                                            ["c", 2.0]]


def test_recorded_v5e_trace():
    """A trace recorded on a TPU v5e by ``record_trace.py``: three
    ``insert`` calls and three chunks of four ``step`` calls, with host
    sleeps between them inside the window."""
    s = trace.reduce(trace.load(str(FIXTURE)))
    assert s.devices == 1
    assert 0 < s.busy_s < s.window_s
    assert {"jit_step", "jit_insert"} <= set(s.modules)
    assert s.modules["jit_step"] > s.modules["jit_insert"]
    assert sum(s.idle_gaps.values()) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-6)
    assert s.idle_gaps.get("serve_loop", 0) > 0.004   # the host sleeps
