"""The benchmark's reduction of profiler traces and span streams: busy
union, idle share, a kernel's device time and launches, gap attribution.
Needs no chip: synthetic events, and one trace recorded on a TPU v5e."""

from __future__ import annotations

import json
from pathlib import Path

import pytest

from bench import devtrace, spans

DATA = Path(__file__).resolve().parent / "data"
TPU0, TPU1 = "/device:TPU:0", "/device:TPU:1"
OPS, MODS = devtrace.OPS_LINE, devtrace.MODULES_LINE


def ev(plane, line, name, start, dur):
    return devtrace.Event(plane, line, name, float(start), float(dur))


def test_busy_union_idle_and_gaps():
    events = [
        ev(TPU0, OPS, "fusion.1", 10, 20),     # 10-30
        ev(TPU0, OPS, "fusion.2", 25, 15),     # 25-40, overlaps
        ev(TPU0, OPS, "copy", 60, 10),         # 60-70
        ev(TPU0, OPS, "late", 95, 20),         # clipped to 95-100
        ev(TPU0, OPS, "outside", 200, 5),      # outside the window
        ev("/host:CPU", "python", "bench.job", 0, 100),
    ]
    s = devtrace.reduce_events(events, 0, 100, chips=1)
    assert s.busy_ns == [30 + 10 + 5]
    assert s.busy_s == pytest.approx(45e-9)
    assert s.window_ns == 100
    assert s.gaps == [(0, 10), (40, 60), (70, 95)]
    assert s.ops == {"fusion.1": 20, "fusion.2": 15, "copy": 10, "late": 5}
    assert devtrace.top(s.ops, 2) == [["fusion.1", 20e-9],
                                      ["fusion.2", 15e-9]]


def test_op_names_drop_their_operands():
    text = ("%fusion.7 = s32[4194304]{0:T(1024)S(1)} fusion(s32[223655]{0:T"
            "(1024)S(1)} %copy-done), kind=kCustom")
    assert devtrace.op_name(text) == "%fusion.7 = s32[4194304]"
    s = devtrace.reduce_events([ev(TPU0, OPS, text, 0, 5),
                                ev(TPU0, OPS, text.replace("7", "8", 1), 5, 5)],
                               0, 10, chips=1)
    assert s.ops == {"%fusion.7 = s32[4194304]": 5,
                     "%fusion.8 = s32[4194304]": 5}


def test_busy_is_averaged_over_the_chips_of_the_cell():
    events = [ev(TPU0, OPS, "a", 0, 40), ev(TPU1, OPS, "a", 0, 20),
              ev("/device:TPU:0 SparseCore", OPS, "sc", 0, 100)]
    one = devtrace.reduce_events(events, 0, 100, chips=1)
    two = devtrace.reduce_events(events, 0, 100, chips=2)
    assert one.busy_ns == [40]
    assert two.busy_ns == [40, 20] and two.busy_s == pytest.approx(30e-9)


def test_kernel_time_and_launches_from_module_events():
    events = [ev(TPU0, MODS, "jit_kern(123)", 0, 7),
              ev(TPU0, MODS, "jit_kern(123)", 10, 9),
              ev(TPU0, MODS, "jit_other(5)", 20, 100),
              ev(TPU1, MODS, "jit_kern(123)", 0, 50)]
    s = devtrace.reduce_events(events, 0, 1000, chips=1)
    assert s.module_time("jit_kern") == (2, 16)
    assert s.module_time("jit_nothing") == (0, 0)


def test_no_device_plane_reads_nothing():
    s = devtrace.reduce_events([ev("/host:CPU", "python", "x", 0, 5)],
                               0, 10, chips=1)
    assert s.busy_ns == [] and s.gaps == [] and s.modules == {}


def _span(name, start, dur, tid=1):
    return spans.Span(name, 7, tid, float(start), float(dur))


def test_self_time_subtracts_direct_children():
    s = spans.nest([
        _span("bench.job", 0, 100),
        _span("sim.many", 10, 60),
        _span("sim.scan", 20, 30),
        _span("capture.walk", 75, 20),
        _span("sim.many", 0, 50, tid=2),       # another thread
    ])
    by = {(x.name, x.tid, x.start_us): x for x in s}
    assert by[("bench.job", 1, 0)].self_us == 100 - 60 - 20
    assert by[("sim.many", 1, 10)].self_us == 30
    assert by[("sim.scan", 1, 20)].self_us == 30
    assert spans.self_seconds(s, ("sim.",)) == pytest.approx(
        (30 + 30 + 50) / 1e6)
    assert spans.self_seconds(s, ("capture.",)) == pytest.approx(20e-6)


def test_gaps_are_named_by_the_innermost_open_span():
    s = spans.nest([_span("bench.job", 0, 100), _span("sim.many", 10, 60),
                    _span("sim.scan", 20, 30)])
    segs = spans.timeline(s)
    assert segs == [(0, 10, "bench.job"), (10, 20, "sim.many"),
                    (20, 50, "sim.scan"), (50, 70, "sim.many"),
                    (70, 100, "bench.job")]
    assert spans.attribute([5, 30, 60, 99, 150], segs) == [
        "bench.job", "sim.scan", "sim.many", "bench.job", None]


def test_read_spans_from_a_jsonl_stream(tmp_path):
    path = tmp_path / "obs.jsonl"
    lines = [{"ev": "span", "name": "bench.job", "pid": 1, "tid": 2,
              "ts": 1000, "dur": 500.0},
             {"ev": "counters", "pid": 1, "ts": 1200, "counters": {"a": 1}},
             {"ev": "span", "name": "sim.scan", "pid": 1, "tid": 2,
              "ts": 1100, "dur": 100.0}]
    path.write_text("".join(json.dumps(x) + "\n" for x in lines))
    s = spans.read_spans(path)
    assert [x.name for x in s] == ["bench.job", "sim.scan"]
    assert s[0].self_us == 400


def test_recorded_tpu_trace():
    """One simulation with ``scan="jax"`` traced on a TPU v5e: the scan
    kernel's executions are found, busy time is inside the window, and the
    host annotation that anchors the clocks is there."""
    events = devtrace.read_xplane(DATA / "small.xplane.pb")
    job = next(e for e in events if e.name == "bench.job")
    assert any(e.name == "bench.anchor" for e in events)
    s = devtrace.reduce_events(events, job.start_ns, job.end_ns, chips=1)
    launches, ns = s.module_time("jit_kern")
    assert launches >= 1 and 0 < ns <= s.window_ns
    assert 0 < s.busy_s * 1e9 <= s.window_ns
    assert s.gaps and all(a < b for a, b in s.gaps)
