"""The readers of the stage metrics (``bench/metrics/``): self time of one
named span and of the spans below it by name, per million references.
Hand-built span streams; nothing runs a cell."""

from __future__ import annotations

import pytest

from bench import manifest, spans
from bench.devtrace import DeviceStats
from bench.run import Context

# metric -> the span it reads
STAGES = {
    "sim.profile_s_per_mref": "sim.profile",
    "sim.scan_layout_s_per_mref": "sim.scan.layout",
    "sim.scan_wait_s_per_mref": "sim.scan.wait",
    "capture.emit_s_per_mref": "capture.walk.emit",
}
REFS = 2_000_000          # 2 Mref: a reading is seconds over 2


def sp(name, start_us, dur_us, tid=1):
    return spans.Span(name, 1, tid, float(start_us), float(dur_us))


def read(metric, span_list, refs=REFS):
    ctx = Context(refs, spans.nest(span_list),
                  DeviceStats(window_ns=1.0, chips=1))
    return manifest.load_reader(metric)(ctx)


def test_every_stage_metric_is_in_the_manifest():
    per_layer = {m["name"]: m for m in manifest.load_manifest()["per_layer"]}
    for metric in STAGES:
        m = per_layer[metric]
        assert m["source"] == "program_span"
        assert m["moves"] == "sim_refs_per_s"


@pytest.mark.parametrize("metric", sorted(STAGES))
def test_children_time_is_subtracted(metric):
    name = STAGES[metric]
    got = read(metric, [
        sp("bench.job", 0, 10_000_000),
        sp(name, 1_000_000, 3_000_000),          # 3 s ...
        sp("other.child", 1_500_000, 1_000_000),  # ... less 1 s inside
        sp(name, 6_000_000, 500_000, tid=2),     # 0.5 s on another thread
    ])
    assert got == pytest.approx((2.0 + 0.5) / 2)


@pytest.mark.parametrize("metric", sorted(STAGES))
def test_none_where_its_spans_are_absent(metric):
    parent = STAGES[metric].rsplit(".", 1)[0]
    assert read(metric, []) is None
    assert read(metric, [sp("bench.job", 0, 10), sp(parent, 1, 5)]) is None
    assert read(metric, [sp(STAGES[metric], 0, 10)], refs=0) is None


@pytest.mark.parametrize("metric", sorted(STAGES))
def test_a_lookalike_name_is_not_counted(metric):
    name = STAGES[metric]
    lookalikes = [sp(name + "r", 0, 4_000_000),       # e.g. sim.profiler
                  sp(name + "_x", 5_000_000, 4_000_000)]
    assert read(metric, lookalikes) is None
    got = read(metric, lookalikes + [sp(name, 10_000_000, 1_000_000)])
    assert got == pytest.approx(0.5)


def test_profile_counts_its_stages_and_its_own_self_time():
    """``sim.profile`` reads the whole build: its stages' self time and
    what the span holds outside them; the walk nested in it is the
    capture layer's, not the profile's."""
    got = read("sim.profile_s_per_mref", [
        sp("sim.profile", 0, 4_000_000),
        sp("sim.profile.collapse", 0, 1_000_000),
        sp("sim.profile.order", 1_000_000, 2_000_000),
        sp("capture.walk", 3_000_000, 500_000),
        sp("sim.profiler", 5_000_000, 9_000_000),
    ])
    assert got == pytest.approx(3.5 / 2)


def test_wait_nested_in_its_launch_reads_only_the_wait():
    got = read("sim.scan_wait_s_per_mref", [
        sp("sim.scan", 0, 5_000_000),
        sp("sim.scan.launch", 1_000_000, 3_000_000),
        sp("sim.scan.wait", 2_000_000, 1_500_000),
    ])
    assert got == pytest.approx(1.5 / 2)
