"""Tests for ``repro.obs``: spans, counters, exporters, and the CLIs.

Covers the tentpole guarantees one by one: the disabled span path is a
shared no-op singleton that allocates nothing that survives the
statement; span events carry pid/tid/ts/dur and nest correctly, on one
clock, mirrored onto the JAX profiler's host plane; counter
flushes are *deltas* so multi-process streams sum; child processes
inherit the sink through ``REPRO_TRACE`` and merge into the same file;
the counters emitted by the simulator hot paths match hand counts on a
tiny batch; and the report/Chrome exporters round-trip the schema.
"""

import gc
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro import obs
from repro.obs.report import (
    aggregate,
    aggregate_events,
    format_report,
    load_events,
    to_chrome,
)


@pytest.fixture(autouse=True)
def _isolated_obs(monkeypatch):
    """Each test starts with tracing off, counters zeroed, env clean."""
    monkeypatch.delenv(obs.ENV_VAR, raising=False)
    obs.disable()
    obs.reset_counters()
    yield
    obs.disable()
    obs.reset_counters()


def _events(path) -> list[dict]:
    return [json.loads(line) for line in
            Path(path).read_text().splitlines() if line.strip()]


# --------------------------------------------------------------------------
# Spans
# --------------------------------------------------------------------------
class TestSpan:
    def test_disabled_span_is_shared_singleton(self):
        assert not obs.enabled()
        s1 = obs.span("a")
        s2 = obs.span("b", depth=3, note="x")
        assert s1 is s2  # one module-level no-op object, reused verbatim

    def test_span_event_schema(self, tmp_path):
        trace = tmp_path / "t.jsonl"
        obs.enable(trace)
        with obs.span("work.unit", depth=2, kind="test"):
            pass
        obs.disable()
        (ev,) = _events(trace)
        assert ev["ev"] == "span" and ev["name"] == "work.unit"
        assert ev["pid"] == os.getpid()
        assert isinstance(ev["tid"], int)
        assert isinstance(ev["ts"], int) and ev["ts"] > 10**15  # us epoch
        assert ev["dur"] >= 0.0
        assert ev["tags"] == {"depth": 2, "kind": "test"}

    def test_nesting_order_and_containment(self, tmp_path):
        trace = tmp_path / "t.jsonl"
        obs.enable(trace)
        with obs.span("outer"):
            with obs.span("inner"):
                pass
        obs.disable()
        inner, outer = _events(trace)  # events are written on __exit__
        assert inner["name"] == "inner" and outer["name"] == "outer"
        assert inner["dur"] <= outer["dur"]
        assert inner["ts"] >= outer["ts"]

    def test_exception_recorded_and_propagated(self, tmp_path):
        trace = tmp_path / "t.jsonl"
        obs.enable(trace)
        with pytest.raises(ValueError):
            with obs.span("boom"):
                raise ValueError("no")
        obs.disable()
        (ev,) = _events(trace)
        assert ev["error"] == "ValueError"

    def test_nonscalar_tags_coerced_to_str(self, tmp_path):
        trace = tmp_path / "t.jsonl"
        obs.enable(trace)
        with obs.span("t", shape=(4, 2), ok=True, none=None):
            pass
        obs.disable()
        (ev,) = _events(trace)
        assert ev["tags"] == {"shape": "(4, 2)", "ok": True, "none": None}

    def test_traced_decorator_toggles_per_call(self, tmp_path):
        @obs.traced("deco.fn", kind="t")
        def f(x):
            return x + 1

        assert f(1) == 2  # disabled: plain call, no sink needed
        trace = tmp_path / "t.jsonl"
        obs.enable(trace)
        assert f(2) == 3
        obs.disable()
        (ev,) = _events(trace)
        assert ev["name"] == "deco.fn" and ev["tags"] == {"kind": "t"}
        assert f(3) == 4  # off again: still works

    def test_traced_defaults_to_qualname(self, tmp_path):
        @obs.traced()
        def g():
            return 7

        trace = tmp_path / "t.jsonl"
        obs.enable(trace)
        assert g() == 7
        obs.disable()
        (ev,) = _events(trace)
        assert ev["name"].endswith("g")


class TestEnableDisable:
    def test_enable_exports_env_disable_clears(self, tmp_path):
        trace = tmp_path / "t.jsonl"
        obs.enable(trace)
        assert obs.enabled() and obs.trace_path() == str(trace)
        assert os.environ[obs.ENV_VAR] == str(trace)
        obs.disable()
        assert not obs.enabled() and obs.trace_path() is None
        assert obs.ENV_VAR not in os.environ

    def test_enable_same_path_is_idempotent(self, tmp_path):
        trace = tmp_path / "t.jsonl"
        obs.enable(trace)
        with obs.span("a"):
            pass
        obs.enable(trace)  # no reopen, no truncation
        with obs.span("b"):
            pass
        obs.disable()
        assert [e["name"] for e in _events(trace)] == ["a", "b"]

    def test_enable_new_path_switches_sink(self, tmp_path):
        t1, t2 = tmp_path / "t1.jsonl", tmp_path / "t2.jsonl"
        obs.enable(t1)
        with obs.span("first"):
            pass
        obs.enable(t2)
        with obs.span("second"):
            pass
        obs.disable()
        assert [e["name"] for e in _events(t1)
                if e["ev"] == "span"] == ["first"]
        assert [e["name"] for e in _events(t2)
                if e["ev"] == "span"] == ["second"]

    def test_unopenable_env_path_never_breaks_import(self, tmp_path,
                                                     monkeypatch, capsys):
        # a directory cannot be opened for append: trace off, run on
        monkeypatch.setenv(obs.ENV_VAR, str(tmp_path))
        obs._init_from_env()
        assert not obs.enabled()
        assert "cannot open trace file" in capsys.readouterr().err


# --------------------------------------------------------------------------
# Counters
# --------------------------------------------------------------------------
class TestCounters:
    def test_count_accumulates_and_resets(self):
        obs.count("x")
        obs.count("x", 2)
        obs.count("y", 0.5)
        assert obs.counters() == {"x": 3, "y": 0.5}
        obs.reset_counters()
        assert obs.counters() == {}

    def test_flush_writes_deltas_not_cumulative(self, tmp_path):
        trace = tmp_path / "t.jsonl"
        obs.enable(trace)
        obs.count("a", 2)
        obs.flush()
        obs.count("a", 3)
        obs.flush()
        obs.flush()  # nothing new: no third event
        obs.disable()
        evs = [e for e in _events(trace) if e["ev"] == "counters"]
        assert [e["counters"]["a"] for e in evs] == [2, 3]
        # the aggregate recovers the cumulative value by summing deltas
        assert aggregate([trace]).counter("a") == 5

    def test_disable_flushes_pending_counters(self, tmp_path):
        trace = tmp_path / "t.jsonl"
        obs.enable(trace)
        obs.count("pending", 4)
        obs.disable()  # implicit final flush
        assert aggregate([trace]).counter("pending") == 4

    def test_flush_is_noop_when_disabled(self):
        obs.count("z", 9)
        obs.flush()  # no sink: must not raise
        assert obs.counters()["z"] == 9

    def test_warn_once_per_key(self, capsys):
        obs.warn_once("k1-test-obs", "first message")
        obs.warn_once("k1-test-obs", "repeat suppressed")
        obs.warn_once("k2-test-obs", "second key")
        err = capsys.readouterr().err
        assert err.count("first message") == 1
        assert "repeat suppressed" not in err
        assert "second key" in err


# --------------------------------------------------------------------------
# Zero-overhead-when-off pin
# --------------------------------------------------------------------------
class TestDisabledPathCost:
    def test_disabled_span_site_leaks_zero_allocations(self):
        """10k disabled span sites must not grow the live-block count.

        This is the structural form of the 'zero overhead when off'
        promise: the no-op singleton means nothing a disabled call site
        allocates survives the statement.
        """
        assert not obs.enabled()

        def site():
            with obs.span("hot.loop", depth=1):
                pass

        for _ in range(100):  # warm up allocator caches / bytecode
            site()
        gc.collect()
        before = sys.getallocatedblocks()
        for _ in range(10_000):
            site()
        after = sys.getallocatedblocks()
        assert after - before <= 16  # interpreter noise only


# --------------------------------------------------------------------------
# One clock, mirrored onto the JAX profiler
# --------------------------------------------------------------------------
class _CountingAnnotation:
    """Stand-in for ``jax.profiler.TraceAnnotation`` that counts."""

    made = 0

    def __init__(self, name, **kwargs):
        type(self).made += 1

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def _stage_work():
    """One contested-distance scan on the jax backend and one vectorized
    grid walk that emits addresses: both run stage-span sites."""
    import numpy as np

    from repro.capture.grid import _walk
    from repro.core import cachesim_vec
    from repro.kernels.flash_attention import capture as flash_capture

    # 64 lines over 4 sets of 2 ways: nearly every revisit is contested
    lines = np.random.default_rng(0).integers(0, 64, 5_000)
    sets = 4
    prof = cachesim_vec.StreamProfile(lines)
    revisit = np.flatnonzero(~prof.cold)
    sd = cachesim_vec._contested_sd(prof.cl, prof.cl % sets, prof.prev,
                                    revisit, sets, cap=2, skip_below=1,
                                    scan="jax")
    res = _walk(flash_capture.capture(sq=1024, sk=1024, d=64),
                count_only=False, bases=None)
    return sd, res.addresses


class TestProfilerClock:
    def test_spans_mirrored_onto_the_profiler_host_plane(self, tmp_path):
        import time

        jax = pytest.importorskip("jax")
        trace = tmp_path / "t.jsonl"
        obs.enable(trace)
        jax.profiler.start_trace(str(tmp_path / "xplane"))
        try:
            with obs.span("mirror.outer"):
                time.sleep(0.002)
                with obs.span("mirror.inner"):
                    time.sleep(0.003)
                time.sleep(0.002)
        finally:
            jax.profiler.stop_trace()
            obs.disable()
        got = {e["name"]: e for e in _events(trace)}
        data = jax.profiler.ProfileData.from_file(
            str(next((tmp_path / "xplane").rglob("*.xplane.pb"))))
        seen = {}
        for plane in data.planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in got:
                        seen[ev.name] = (ev.start_ns, ev.duration_ns)
        assert set(seen) == {"mirror.outer", "mirror.inner"}
        for name, (_, dur_ns) in seen.items():
            dur_ns_obs = got[name]["dur"] * 1e3
            assert abs(dur_ns - dur_ns_obs) <= max(0.05 * dur_ns_obs, 50e3)
        (o0, od), (i0, idur) = seen["mirror.outer"], seen["mirror.inner"]
        assert o0 <= i0 and i0 + idur <= o0 + od

    def test_child_never_ends_past_its_parent(self, tmp_path):
        from _obs_spans import bounds

        trace = tmp_path / "t.jsonl"
        obs.enable(trace)
        for _ in range(200):
            with obs.span("clock.parent"):
                with obs.span("clock.child"):
                    pass
        obs.disable()
        evs = _events(trace)
        for child, par in zip(evs[::2], evs[1::2]):
            assert (child["name"], par["name"]) == ("clock.child",
                                                    "clock.parent")
            (c0, c1), (p0, p1) = bounds(child), bounds(par)
            assert p0 <= c0 <= c1 <= p1

    def test_off_path_makes_no_annotation_and_no_event(self, tmp_path,
                                                      monkeypatch):
        jax = pytest.importorskip("jax")
        monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                            _CountingAnnotation)
        _CountingAnnotation.made = 0
        assert not obs.enabled()
        assert obs.span("sim.scan.layout") is obs._NULL_SPAN
        sd_off, addr_off = _stage_work()
        assert _CountingAnnotation.made == 0
        assert list(tmp_path.iterdir()) == []

        # the same work traced: the stub is what the mirror enters, and
        # the answers do not change
        trace = tmp_path / "t.jsonl"
        obs.enable(trace)
        sd_on, addr_on = _stage_work()
        obs.disable()
        names = {e["name"] for e in _events(trace) if e["ev"] == "span"}
        assert {"sim.scan.layout", "sim.scan.launch", "sim.scan.wait",
                "capture.walk.schedule", "capture.walk.emit"} <= names
        assert _CountingAnnotation.made == len(
            [e for e in _events(trace) if e["ev"] == "span"])
        assert sd_on.tobytes() == sd_off.tobytes()
        assert addr_on.tobytes() == addr_off.tobytes()


# --------------------------------------------------------------------------
# Cross-process merge
# --------------------------------------------------------------------------
class TestCrossProcess:
    def test_child_inherits_sink_via_env(self, tmp_path):
        trace = tmp_path / "merged.jsonl"
        obs.enable(trace)
        child = ("from repro import obs\n"
                 "with obs.span('child.work'):\n"
                 "    pass\n"
                 "obs.count('child.counter', 7)\n"
                 "obs.flush()\n")
        env = dict(os.environ)
        src = str(Path(obs.__file__).resolve().parents[2])
        env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
        with obs.span("parent.work"):
            subprocess.run([sys.executable, "-c", child], env=env,
                           check=True, timeout=120)
        obs.disable()
        rep = aggregate([trace])
        assert len(rep.pids) >= 2  # parent + child merged into one stream
        assert rep.spans["child.work"].count == 1
        assert rep.spans["parent.work"].count == 1
        assert rep.counter("child.counter") == 7


# --------------------------------------------------------------------------
# Counter accuracy: hand counts on a tiny simulate_batch
# --------------------------------------------------------------------------
class TestHotPathCounters:
    def test_memo_and_profile_counters_match_hand_count(self):
        from repro.core import cachesim, cachesim_vec, tracegen

        w = tracegen.make_suite(refs=2_000)[0]
        addr = w.trace(4).addresses.copy()  # fresh identity: memo miss
        cfg = cachesim.host_config(4)       # 3 levels: L1 -> L2 -> L3
        obs.reset_counters()

        cachesim_vec.simulate_batch(addr, [cfg])
        c = obs.counters()
        assert c["memo.miss"] == 1 and "memo.hit" not in c
        # one StreamProfile scan per unique geometry, one per level
        assert c["profile.geom"] == 3 == c["profile.scan"]
        assert c["node.compute"] == 3 and "node.reuse" not in c

        obs.reset_counters()
        cachesim_vec.simulate_batch(addr, [cfg])  # identical rerun
        c = obs.counters()
        assert c["memo.hit"] == 1 and "memo.miss" not in c
        assert c["node.reuse"] == 3 and "node.compute" not in c
        assert "profile.scan" not in c  # nothing re-scanned

    def test_scan_invariant_profile_scan_bounded_by_geom(self):
        """The CI gate's cold-run invariant, at unit scale: every
        StreamProfile construction covers at least one unique geometry —
        segmented scans cover several at once, so scan <= geom."""
        from repro.core import cachesim, cachesim_vec, tracegen

        w = tracegen.make_suite(refs=2_000)[1]
        addr = w.trace(4).addresses.copy()
        cfgs = [cachesim.host_config(4), cachesim.ndp_config(4),
                cachesim.host_config(4, prefetcher=True)]
        obs.reset_counters()
        cachesim_vec.simulate_batch(addr, cfgs)
        c = obs.counters()
        assert 0 < c["profile.scan"] <= c["profile.geom"]
        # the two LLC variants behind the host-L2 and pf-L2 miss streams
        # share one segmented scan, so here the bound is strict
        assert c["profile.scan"] < c["profile.geom"]
        assert c.get("profile.segments", 0) >= 2


# --------------------------------------------------------------------------
# Report aggregation + Chrome export
# --------------------------------------------------------------------------
def _span_ev(name, ts, dur, pid=1, tid=1):
    return {"ev": "span", "name": name, "pid": pid, "tid": tid,
            "ts": ts, "dur": dur}


class TestReport:
    def test_aggregate_stats_and_wall(self):
        events = [
            _span_ev("a", 1_000_000, 2_000_000),
            _span_ev("a", 2_000_000, 4_000_000),
            _span_ev("b", 3_000_000, 1_000_000, pid=2),
            {"ev": "counters", "pid": 1, "ts": 0, "counters": {"x": 2}},
            {"ev": "counters", "pid": 2, "ts": 0, "counters": {"x": 3.5}},
        ]
        rep = aggregate_events(events)
        a = rep.spans["a"]
        assert a.count == 2 and a.total_s == 6.0
        assert a.min_s == 2.0 and a.max_s == 4.0 and a.mean_s == 3.0
        assert rep.span_total("b") == 1.0 and rep.span_total("nope") == 0.0
        # wall = [min ts, max ts+dur] = [1s, 6s]
        assert rep.wall_s == pytest.approx(5.0)
        assert rep.counter("x") == 5.5
        assert rep.pids == {1, 2} and rep.events == 5

    def test_corrupt_lines_skipped_and_counted(self, tmp_path):
        trace = tmp_path / "t.jsonl"
        trace.write_text(
            json.dumps(_span_ev("ok", 0, 1000)) + "\n"
            + '{"ev": "span", "name": "trunca'       # killed mid-write
            + "\n[1, 2, 3]\n"                        # not an object
            + '{"no_ev_key": 1}\n')
        events, skipped = load_events([trace])
        assert len(events) == 1 and skipped == 3
        rep = aggregate([trace])
        assert rep.skipped_lines == 3 and rep.spans["ok"].count == 1
        assert "3 corrupt line(s) skipped" in format_report(rep)

    def test_multiple_files_merge(self, tmp_path):
        t1, t2 = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
        t1.write_text(json.dumps(_span_ev("s", 0, 1000, pid=1)) + "\n")
        t2.write_text(json.dumps(_span_ev("s", 500, 1000, pid=2)) + "\n")
        rep = aggregate([t1, t2])
        assert rep.spans["s"].count == 2 and rep.pids == {1, 2}

    def test_format_report_table(self):
        rep = aggregate_events([
            _span_ev("alpha", 0, 2_000_000),
            {"ev": "counters", "pid": 1, "ts": 0,
             "counters": {"hits": 42, "busy_s": 1.25}},
        ])
        text = format_report(rep)
        assert "alpha" in text and "hits" in text
        assert "42" in text and "1.25" in text
        assert "wall 2.000s" in text

    def test_to_dict_round_trips_through_json(self):
        rep = aggregate_events([_span_ev("a", 0, 1_500_000),
                                {"ev": "counters", "pid": 1, "ts": 0,
                                 "counters": {"k": 3}}])
        d = json.loads(json.dumps(rep.to_dict()))
        assert d["spans"]["a"]["count"] == 1
        assert d["spans"]["a"]["total_seconds"] == 1.5
        assert d["counters"]["k"] == 3
        assert d["wall_seconds"] == 1.5


class TestChromeExport:
    def test_span_events_become_complete_events(self):
        out = to_chrome([_span_ev("a", 10, 20, pid=3, tid=4)])
        assert out["displayTimeUnit"] == "ms"
        (ev,) = out["traceEvents"]
        assert ev == {"name": "a", "ph": "X", "ts": 10.0, "dur": 20.0,
                      "pid": 3, "tid": 4, "args": {}}

    def test_counter_deltas_become_cumulative_samples(self):
        out = to_chrome([
            {"ev": "counters", "pid": 1, "ts": 10, "counters": {"c": 2}},
            {"ev": "counters", "pid": 1, "ts": 20, "counters": {"c": 3}},
        ])
        samples = [e for e in out["traceEvents"] if e["ph"] == "C"]
        assert [s["args"]["value"] for s in samples] == [2, 5]

    def test_malformed_events_are_dropped(self):
        out = to_chrome([{"ev": "span", "name": "x"},  # no ts/dur
                         _span_ev("ok", 0, 1)])
        assert [e["name"] for e in out["traceEvents"]] == ["ok"]


# --------------------------------------------------------------------------
# CLIs: python -m repro.obs, and --trace wiring on a real pipeline
# --------------------------------------------------------------------------
class TestCLI:
    def test_report_and_chrome_subcommands(self, tmp_path, capsys):
        from repro.obs.__main__ import main

        trace = tmp_path / "t.jsonl"
        obs.enable(trace)
        with obs.span("stage.one"):
            pass
        obs.count("n", 3)
        obs.disable()

        assert main(["report", str(trace)]) == 0
        out = capsys.readouterr().out
        assert "stage.one" in out and "n" in out

        assert main(["report", "--json", str(trace)]) == 0
        d = json.loads(capsys.readouterr().out)
        assert d["spans"]["stage.one"]["count"] == 1
        assert d["counters"]["n"] == 3

        chrome_out = tmp_path / "t.trace.json"
        assert main(["chrome", str(trace), "-o", str(chrome_out)]) == 0
        loaded = json.loads(chrome_out.read_text())
        assert any(e["ph"] == "X" for e in loaded["traceEvents"])

    def test_study_cli_trace_flag_end_to_end(self, tmp_path, capsys):
        """--trace on a real (tiny) pipeline run produces a trace whose
        top-level span covers the run and whose counters are populated."""
        from repro.study.__main__ import main

        trace = tmp_path / "study.jsonl"
        out = tmp_path / "study.csv"
        assert main(["--refs", "2000", "--workloads", "STRCpy",
                     "--trace", str(trace), "--out", str(out)]) == 0
        assert not obs.enabled()  # CLI disables on the way out
        capsys.readouterr()
        rep = aggregate([trace])
        assert rep.spans["study.run"].count == 1
        assert rep.counter("engine.trace.run") > 0
        assert 0 < rep.counter("profile.scan") <= rep.counter("profile.geom")
        # per-stage total within 10% of the trace's end-to-end wall
        assert rep.span_total("study.run") >= 0.9 * rep.wall_s


class TestSuiteCLIFlags:
    def test_json_flag_is_format_shorthand(self):
        from repro.suite.__main__ import build_parser

        assert build_parser().parse_args([]).format == "csv"
        assert build_parser().parse_args(["--json"]).format == "json"
        assert build_parser().parse_args(
            ["--format", "csv", "--json"]).format == "json"

    def test_table3_section_alias_accepted(self):
        from repro.suite.__main__ import parse_sections

        assert parse_sections("table3") == ()
        assert parse_sections("table3,serving") == ("serving",)
        with pytest.raises(Exception, match="unknown section"):
            parse_sections("table9")
