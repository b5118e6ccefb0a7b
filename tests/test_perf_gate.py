"""Tests for the CI counter gate (benchmarks.perf_gate)."""

import io
import json
import pathlib
import re

import pytest

from benchmarks import perf_gate


class TestObsGate:
    """Structural counter gates over a repro.obs trace."""

    @staticmethod
    def _rep():
        from repro.obs.report import aggregate_events

        return aggregate_events([
            {"ev": "span", "name": "suite.run", "pid": 1, "tid": 1,
             "ts": 0, "dur": 9_000_000},
            {"ev": "span", "name": "suite.registry", "pid": 1, "tid": 1,
             "ts": 9_000_000, "dur": 1_000_000},
            {"ev": "counters", "pid": 1, "ts": 0,
             "counters": {"profile.scan": 42, "profile.geom": 42,
                          "store.recall.cold": 2}},
        ])

    def test_parse_require(self):
        assert perf_gate.parse_require("a==b") == ("a", "==", "b")
        assert perf_gate.parse_require("a <= 3") == ("a", "<=", "3")
        assert perf_gate.parse_require("x<y") == ("x", "<", "y")
        assert perf_gate.parse_require("n!=0") == ("n", "!=", "0")
        with pytest.raises(SystemExit, match="bad --obs-require"):
            perf_gate.parse_require("nonsense")
        with pytest.raises(SystemExit, match="bad --obs-require"):
            perf_gate.parse_require("==3")

    def test_requires_pass_and_fail(self):
        rep = self._rep()
        out = io.StringIO()
        fails = perf_gate.obs_gate(
            rep, ["profile.scan==profile.geom", "store.recall.cold<=2",
                  "missing.counter==0"], [], out=out)
        assert fails == []
        fails = perf_gate.obs_gate(rep, ["store.recall.cold==0"], [],
                                   out=out)
        assert fails == ["store.recall.cold==0"]
        assert "VIOLATED" in out.getvalue()

    def test_span_token_resolves_total_seconds(self):
        rep = self._rep()
        out = io.StringIO()
        assert perf_gate.obs_gate(
            rep, ["span:suite.run>=8", "span:suite.run<=10"], [],
            out=out) == []
        assert perf_gate.obs_gate(
            rep, ["span:absent==0"], [], out=out) == []

    def test_coverage_pass_and_fail(self):
        rep = self._rep()  # wall 10s; suite.run 9s + suite.registry 1s
        out = io.StringIO()
        assert perf_gate.obs_gate(
            rep, [], ["suite.registry+suite.run=0.95"], out=out) == []
        fails = perf_gate.obs_gate(rep, [], ["suite.registry=0.5"],
                                   out=out)
        assert fails == ["suite.registry=0.5"]
        with pytest.raises(SystemExit, match="bad --obs-min-coverage"):
            perf_gate.obs_gate(rep, [], ["suite.run=lots"], out=out)

    def test_cli_obs_trace_alone(self, tmp_path):
        trace = tmp_path / "t.jsonl"
        lines = [
            json.dumps({"ev": "span", "name": "suite.run", "pid": 1,
                        "tid": 1, "ts": 0, "dur": 5_000_000}),
            json.dumps({"ev": "counters", "pid": 1, "ts": 0,
                        "counters": {"store.recall.cold": 0,
                                     "engine.sim.run": 0}}),
        ]
        trace.write_text("\n".join(lines) + "\n")
        args = ["--obs-trace", str(trace),
                "--obs-require", "store.recall.cold==0",
                "--obs-require", "engine.sim.run==0",
                "--obs-min-coverage", "suite.run=0.9"]
        assert perf_gate.main(args) == 0
        assert perf_gate.main(["--obs-trace", str(trace),
                               "--obs-require", "engine.sim.run>0"]) == 1

    def test_cli_flag_validation(self, tmp_path):
        with pytest.raises(SystemExit):  # obs flags need --obs-trace
            perf_gate.main(["--obs-require", "a==0"])
        with pytest.raises(SystemExit):  # no --obs-trace
            perf_gate.main([])


# --------------------------------------------------------------------------
# Every gate expression the CI workflow passes, on synthetic traces
# --------------------------------------------------------------------------
_ROOT = pathlib.Path(__file__).resolve().parents[1]
_CI_GATES = [(flag, quoted or bare) for flag, quoted, bare in re.findall(
    r"--obs-(require|min-coverage)\s+(?:'([^']*)'|(\S+))",
    (_ROOT / ".github" / "workflows" / "ci.yml").read_text())]
_SRC = "\n".join(p.read_text()
                 for p in (_ROOT / "src" / "repro").rglob("*.py"))

# (holding, breaking) left-hand value for a right-hand value r
_LHS = {"==": (0, 1), "!=": (1, 0), "<=": (0, 1), ">=": (0, -1),
        "<": (-1, 0), ">": (1, 0)}


def _synthetic_trace(flag: str, expr: str, holds: bool) -> tuple[list, list]:
    """Events that satisfy (``holds``) or break one CI gate expression,
    and the counter/span names it reads."""
    if flag == "require":
        lhs, op, rhs = perf_gate.parse_require(expr)
        try:
            r, names = float(rhs), [lhs]
        except ValueError:
            r, names = 5.0, [lhs, rhs]
        counters = {lhs: r + _LHS[op][0 if holds else 1]}
        if len(names) == 2:
            counters[rhs] = r
        return [{"ev": "counters", "pid": 1, "ts": 0,
                 "counters": counters}], names
    spans, _, frac = expr.partition("=")
    names = spans.split("+")
    wall_us = 10_000_000
    covered = wall_us * (float(frac) + (0.05 if holds else -0.05))
    events = [{"ev": "span", "name": "gate.wall", "pid": 1, "tid": 1,
               "ts": 0, "dur": wall_us}]
    for i, name in enumerate(names):
        dur = covered / len(names)
        events.append({"ev": "span", "name": name, "pid": 1, "tid": 2,
                       "ts": i * dur, "dur": dur})
    return events, names


def test_ci_workflow_passes_gate_expressions():
    assert {f for f, _ in _CI_GATES} == {"require", "min-coverage"}


@pytest.mark.parametrize("holds", [True, False], ids=["holds", "violated"])
@pytest.mark.parametrize("flag,expr", _CI_GATES,
                         ids=[e for _, e in _CI_GATES])
def test_ci_gate_expression(flag, expr, holds, tmp_path, capsys):
    events, names = _synthetic_trace(flag, expr, holds)
    for name in names:  # a misspelt name in the workflow fails here
        assert f'"{name}"' in _SRC, f"{name!r} is emitted nowhere in src"
    trace = tmp_path / "t.jsonl"
    trace.write_text("".join(json.dumps(e) + "\n" for e in events))
    rc = perf_gate.main(["--obs-trace", str(trace), f"--obs-{flag}", expr])
    out = capsys.readouterr().out
    assert rc == (0 if holds else 1)
    assert out.rstrip().endswith("ok" if holds else "VIOLATED")
