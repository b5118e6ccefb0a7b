"""Structural counter gates over a ``repro.obs`` trace.

Speed is measured on the chip by ``bench/``; this gate checks what a
trace can show exactly and deterministically: that a path kept its
sharing structure.  Given ``--obs-trace TRACE.jsonl`` (a ``repro.obs``
trace, recorded via ``--trace`` on the suite CLI) it asserts *counter
invariants*::

    # cold roster: every profile pass goes through the trace memo, and
    # the segmented walk may serve several geometries from one scan
    python -m benchmarks.perf_gate --obs-trace cold.jsonl \
        --obs-require 'profile.scan<=profile.geom'

    # warm rerun: pure store recall — zero cold recalls, zero sims
    python -m benchmarks.perf_gate --obs-trace warm.jsonl \
        --obs-require store.recall.cold==0 \
        --obs-require engine.sim.run==0 --obs-require profile.scan==0

    # the per-stage spans must cover the end-to-end wall-clock
    python -m benchmarks.perf_gate --obs-trace cold.jsonl \
        --obs-min-coverage suite.registry+suite.run=0.9

``--obs-require`` takes ``NAME OP NAME-or-NUMBER`` (operators ``==``
``!=`` ``<=`` ``>=`` ``<`` ``>``; a name resolves to the merged counter
value, missing counters read as 0; ``span:NAME`` resolves to that span's
total seconds).  ``--obs-min-coverage A+B=F`` requires the summed span
totals of ``A``/``B`` to cover at least fraction ``F`` of the trace's
end-to-end wall.  The gate exits 1 when any check is violated.
"""

from __future__ import annotations

import argparse
import operator
import sys

_OBS_OPS = {
    "==": operator.eq, "!=": operator.ne, "<=": operator.le,
    ">=": operator.ge, "<": operator.lt, ">": operator.gt,
}


def parse_require(expr: str) -> tuple[str, str, str]:
    """``"profile.scan==profile.geom"`` -> ``(lhs, op, rhs)``."""
    for op in ("==", "!=", "<=", ">=", "<", ">"):  # 2-char ops first
        if op in expr:
            lhs, rhs = expr.split(op, 1)
            lhs, rhs = lhs.strip(), rhs.strip()
            if lhs and rhs:
                return lhs, op, rhs
    raise SystemExit(
        f"bad --obs-require {expr!r}; expected NAME OP NAME-or-NUMBER "
        f"with OP in {sorted(_OBS_OPS)}")


def _resolve(rep, token: str) -> float:
    """Numeric literal, ``span:NAME`` total seconds, or counter value."""
    try:
        return float(token)
    except ValueError:
        pass
    if token.startswith("span:"):
        return rep.span_total(token[len("span:"):])
    return rep.counter(token, 0.0)


def obs_gate(rep, requires: list[str], coverages: list[str], *,
             out=None) -> list[str]:
    """Check counter invariants + span coverage; return failed checks.

    ``rep`` is a :class:`repro.obs.report.ObsReport`; ``requires`` are
    raw ``--obs-require`` expressions, ``coverages`` raw
    ``--obs-min-coverage`` specs (``NAME[+NAME...]=FRACTION``).  The
    verdicts print to ``out`` (default: the current ``sys.stdout``).
    """
    out = sys.stdout if out is None else out
    failures: list[str] = []
    for expr in requires:
        lhs, op, rhs = parse_require(expr)
        lv, rv = _resolve(rep, lhs), _resolve(rep, rhs)
        ok = _OBS_OPS[op](lv, rv)
        verdict = "ok" if ok else "VIOLATED"
        print(f"obs require  {expr:44s} [{lv:g} {op} {rv:g}]  {verdict}",
              file=out)
        if not ok:
            failures.append(expr)
    for spec in coverages:
        names, _, frac_text = spec.partition("=")
        try:
            frac = float(frac_text)
        except ValueError:
            raise SystemExit(
                f"bad --obs-min-coverage {spec!r}; expected "
                f"NAME[+NAME...]=FRACTION")
        total = sum(rep.span_total(n.strip())
                    for n in names.split("+") if n.strip())
        cov = total / rep.wall_s if rep.wall_s else 0.0
        ok = cov >= frac
        verdict = "ok" if ok else "VIOLATED"
        print(f"obs coverage {names:44s} [{total:.3f}s / "
              f"{rep.wall_s:.3f}s = {cov:.1%} >= {frac:.0%}]  {verdict}",
              file=out)
        if not ok:
            failures.append(spec)
    return failures


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m benchmarks.perf_gate",
        description="fail CI when a repro.obs trace violates a counter "
                    "invariant or span-coverage floor")
    ap.add_argument("--obs-trace", required=True, metavar="TRACE.jsonl",
                    action="append",
                    help="repro.obs trace file(s) to merge and gate "
                         "counter invariants over (repeatable)")
    ap.add_argument("--obs-require", default=[], action="append",
                    metavar="EXPR",
                    help="counter invariant, e.g. store.recall.cold==0 "
                         "or profile.scan<=profile.geom (repeatable)")
    ap.add_argument("--obs-min-coverage", default=[], action="append",
                    metavar="NAME[+NAME..]=FRACTION",
                    help="require the named spans' summed total to cover "
                         "at least FRACTION of the trace wall-clock "
                         "(repeatable)")
    args = ap.parse_args(argv)

    from repro.obs.report import aggregate

    rep = aggregate(args.obs_trace)
    failures = obs_gate(rep, args.obs_require, args.obs_min_coverage)
    if failures:
        print(f"perf gate FAILED: counter invariant(s) violated: "
              f"{'; '.join(failures)}", file=sys.stderr)
        return 1
    print(f"perf gate OK: "
          f"{len(args.obs_require) + len(args.obs_min_coverage)}"
          f" counter invariant(s) hold", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
