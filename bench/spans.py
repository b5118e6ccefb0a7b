"""Host spans of the program's ``repro.obs`` trace stream.

The program writes one JSON object per line: spans with ``ts`` (microseconds
since the epoch), ``dur`` (microseconds) and the ``pid``/``tid`` of their
thread.  A span's self time is its duration less the time covered by the
spans nested directly inside it on the same thread.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path


@dataclass
class Span:
    name: str
    pid: int
    tid: int
    start_us: float
    dur_us: float
    child_us: float = 0.0

    @property
    def end_us(self) -> float:
        return self.start_us + self.dur_us

    @property
    def self_us(self) -> float:
        return max(0.0, self.dur_us - self.child_us)


def read_spans(path: Path) -> list[Span]:
    """Every span event of a JSONL stream, nested (``child_us`` filled
    in)."""
    spans = []
    with open(path, encoding="utf-8") as f:
        for line in f:
            ev = json.loads(line)
            if ev.get("ev") == "span":
                spans.append(Span(ev["name"], ev["pid"], ev["tid"],
                                  float(ev["ts"]), float(ev["dur"])))
    return nest(spans)


def nest(spans: list[Span], slack_us: float = 2.0) -> list[Span]:
    """Fill in each span's direct-children time.

    ``ts`` is the wall clock truncated to whole microseconds and ``dur``
    comes from another clock, so containment allows ``slack_us``."""
    by_thread: dict[tuple[int, int], list[Span]] = {}
    for s in spans:
        by_thread.setdefault((s.pid, s.tid), []).append(s)
    for group in by_thread.values():
        group.sort(key=lambda s: (s.start_us, -s.dur_us))
        stack: list[Span] = []
        for s in group:
            while stack and stack[-1].end_us + slack_us < s.end_us:
                stack.pop()
            if stack:
                stack[-1].child_us += s.dur_us
            stack.append(s)
    return spans


def self_seconds(spans: list[Span], prefixes: tuple[str, ...]) -> float:
    """Summed self time, in seconds, of the spans whose name starts with
    any of ``prefixes``."""
    return sum(s.self_us for s in spans
               if s.name.startswith(prefixes)) / 1e6


def timeline(spans: list[Span]) -> list[tuple[float, float, str]]:
    """One thread's spans flattened to ``(start_us, end_us, name)``
    segments that do not overlap, each named by the innermost span open
    in it."""
    segs: list[tuple[float, float, str]] = []
    stack: list[tuple[float, str]] = []      # (end_us, name), nested
    cursor = 0.0

    def close_until(t: float) -> None:
        nonlocal cursor
        while stack and stack[-1][0] <= t:
            end, name = stack.pop()
            if end > cursor:
                segs.append((cursor, end, name))
                cursor = end

    for s in sorted(spans, key=lambda s: (s.start_us, -s.dur_us)):
        close_until(s.start_us)
        if stack and s.start_us > cursor:
            segs.append((cursor, s.start_us, stack[-1][1]))
        cursor = max(cursor, s.start_us)
        end = min(s.end_us, stack[-1][0]) if stack else s.end_us
        stack.append((end, s.name))
    close_until(float("inf"))
    return segs


def attribute(points_us: list[float],
              segs: list[tuple[float, float, str]]) -> list[str | None]:
    """For each time in ``points_us``, the name of the segment holding it
    (``None`` where no span was open)."""
    order = sorted(range(len(points_us)), key=points_us.__getitem__)
    out: list[str | None] = [None] * len(points_us)
    k = 0
    for i in order:
        t = points_us[i]
        while k < len(segs) and segs[k][1] < t:
            k += 1
        if k < len(segs) and segs[k][0] <= t <= segs[k][1]:
            out[i] = segs[k][2]
    return out
