"""Span-nesting helpers shared by the stage-span tests.

A ``repro.obs`` span event is written when the span exits, so a parent
always comes after its children in the stream.  Start and end are compared
in whole tenths of a microsecond (``ts`` is whole microseconds, ``dur``
has one decimal), where the shared clock makes containment exact.
"""

from __future__ import annotations

import json
from pathlib import Path


def span_events(path) -> list[dict]:
    """The span events of a JSONL trace, in the order they were written."""
    out = []
    for line in Path(path).read_text().splitlines():
        ev = json.loads(line)
        if ev.get("ev") == "span":
            out.append(ev)
    return out


def bounds(ev: dict) -> tuple[int, int]:
    """(start, end) of a span event, in tenths of a microsecond."""
    start = ev["ts"] * 10
    return start, start + round(ev["dur"] * 10)


def parent(events: list[dict], i: int) -> dict | None:
    """The innermost span enclosing ``events[i]`` on its thread."""
    lo, hi = bounds(events[i])
    best = None
    for ev in events[i + 1:]:
        if (ev["pid"], ev["tid"]) != (events[i]["pid"], events[i]["tid"]):
            continue
        a, b = bounds(ev)
        if a <= lo and hi <= b and (best is None
                                    or b - a < bounds(best)[1]
                                    - bounds(best)[0]):
            best = ev
    return best


def parents_by_name(events: list[dict]) -> dict[str, set]:
    """For each span name, the names of the spans directly enclosing it
    (``None`` for a span at the top)."""
    out: dict[str, set] = {}
    for i, ev in enumerate(events):
        p = parent(events, i)
        out.setdefault(ev["name"], set()).add(p and p["name"])
    return out
