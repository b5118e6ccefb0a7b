"""Reduction of a JAX profiler trace to the benchmark's device numbers.

The profiler writes an XSpace (``*.xplane.pb``): planes (one per device and
one for the host), lines on each plane, and events with a start and a
duration in nanoseconds.  A TPU plane is named ``/device:TPU:<n>``; its
``XLA Ops`` line holds every operation that ran on the chip and its
``XLA Modules`` line one event per program execution, named after the
jitted function (``jit_<name>(<id>)``).

- busy time: the union of the op intervals inside the window, per chip;
- idle gaps: the window less that union;
- a kernel's device time and launch count: its module events;
- the operations that took most time: op durations summed by name.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


_HLO_HEAD = re.compile(r"%?[\w.\-]+ = [\w\[\],]+")


def op_name(name: str) -> str:
    """An op event's name without its operands: ``%fusion.3 = s32[64]``
    from the whole HLO instruction text the trace carries."""
    head = _HLO_HEAD.match(name)
    return head.group(0) if head else name[:80]


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class DeviceStats:
    """What one traced window put on the chips."""

    window_ns: float
    chips: int
    busy_ns: list[float] = field(default_factory=list)   # per chip
    gaps: list[tuple[float, float]] = field(default_factory=list)  # chip 0
    modules: dict[str, tuple[int, float]] = field(default_factory=dict)
    ops: dict[str, float] = field(default_factory=dict)

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the chips the cell uses."""
        return sum(self.busy_ns) / max(1, self.chips) / 1e9

    def module_time(self, prefix: str) -> tuple[int, float]:
        """(launches, device ns) of the programs whose name starts with
        ``prefix``."""
        n = t = 0
        for name, (k, ns) in self.modules.items():
            if name.startswith(prefix):
                n += k
                t += ns
        return n, t


def read_xplane(path: Path) -> list[Event]:
    """Every event of an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    return [Event(plane.name, line.name, ev.name, float(ev.start_ns),
                  float(ev.duration_ns))
            for plane in data.planes for line in plane.lines
            for ev in line.events]


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merged, sorted, non-overlapping cover of ``intervals``."""
    out: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def complement(cover: list[tuple[float, float]], lo: float,
               hi: float) -> list[tuple[float, float]]:
    """The parts of ``[lo, hi]`` that a merged ``cover`` leaves out."""
    gaps, cursor = [], lo
    for a, b in cover:
        if a > cursor:
            gaps.append((cursor, min(a, hi)))
        cursor = max(cursor, b)
        if cursor >= hi:
            break
    if cursor < hi:
        gaps.append((cursor, hi))
    return [(a, b) for a, b in gaps if b > a]


def reduce_events(events: list[Event], lo_ns: float, hi_ns: float,
                  chips: int) -> DeviceStats:
    """Device numbers of the window ``[lo_ns, hi_ns]`` on the first
    ``chips`` TPU planes.  Events are clipped to the window."""
    planes = sorted({e.plane for e in events
                     if e.plane.startswith(DEVICE_PREFIX)
                     and e.plane[len(DEVICE_PREFIX):].isdigit()},
                    key=lambda p: int(p[len(DEVICE_PREFIX):]))[:chips]
    stats = DeviceStats(window_ns=hi_ns - lo_ns, chips=chips)
    for i, plane in enumerate(planes):
        spans = []
        for e in events:
            if e.plane != plane or e.end_ns <= lo_ns or e.start_ns >= hi_ns:
                continue
            a, b = max(e.start_ns, lo_ns), min(e.end_ns, hi_ns)
            if e.line == OPS_LINE:
                spans.append((a, b))
                key = op_name(e.name)
                stats.ops[key] = stats.ops.get(key, 0.0) + (b - a)
            elif e.line == MODULES_LINE:
                name = e.name.split("(", 1)[0]
                k, ns = stats.modules.get(name, (0, 0.0))
                stats.modules[name] = (k + 1, ns + (b - a))
        cover = union(spans)
        stats.busy_ns.append(sum(b - a for a, b in cover))
        if i == 0:
            stats.gaps = complement(cover, lo_ns, hi_ns)
    return stats


def top(totals: dict[str, float], n: int = 10) -> list[list]:
    """The ``n`` largest ``[name, seconds]`` pairs of a ns-valued dict."""
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]
