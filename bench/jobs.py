"""A cell's jobs: its configuration and traffic mix made into the work that
drives the program, and the check of what that work produces.

A traffic file's ``kind`` names the module under ``bench/kinds/`` that
makes its jobs, so a new kind of job is a new file there.  Each such
module defines ``Job(config, traffic, seed)`` with:

- ``setup()``: the cell's checks, before the warm-up job;
- ``job() -> dict``: the timed unit, a cold characterization, returning
  its answers;
- ``count_refs() -> int``: the trace references one job characterizes,
  each trace once;
- ``last_outputs() -> dict``: what the last job produced that the check
  reads; the program's state is dropped after it;
- ``placement() -> dict``: the same, made without a timed job (for the
  control);
- ``reference(last, level) -> dict``: the plain reference's answers
  (:mod:`bench.reference`), with ``level`` the cache level it simulates;
- ``compare(jobs, last, want) -> list[Number]``: the numbers compared,
  each with its limit.

Every job starts from the state a fresh command-line run has: a new
registry or capture, empty capture and simulator memos, no result store.
Only JAX's compilation cache is warm.
"""

from __future__ import annotations

import importlib
from dataclasses import dataclass


@dataclass(frozen=True)
class Number:
    """One number the check compares, with its limit (pass: value <= limit)."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


def annotate(name: str):
    """A host span on the profiler's clock (a no-op unless tracing)."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name)


def differing(got: tuple, want: tuple) -> int:
    """Positions at which two counter tuples differ, a missing one
    counting as differing."""
    n = max(len(got), len(want))
    return sum(1 for i in range(n)
               if i >= len(got) or i >= len(want) or got[i] != want[i])


def kind_module(kind: str):
    """The module of ``bench/kinds/`` that makes jobs of this kind."""
    if not kind.isidentifier():
        raise SystemExit(f"bench: job kind {kind!r} is not a module name")
    try:
        return importlib.import_module(f"bench.kinds.{kind}")
    except ModuleNotFoundError as e:
        if e.name == f"bench.kinds.{kind}":
            raise SystemExit(f"bench: no job kind {kind!r} "
                             f"(bench/kinds/{kind}.py)") from e
        raise


def make(config: dict, traffic: dict, seed: int):
    return kind_module(traffic["kind"]).Job(config, traffic, seed)
