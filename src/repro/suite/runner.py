"""Suite runner: fan the roster over the memoized engine, persist results.

:class:`SuiteRunner` characterizes every registered entry with the
standard Step-2/Step-3 pipeline — locality on the 1-core trace, then the
host core sweep submitted as one
:meth:`repro.study.engine.SimEngine.simulate_batch` (via
``classify.measure``) — and assigns the six-class verdict.  Each finished
entry row is persisted to a content-addressed :class:`ResultStore`, so
re-running a suite re-simulates only the missing cells; recalled rows are
byte-identical to freshly computed ones (they store the rounded values).

Optional roster sections (``sections=("scalability", "energy")`` /
``--sections``) append per-entry scalability and energy columns computed
from the same memoized engine cells; sectioned rows are stored under
section-specific record keys so plain and sectioned rosters never recall
each other's rows.  The ``serving`` section swaps the roster itself: the
registry resolves through :func:`~repro.suite.registry.registry_for` to
the production-traffic scenarios of :mod:`repro.serving`, and the section
columns add each scenario's phase timeline
(:func:`repro.serving.phases.measure_windows` on the shared engine) plus
the best data-movement mitigation measured across the host+pf / NUCA /
NDP substrates.

Entry-level process fan-out: with ``processes > 1`` the runner
characterizes whole entries — not just core-sweep cells — across a
:class:`~concurrent.futures.ProcessPoolExecutor`.  Workload generators
close over ndarrays and nested functions, so entries cannot cross the
pickle boundary; instead each worker rebuilds the
:func:`~repro.suite.registry.default_registry` from the registry's
``refs`` marker (cached per process) and characterizes entries by name.
Rows computed in workers are identical to in-process rows (the pipeline
is deterministic), and the parent persists them to the store exactly as
in the sequential path.  Workers are spawned with ``JAX_PLATFORMS=cpu``:
they capture and simulate on the host and never claim the accelerator,
which belongs to one process.  The ``jax`` backend runs its scan on that
accelerator, so it cannot fan out and ``processes > 1`` with it raises.
"""

from __future__ import annotations

import contextlib
import functools
import multiprocessing
import os
import time
from concurrent.futures import ProcessPoolExecutor
from dataclasses import dataclass

from repro import obs
from repro.core import cachesim, classify
from repro.core.scalability import sweep_configs
from repro.core.sweep import CORE_SWEEP
from repro.study.engine import SimEngine
from repro.study.result import StudyResult
from repro.study.study import Study

from .registry import LEGACY_SCHEMA, SUITE_SCHEMA, SuiteEntry, SuiteRegistry
from .store import ResultStore

__all__ = ["SuiteRunner", "ROSTER_COLUMNS", "SECTION_COLUMNS", "CLASSES"]

ROSTER_COLUMNS = (
    "name", "domain", "source", "expected", "assigned", "match",
    "spatial", "temporal", "ai", "mpki", "lfmr_mean", "lfmr_slope",
)

# Optional per-entry roster sections (``--sections``): extra columns
# appended to every row, computed from the same memoized engine cells.
# ``scalability``: host strong-scaling speedup and the NDP-vs-host speedup
# at the sweep's top core count (paper Figs. 5/16).  ``energy``: per-thread
# host and NDP energy at the top core count plus their ratio (Figs. 7-17).
# ``serving``: phase structure (window count, distinct phases, dominant
# phase, the full per-window class timeline) and the best-performing
# data-movement mitigation with its speedup over the plain host at the
# sweep's top core count; requesting it also swaps the roster to the
# repro.serving scenarios (see registry_for).  ``models``: the entry's
# swept axes (mode, batch, cache/sequence geometry) plus the whole-step
# op census (total / dense / stream / pallas op counts and the shared
# address-space footprint) from the zoo's capture census; requesting it
# swaps the roster to the model zoo.
SECTION_COLUMNS: dict[str, tuple[str, ...]] = {
    "scalability": ("host_speedup", "ndp_speedup"),
    "energy": ("host_mj", "ndp_mj", "ndp_energy_ratio"),
    "serving": ("windows", "phases", "dominant_phase", "phase_timeline",
                "best_mitigation", "best_speedup"),
    "models": ("mode", "batch", "geometry", "model_ops", "dense_ops",
               "stream_ops", "pallas_ops", "footprint_mib"),
}

# A mitigation must beat the plain host by this factor before the roster
# recommends it; below the bar the row reports "none" (matching the
# MITIGATIONS entries for the compute-friendly classes).
_MITIGATION_BAR = 1.05
CLASSES = classify.CLASSES


@dataclass
class RunStats:
    computed: int = 0
    recalled: int = 0

    def as_dict(self) -> dict[str, int]:
        return {"computed": self.computed, "recalled": self.recalled}


@contextlib.contextmanager
def _env(**overrides: str):
    """Set environment variables for the duration of the block (so every
    process spawned inside it inherits them), then restore them."""
    saved = {k: os.environ.get(k) for k in overrides}
    os.environ.update(overrides)
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


@functools.lru_cache(maxsize=1)
def _worker_runner(refs: int, seed: int, cores: tuple[int, ...],
                   backend: str, sections: tuple[str, ...],
                   store_root: str | None,
                   only: tuple[str, ...] | None = None) -> "SuiteRunner":
    """Per-process runner over a rebuilt registry (fork/spawn-safe:
    constructed on first task, reused for every entry the worker gets).
    ``registry_for`` resolves the same roster the parent ran — the serving
    scenarios when the serving section is on, the models roster (with the
    parent's ``only`` filter, so a filtered sweep run never rebuilds the
    whole zoo in a worker) for the models section, the default roster
    else.  ``store_root`` (the parent's store directory) reconnects the
    worker to the shared cell store, so simulation cells finished by any
    pool member — this run or a previous one — are recalled instead of
    re-run."""
    from .registry import registry_for

    runner = SuiteRunner(registry_for(refs=refs, sections=sections,
                                      only=only),
                         seed=seed, cores=cores,
                         backend=backend, store=None, sections=sections)
    if store_root is not None:
        runner.study.engine.profile_store = \
            ResultStore(store_root).sub("cells")
    return runner


def _characterize_entry(task: tuple) -> tuple:
    """Process-pool task: one entry's roster row, by name.

    Workers inherit the parent's trace sink through ``REPRO_TRACE`` (set
    by :func:`repro.obs.enable` before the pool spawns), so their spans
    land in the same stream, pid-tagged.  Counters are flushed per task —
    pool busy time aggregates across workers no matter how the pool is
    torn down.
    """
    name, refs, seed, cores, backend, sections, store_root, only = task
    t0 = time.perf_counter()
    with obs.span("suite.worker.entry", entry=name):
        runner = _worker_runner(refs, seed, cores, backend, sections,
                                store_root, only)
        entry = next(e for e in runner.registry if e.name == name)
        row = runner._characterize(entry)
    obs.count("pool.tasks")
    obs.count("pool.busy_s", time.perf_counter() - t0)
    obs.flush()
    return row


class SuiteRunner:
    """One registry x one memoized engine x one (optional) result store."""

    def __init__(
        self,
        registry: SuiteRegistry,
        *,
        seed: int = 0,
        cores: tuple[int, ...] = CORE_SWEEP,
        backend: str | None = None,
        store: ResultStore | None = None,
        processes: int | None = None,
        sections: tuple[str, ...] = (),
    ) -> None:
        self.registry = registry
        self.seed = seed
        self.cores = tuple(cores)
        self.store = store
        # Resolve the backend now so the store fingerprint names the
        # implementation that actually runs (REPRO_SIM_BACKEND included).
        self.backend = backend if backend is not None else \
            cachesim.default_backend()
        self.processes = processes
        unknown = set(sections) - set(SECTION_COLUMNS)
        if unknown:
            raise ValueError(
                f"unknown roster section(s) {sorted(unknown)}; expected a "
                f"subset of {sorted(SECTION_COLUMNS)}")
        # canonical order, so column layout never depends on CLI order
        self.sections = tuple(s for s in SECTION_COLUMNS if s in sections)
        self.columns: tuple[str, ...] = ROSTER_COLUMNS + tuple(
            c for s in self.sections for c in SECTION_COLUMNS[s])
        # Cell store (satellite of the roster store): content-addressed
        # SimResult records shared across process-pool workers.  Scoped to
        # pool runs — in-process runs already share cells through the
        # engine memo, and the per-cell JSON round-trips would only slow
        # the sequential path down.
        pool = processes is not None and (processes == 0 or processes > 1)
        cell_store = (store.sub("cells")
                      if store is not None and pool else None)
        self.study = Study(
            suite=registry.workloads(), seed=seed, cores=self.cores,
            engine=SimEngine(backend=self.backend,
                             profile_store=cell_store),
        )
        self.stats = RunStats()
        self._rows: dict[str, tuple] = {}
        self._rebuilt: dict[str, SuiteEntry] | None = None

    # ---- characterization ------------------------------------------------
    def _characterize(self, entry: SuiteEntry) -> tuple:
        with obs.span("suite.entry", entry=entry.name, source=entry.source):
            return self._characterize_inner(entry)

    def _characterize_inner(self, entry: SuiteEntry) -> tuple:
        w = entry.workload
        spatial, temporal = self.study.locality(w)
        m = self.study.metrics(w)
        assigned = classify.classify(m)
        row = (
            entry.name, entry.domain, entry.source, entry.expected_class,
            assigned, int(assigned == entry.expected_class),
            round(spatial, 3), round(temporal, 3), round(m.ai, 3),
            round(m.mpki, 2), round(m.lfmr_mean, 3), round(m.lfmr_slope, 3),
        )
        for section in self.sections:
            row += self._section_values(section, entry)
        return row

    def _section_values(self, section: str, entry: SuiteEntry) -> tuple:
        """Extra per-entry columns, from the same memoized engine cells."""
        if section == "serving":
            return self._serving_values(entry)
        if section == "models":
            return self._model_values(entry)
        r = self.study.scalability(entry.workload)
        host = r.points["host"]
        ndp = r.points["ndp"]
        if section == "scalability":
            return (round(host[-1].perf / host[0].perf, 3),
                    round(ndp[-1].perf / host[-1].perf, 3))
        # energy: per-thread J -> mJ at the sweep's top core count; the
        # ratio is derived from the rounded columns so the row is
        # internally consistent after a store round-trip
        host_mj = round(host[-1].energy.total_j * 1e3, 6)
        ndp_mj = round(ndp[-1].energy.total_j * 1e3, 6)
        return (host_mj, ndp_mj,
                round(ndp_mj / host_mj if host_mj else 0.0, 3))

    def _serving_values(self, entry: SuiteEntry) -> tuple:
        """Phase timeline + best measured mitigation for a serving entry.

        Non-serving entries (the section can ride on the default roster
        too) skip the window pass — they have no scheduling windows — and
        report placeholder phase columns next to a real best-mitigation
        measurement.
        """
        if entry.source == "serving":
            from repro.serving.phases import measure_windows

            tl = measure_windows(entry.name, seed=self.seed,
                                 cores=self.cores, engine=self.study.engine)
            phase_cols = (len(tl.labels), tl.n_phases, tl.dominant,
                          tl.timeline())
        else:
            phase_cols = (0, 0, "-", "-")
        return phase_cols + self._best_mitigation(entry)

    def _model_values(self, entry: SuiteEntry) -> tuple:
        """Swept axes + whole-step op census for a model entry
        (placeholder columns on any other source — the section can ride
        on other rosters too)."""
        if entry.source != "model":
            return ("-", 0, "-", 0, 0, 0, 0, 0.0)
        from repro.capture.zoo import census_for

        p = dict(entry.params)
        return (p["mode"], p["batch"], p["geometry"]) + census_for(entry.name)

    def _best_mitigation(self, entry: SuiteEntry) -> tuple:
        """(name, speedup) of the best substrate vs the plain host at the
        sweep's top core count: NDP, prefetch+NUCA host, or NUCA alone —
        the three §5 mitigation levers — gated on :data:`_MITIGATION_BAR`.
        """
        plain = self.study.scalability(entry.workload)
        tuned = self.study.scalability(entry.workload, nuca=True)
        base = plain.points["host"][-1].perf
        candidates = {
            "ndp": plain.points["ndp"][-1].perf / base,
            "prefetch+nuca": tuned.points["host+pf"][-1].perf / base,
            "nuca": tuned.points["host"][-1].perf / base,
        }
        best = max(candidates, key=lambda k: candidates[k])
        if candidates[best] < _MITIGATION_BAR:
            return ("none", 1.0)
        return (best, round(candidates[best], 3))

    def _fingerprint(self, entry: SuiteEntry) -> str:
        return entry.fingerprint(seed=self.seed, cores=self.cores,
                                 backend=self.backend,
                                 sections=self.sections)

    def _recall(self, entry: SuiteEntry) -> tuple | None:
        """Store lookup for one entry; caches and counts on hit.

        A record that parses but has the wrong shape (schema mismatch,
        drifted columns, missing/short row) is treated exactly like a
        miss — the entry recomputes and the fresh row overwrites it.
        """
        if self.store is None:
            return None
        rec = self.store.get(self._fingerprint(entry))
        if (rec is not None
                and rec.get("schema", LEGACY_SCHEMA) == SUITE_SCHEMA
                and rec.get("columns") == list(self.columns)
                and isinstance(rec.get("row"), list)
                and len(rec["row"]) == len(self.columns)):
            obs.count("store.recall.warm")
            row = tuple(rec["row"])
            self._rows[entry.name] = row
            self.stats.recalled += 1
            return row
        obs.count("store.recall.cold")
        return None

    def _persist(self, entry: SuiteEntry, row: tuple) -> None:
        self._rows[entry.name] = row
        self.stats.computed += 1
        if self.store is not None:
            self.store.put(self._fingerprint(entry),
                           {"schema": SUITE_SCHEMA,
                            "columns": list(self.columns),
                            "row": list(row)})

    def row(self, entry: SuiteEntry) -> tuple:
        """One roster row, store-first (computed and persisted on miss)."""
        got = self._rows.get(entry.name)
        if got is not None:
            return got
        got = self._recall(entry)
        if got is not None:
            return got
        row = self._characterize(entry)
        self._persist(entry, row)
        return row

    def compute_all(self, *, processes: int | None = None) -> None:
        """Materialize every entry row, fanning misses across processes.

        ``processes`` (default: the constructor's ``processes``) > 1 fans
        whole entries over a :class:`ProcessPoolExecutor`; each worker
        rebuilds the default registry from ``registry.refs`` (required —
        a hand-built registry cannot cross the pickle boundary) and
        returns finished rows, which the parent persists.  ``0`` means
        one process per CPU.  Store-recalled entries never reach the
        pool, and neither does any entry the rebuilt registry would not
        reproduce *identically* (same entry fingerprint, same workload
        generator) — a registry that was extended or had entries swapped
        after ``default_registry`` keeps working, with the divergent
        entries characterized in-process.
        """
        processes = self.processes if processes is None else processes
        if processes == 0:
            processes = os.cpu_count() or 1
        if (processes or 1) > 1 and self.backend == "jax":
            raise ValueError(
                "backend 'jax' runs the window scan on the accelerator, "
                "which one process holds at a time; run with processes=1 "
                "(--processes 1)")
        todo = [
            e for e in self.registry
            if e.name not in self._rows and self._recall(e) is None
        ]
        if not todo:
            return
        if processes is None or processes <= 1 or len(todo) == 1:
            self._prewarm(todo)
            for entry in todo:
                self._persist(entry, self._characterize(entry))
            return
        if self.registry.refs is None:
            raise ValueError(
                "process fan-out needs a registry reconstructible from "
                "registry_for(refs=...); this registry has no refs "
                "marker — run with processes=1"
            )
        remote, local = [], []
        for entry in todo:
            (remote if self._reconstructible(entry) else local).append(entry)
        if remote:
            tasks = [
                (e.name, self.registry.refs, self.seed, self.cores,
                 self.backend, self.sections,
                 str(self.store.root) if self.store is not None else None,
                 self.registry.only)
                for e in remote
            ]
            # spawn, not fork: the parent may have JAX (or another
            # multithreaded library) loaded, and forking a multithreaded
            # process can deadlock a child on an inherited lock.  Workers
            # rebuild everything from the pickled task tuple anyway.
            # Workers do host-only capture and NumPy simulation, so they
            # are spawned with JAX_PLATFORMS=cpu: none of them may claim
            # the accelerator the parent (or another worker) holds.
            ctx = multiprocessing.get_context("spawn")
            n_workers = min(processes, len(remote))
            t0 = time.perf_counter()
            with obs.span("suite.pool", entries=len(remote),
                          processes=n_workers), \
                    _env(JAX_PLATFORMS="cpu"), \
                    ProcessPoolExecutor(max_workers=n_workers,
                                        mp_context=ctx) as pool:
                for entry, row in zip(remote,
                                      pool.map(_characterize_entry, tasks)):
                    self._persist(entry, tuple(row))
            # pool.busy_s (accumulated in workers) over workers x wall is
            # the fleet busy fraction the obs report derives
            obs.count("pool.wall_s", time.perf_counter() - t0)
            obs.count("pool.workers", n_workers)
        for entry in local:
            self._persist(entry, self._characterize(entry))

    def _prewarm(self, entries: list[SuiteEntry]) -> None:
        """One cross-workload batch over every cell the roster pass needs.

        Submitting the whole grid as a single
        :meth:`~repro.study.engine.SimEngine.simulate_cells` call lets the
        vectorized backend stack same-geometry nodes from *different*
        traces into segmented stream profiles — one collapse + sort +
        capped window scan per unique hierarchy geometry across the
        roster, instead of one per entry.  The per-entry characterization
        that follows then runs entirely on engine hits.  The grid mirrors
        what the sections will ask for (``classify.measure``'s host sweep
        always; the scalability/energy/serving sweeps when requested), so
        no cell is simulated that would not have been.
        """
        factories = []
        if set(self.sections) & {"scalability", "energy", "serving"}:
            factories += list(sweep_configs(nuca=False).values())
        if "serving" in self.sections:
            # _best_mitigation also sweeps the NUCA variants
            factories += list(sweep_configs(nuca=True).values())
        items = [
            (e.workload, c, cfg)
            for e in entries
            for c in self.cores
            for cfg in ([cachesim.host_config(c)]
                        + [f(c) for f in factories])
        ]
        if "serving" in self.sections:
            # The phase timeline measures every scheduling window as a
            # standalone workload (host sweep only, no mitigation grid);
            # batching them here folds ~10 windows x entries into the same
            # segmented pass.
            from repro.serving.phases import _window_workload
            from repro.serving.scenario import SCENARIOS
            for e in entries:
                if e.source != "serving" or e.name not in SCENARIOS:
                    continue
                scen = SCENARIOS[e.name]
                items += [
                    (_window_workload(scen, i, wt), c,
                     cachesim.host_config(c))
                    for i, wt in enumerate(
                        scen.window_traces(seed=self.seed))
                    for c in self.cores
                ]
        if items:
            with obs.span("suite.prewarm", entries=len(entries),
                          cells=len(items)):
                self.study.engine.simulate_cells(items, seed=self.seed)

    def _reconstructible(self, entry: SuiteEntry) -> bool:
        """Would a worker's rebuilt default registry reproduce ``entry``
        exactly?  Checked on the entry fingerprint (params, domain,
        expected class, seed/cores/backend) *and* the workload-generator
        fingerprint (code object + closed-over parameters), so a swapped
        generator under an unchanged name is caught, not silently
        mischaracterized."""
        from repro.study.engine import _fingerprint as workload_fingerprint

        other = self._rebuilt_default().get(entry.name)
        if other is None:
            return False
        kw = dict(seed=self.seed, cores=self.cores, backend=self.backend)
        return (other.fingerprint(**kw) == entry.fingerprint(**kw)
                and workload_fingerprint(other.workload)
                == workload_fingerprint(entry.workload))

    def _rebuilt_default(self) -> dict[str, SuiteEntry]:
        if self._rebuilt is None:
            from .registry import registry_for
            self._rebuilt = {
                e.name: e
                for e in registry_for(refs=self.registry.refs,
                                      sections=self.sections,
                                      only=self.registry.only)
            }
        return self._rebuilt

    # ---- tables ----------------------------------------------------------
    def roster(self) -> StudyResult:
        """The Table-3-style roster: one row per entry, both sources."""
        self.compute_all()
        res = StudyResult("suite_roster", self.columns)
        for entry in self.registry:
            res.append(self.row(entry))
        return res

    def histogram(self) -> StudyResult:
        """Per-class entry counts, split by source (Fig. 2-style census).

        Columns follow the registry's sources in canonical order (the
        default roster keeps its synthetic/captured split; the serving
        roster gets a serving column instead).
        """
        roster = self.roster()
        present = {e.source for e in self.registry}
        sources = tuple(
            s for s in ("synthetic", "captured", "serving", "model")
            if s in present
        ) or ("synthetic", "captured")
        counts: dict[str, dict[str, int]] = {
            c: dict.fromkeys(sources, 0) for c in CLASSES
        }
        for rec in roster.records():
            counts.setdefault(rec["assigned"], dict.fromkeys(sources, 0))
            counts[rec["assigned"]][rec["source"]] += 1
        res = StudyResult("class_histogram", ("class",) + sources + ("total",))
        for cls in sorted(counts):
            vals = tuple(counts[cls][s] for s in sources)
            if cls in CLASSES or any(vals):
                res.append((cls,) + vals + (sum(vals),))
        return res

    def divergent(self, *, source: str = "captured") -> list[dict]:
        """Entries of ``source`` whose assigned class != expected class."""
        return [
            rec for rec in self.roster().records()
            if rec["source"] == source and not rec["match"]
        ]
