"""Benchmark driver: one section per DAMOV table/figure + the TPU tables.

Usage::

    PYTHONPATH=src python -m benchmarks.run [--fast] [--only SECTION]

All figure sections are queries over ONE shared :class:`repro.study.Study`:
the memoized engine simulates each (workload, cores, config) cell exactly
once — submitting every sweep through the batched single-pass backend —
and every section reuses it, so the full run is one simulation pass.

Sections map 1:1 to paper artifacts:

- fig1   — roofline + MPKI vs NDP speedup (Fig. 1)
- fig3   — locality-based clustering (Fig. 3)
- fig4   — LFMR/MPKI per function (Fig. 4)
- fig5   — scalability curves, 3 systems (Figs. 5, 16)
- fig7   — energy breakdowns (Figs. 7-17)
- fig18  — per-class NDP-speedup summary + §3.5 validation accuracy
- table3 — the registered benchmark-suite roster (repro.suite): synthetic
           family expansions + captured Pallas-kernel traces in one
           classification table
- suite  — the suite subsystem's per-class histogram over the same
           runner/roster (the CI smoke for the repro.suite path; shares
           table3's runner, engine and result store)
- serving / serving_warm — the repro.serving traffic-scenario roster with
           phase-timeline columns: ``serving`` composes + classifies the
           16 scenarios cold against a fresh throwaway store, then
           ``serving_warm`` re-rosters against that store, timing the
           pure content-addressed recall path
- models — the whole-model roster (repro.capture.zoo): traces + classifies
           the CI-pair subset of the 176-entry axis sweep (one dense + one
           SSM config across decode/prefill/eval/train x batch x geometry)
           cold against its own throwaway store, timing jaxpr walk + eqn
           lowering + windowed trace walks end to end (skipped when jax
           is unavailable)
- models_sweep — the streamed whole-step data path: the zoo's bs64 decode
           megaref walk fed op-by-op (ModelCapture.walk_stream) into
           cachesim_stream.simulate_chunked, never materializing a
           concatenated trace (CI gates capture.model.concat==0 over
           this section's obs trace)
- megaref — the chunk-streaming simulator over one long bounded-footprint
           trace (2M refs fast / 10M full), always cold: times
           ``cachesim_stream.simulate_chunked`` end to end
- case1..case4 — §5 case studies
- roofline — §Roofline TPU table (analytic pod-mesh cost model)

Each section prints its CSV rows and a ``# <section>: N rows in Xs``
line.  Speed is measured on the chip by ``bench/``, not here.
"""

from __future__ import annotations

import argparse
import sys
import time

from repro.study import Study, StudyResult
from repro.suite import ResultStore

from . import paper_figures, roofline_table


def emit(section: str, result) -> list[tuple]:
    if isinstance(result, StudyResult):
        rows, header = result.to_rows(), result.columns
    else:
        rows, header = result
    print(f"\n## {section}")
    print(",".join(str(h) for h in header))
    for r in rows:
        print(",".join(str(x) for x in r))
    sys.stdout.flush()
    return rows


def main() -> None:
    from repro.core.cachesim import BACKENDS
    from repro.core.tracegen import DEFAULT_REFS

    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="reduced trace length (CI)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--backend", choices=BACKENDS, default=None,
                    help="cache-simulation implementation; default: "
                         "$REPRO_SIM_BACKEND or 'vectorized'")
    args = ap.parse_args()

    refs = 20_000 if args.fast else DEFAULT_REFS
    study = Study(refs=refs, backend=args.backend)

    # table3 and suite share one runner (engine + content-addressed result
    # store), so repeat benchmark runs recall the roster instead of
    # re-simulating and the suite section is free once table3 ran.
    runner_box: list = []

    def suite_runner():
        if not runner_box:
            from repro.suite import SuiteRunner, default_registry
            runner_box.append(SuiteRunner(
                default_registry(refs=refs), store=ResultStore(),
                backend=args.backend))
        return runner_box[0]

    def suite_histogram():
        runner = suite_runner()
        res = runner.histogram()
        res.name = "suite"
        return res

    # serving roster: cold composition+classification vs pure store recall.
    # The cold section owns a throwaway store so repeat benchmark runs stay
    # cold (committing it to the default store would turn "cold" into a
    # recall timing on the second run).
    serving_store_box: list = []

    def _serving_store() -> ResultStore:
        if not serving_store_box:
            import atexit
            import shutil
            import tempfile

            tmp = tempfile.mkdtemp(prefix="bench-serving-store-")
            atexit.register(shutil.rmtree, tmp, ignore_errors=True)
            serving_store_box.append(ResultStore(tmp))
        return serving_store_box[0]

    def serving_roster(section: str):
        from repro.suite import SuiteRunner, serving_registry

        runner = SuiteRunner(serving_registry(refs=refs),
                             store=_serving_store(), backend=args.backend,
                             sections=("serving",))
        res = runner.roster()
        res.name = section
        return res

    # whole-model roster: cold jaxpr walk + windowed trace + classify.
    # The zoo is now a 176-entry axis sweep, so this section times the
    # CI-pair subset (one dense + one SSM config across every mode /
    # batch / geometry — 46 entries); the full sweep is a local run.
    # Same throwaway-store rationale as serving; needs jax to trace
    # (gated, not stubbed — there is no jax-free fallback).
    def models_roster():
        from repro.suite import SuiteRunner, models_registry
        from repro.study.result import StudyResult

        try:
            import jax  # noqa: F401
        except ImportError:
            return StudyResult(name="models", columns=("name", "note"),
                               rows=[("models", "skipped: no jax")])
        runner = SuiteRunner(
            models_registry(refs=refs,
                            only=("qwen2.5-14b", "mamba2-780m")),
            store=_serving_store(), backend=args.backend,
            sections=("models",))
        res = runner.roster()
        res.name = "models"
        return res

    # models_sweep: the streamed whole-step data path — the zoo's biggest
    # decode entry (bs64, a megaref walk) fed op-by-op from
    # ModelCapture.walk_stream into cachesim_stream.simulate_chunked.  No
    # concatenated trace array is ever materialized (the obs counter gate
    # in CI asserts capture.model.concat==0 over this section), so peak
    # trace memory is the largest single op.  Always cold, like megaref.
    def models_sweep_rows():
        from repro.study.result import StudyResult

        try:
            import jax  # noqa: F401
        except ImportError:
            return StudyResult(name="models_sweep",
                               columns=("name", "note"),
                               rows=[("models_sweep", "skipped: no jax")])
        from repro.capture.zoo import capture_for
        from repro.core import cachesim
        from repro.core.cachesim_stream import DEFAULT_CHUNK, simulate_chunked

        entry = "model.qwen2.5-14b.decode.bs64"
        mc = capture_for(entry)
        refs_whole = mc.walk(count_only=True).refs
        header = ("name", "refs", "chunk", "l1_misses", "llc_misses",
                  "lfmr", "mpki")
        rows = []
        for cfg in (cachesim.host_config(4), cachesim.ndp_config(4)):
            sim = simulate_chunked(
                mc.walk_stream(), cfg, chunk=DEFAULT_CHUNK,
                name=f"{entry}.{cfg.name}",
                scan="jax" if args.backend == "jax" else None)
            rows.append((sim.name, refs_whole, DEFAULT_CHUNK,
                         sim.l1_misses, sim.level_misses[-1],
                         round(sim.lfmr, 4), round(sim.mpki, 2)))
        return rows, header

    # megaref: the chunk-streaming path over a single long trace with a
    # bounded footprint (the whole-model shape: refs grow, the working
    # set does not).  Deterministic synthetic stream so the section is
    # comparable across runs; always cold — nothing here touches a store.
    def megaref_rows():
        import numpy as np

        from repro.core import cachesim
        from repro.core.cachesim_stream import DEFAULT_CHUNK, simulate_chunked

        n = 2_000_000 if args.fast else 10_000_000
        rng = np.random.default_rng(0)
        sweep = (np.arange(n, dtype=np.int64) * 3) % (1 << 19)
        hot = rng.integers(0, 4_096, n, dtype=np.int64)
        addr = np.where(rng.random(n) < 0.3, hot, sweep) * 8
        header = ("name", "refs", "chunk", "l1_misses", "llc_misses",
                  "lfmr", "mpki")
        rows = []
        for cfg in (cachesim.host_config(4), cachesim.ndp_config(4)):
            sim = simulate_chunked(addr, cfg, chunk=DEFAULT_CHUNK,
                                   name=f"megaref.{cfg.name}",
                                   scan="jax" if args.backend == "jax"
                                   else None)
            rows.append((sim.name, n, DEFAULT_CHUNK, sim.l1_misses,
                         sim.level_misses[-1], round(sim.lfmr, 4),
                         round(sim.mpki, 2)))
        return rows, header

    sections = {
        "fig1": lambda: paper_figures.fig1_roofline_mpki(study),
        "fig3": lambda: paper_figures.fig3_locality_clustering(study),
        "fig4": lambda: paper_figures.fig4_lfmr_mpki(study),
        "fig5": lambda: paper_figures.fig5_scalability(study),
        "fig5_nuca": lambda: paper_figures.fig5_scalability(study, nuca=True),
        "fig7": lambda: paper_figures.fig7_energy(study),
        "fig18": lambda: paper_figures.fig18_summary_and_validation(study),
        "table3": lambda: paper_figures.table3_suite_roster(suite_runner()),
        "suite": suite_histogram,
        # warm must follow cold in dict order; an --only serving_warm run
        # fills the throwaway store inside its own timing (still a valid
        # upper bound on the recall path)
        "serving": lambda: serving_roster("serving"),
        "serving_warm": lambda: serving_roster("serving_warm"),
        "models": models_roster,
        "models_sweep": models_sweep_rows,
        "megaref": megaref_rows,
        "case1": lambda: paper_figures.case1_noc(study),
        "case2": lambda: paper_figures.case2_accelerators(study),
        "case3": lambda: paper_figures.case3_core_models(study),
        "case4": lambda: paper_figures.case4_offload(study),
        "roofline": roofline_table.rows,
    }
    if args.fast:
        sections.pop("fig18")  # the 70-workload held-out sweep is slow

    for name, fn in sections.items():
        if args.only and args.only != name:
            continue
        t0 = time.time()
        result = fn()
        rows = emit(name, result)
        dt = time.time() - t0
        print(f"# {name}: {len(rows)} rows in {dt:.1f}s")

    s = study.stats
    print(f"# engine: {study.engine.cells} cells, "
          f"{s.sim_runs} simulated, {s.sim_hits} cache hits "
          f"({s.sim_hit_rate:.0%} hit rate)")


if __name__ == "__main__":
    from repro import compile_cache

    compile_cache.enable()
    main()
