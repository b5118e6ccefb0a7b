"""Tests for the benchmark-suite subsystem (repro.suite).

Registry invariants (roster size, both sources, name uniqueness,
fingerprint content-addressing), the result store (round-trip, atomic
layout, corrupt-record tolerance), the runner (store-first recall with
zero re-simulation, byte-identical rosters), the suite substrate, and the
CLI.  Heavy full-roster paths are exercised on reduced registries; the CI
suite-smoke leg covers the full --fast roster.
"""

import numpy as np
import pytest

from repro.capture import captured_workloads
from repro.core import tracegen
from repro.study.substrate import SuiteSubstrate, get_substrate
from repro.suite import (
    ROSTER_COLUMNS,
    ResultStore,
    SuiteRegistry,
    SuiteRunner,
    default_registry,
)

REFS = 2_000
CORES = (1, 4)


def _tiny_registry(*, with_captured: bool = False,
                   refs: int = REFS) -> SuiteRegistry:
    reg = SuiteRegistry()
    for w in tracegen.make_suite(refs=refs)[:3]:
        reg.register(w, domain="synthetic-test", source="synthetic",
                     refs=refs)
    if with_captured:
        w = next(x for x in captured_workloads()
                 if x.name == "pal.stream.copy.1MiB")
        reg.register(w, domain="TPU-kernel/streaming", source="captured")
    return reg


# --------------------------------------------------------------------------
# Registry
# --------------------------------------------------------------------------
class TestRegistry:
    def test_default_roster_size_and_sources(self):
        reg = default_registry(refs=REFS)
        assert len(reg) >= 45
        synth = reg.by_source("synthetic")
        captured = reg.by_source("captured")
        assert len(synth) >= 18 and len(captured) >= 24
        assert len(synth) + len(captured) == len(reg)
        names = [e.name for e in reg]
        assert len(set(names)) == len(names)
        # every synthetic family and every kernel family is represented
        assert {e.workload.family for e in synth} == set(tracegen.FAMILIES)
        assert {e.workload.family for e in captured} == {
            "pallas-stream", "pallas-gather", "pallas-flashattn",
            "pallas-pagedkv", "pallas-moe", "pallas-ssm"}

    def test_duplicate_name_rejected(self):
        reg = _tiny_registry()
        w = reg.entries[0].workload
        with pytest.raises(ValueError, match="already registered"):
            reg.register(w, domain="x", source="synthetic")

    def test_bad_source_rejected(self):
        reg = SuiteRegistry()
        w = tracegen.make_suite(refs=REFS)[0]
        with pytest.raises(ValueError, match="synthetic|captured"):
            reg.register(w, domain="x", source="pallas")

    def test_fingerprint_is_content_addressed(self):
        reg = _tiny_registry()
        e = reg.entries[0]
        base = e.fingerprint(seed=0, cores=CORES)
        assert base == e.fingerprint(seed=0, cores=CORES)
        assert base != e.fingerprint(seed=1, cores=CORES)
        assert base != e.fingerprint(seed=0, cores=(1, 4, 16))
        assert base != reg.entries[1].fingerprint(seed=0, cores=CORES)
        # an explicit backend cross-check must not recall the other
        # backend's stored rows
        assert base != e.fingerprint(seed=0, cores=CORES,
                                     backend="reference")
        # different synthetic trace length -> different params -> new key
        other = _tiny_registry(refs=2 * REFS).entries[0]
        assert base != other.fingerprint(seed=0, cores=CORES)


# --------------------------------------------------------------------------
# Result store
# --------------------------------------------------------------------------
class TestResultStore:
    KEY = "ab" + "0" * 62

    def test_round_trip(self, tmp_path):
        store = ResultStore(tmp_path)
        assert store.get(self.KEY) is None
        rec = {"columns": ["a"], "row": [1.5]}
        store.put(self.KEY, rec)
        assert store.get(self.KEY) == rec
        assert self.KEY in store
        assert len(store) == 1
        assert (tmp_path / "ab" / f"{self.KEY}.json").exists()

    def test_corrupt_record_treated_as_missing(self, tmp_path):
        store = ResultStore(tmp_path)
        store.put(self.KEY, {"x": 1})
        (tmp_path / "ab" / f"{self.KEY}.json").write_text("{trunc")
        assert store.get(self.KEY) is None

    def test_non_hex_key_rejected(self, tmp_path):
        store = ResultStore(tmp_path)
        with pytest.raises(ValueError, match="hex"):
            store.get("../../etc/passwd")

    def test_env_var_default_root(self, tmp_path, monkeypatch):
        monkeypatch.setenv("REPRO_SUITE_STORE", str(tmp_path / "s"))
        assert ResultStore().root == tmp_path / "s"

    def test_keys_iteration(self, tmp_path):
        store = ResultStore(tmp_path)
        keys = [f"{i:02x}" + "0" * 62 for i in range(5)]
        for k in keys:
            store.put(k, {"schema": 1})
        assert list(store.keys()) == sorted(keys)
        assert list(ResultStore(tmp_path / "missing").keys()) == []

    def test_prune_by_schema(self, tmp_path):
        from repro.suite.registry import LEGACY_SCHEMA, SUITE_SCHEMA

        store = ResultStore(tmp_path)
        current = "aa" + "0" * 62
        legacy = "bb" + "0" * 62      # PR-3-era record: no schema marker
        stale = "cc" + "0" * 62       # explicit old schema
        corrupt = "dd" + "0" * 62
        store.put(current, {"schema": SUITE_SCHEMA, "row": [1]})
        store.put(legacy, {"columns": ["a"], "row": [2]})
        store.put(stale, {"schema": SUITE_SCHEMA - 1, "row": [3]})
        store.put(corrupt, {"x": 1})
        (tmp_path / "dd" / f"{corrupt}.json").write_text("{trunc")

        removed = store.prune(
            lambda key, rec: rec.get("schema", LEGACY_SCHEMA) == SUITE_SCHEMA)
        assert removed == 2
        assert current in store
        # markerless records read as LEGACY_SCHEMA — still servable by the
        # runner's recall path (same default), so gc must keep them
        assert legacy in store
        assert stale not in store
        assert corrupt not in store
        assert len(store) == 2

    def test_gc_cli(self, tmp_path, capsys):
        from repro.suite.__main__ import main
        from repro.suite.registry import SUITE_SCHEMA

        store = ResultStore(tmp_path)
        store.put("aa" + "0" * 62, {"schema": SUITE_SCHEMA, "row": [1]})
        store.put("bb" + "0" * 62, {"row": [2]})  # legacy marker: kept
        store.put("cc" + "0" * 62, {"schema": SUITE_SCHEMA + 1, "row": [3]})
        assert main(["--gc", "--store", str(tmp_path)]) == 0
        err = capsys.readouterr().err
        assert "pruned 1" in err and "2 kept" in err
        assert len(store) == 2


# --------------------------------------------------------------------------
# Runner
# --------------------------------------------------------------------------
class TestRunner:
    def test_roster_rows_and_histogram(self):
        runner = SuiteRunner(_tiny_registry(), cores=CORES)
        roster = runner.roster()
        assert roster.columns == ROSTER_COLUMNS
        assert len(roster) == 3
        hist = runner.histogram()
        assert sum(hist.column("total")) == 3
        assert sum(hist.column("synthetic")) == 3

    def test_store_recall_skips_simulation(self, tmp_path):
        reg = _tiny_registry()
        store = ResultStore(tmp_path)
        first = SuiteRunner(reg, cores=CORES, store=store)
        r1 = first.roster()
        assert first.stats.computed == 3 and first.stats.recalled == 0
        assert first.study.engine.stats.sim_runs > 0

        second = SuiteRunner(_tiny_registry(), cores=CORES, store=store)
        r2 = second.roster()
        assert second.stats.recalled == 3 and second.stats.computed == 0
        assert second.study.engine.stats.sim_runs == 0  # nothing re-simulated
        assert r1.to_csv() == r2.to_csv()

    def test_partial_store_simulates_only_missing(self, tmp_path):
        store = ResultStore(tmp_path)
        reg = _tiny_registry()
        warm = SuiteRunner(
            SuiteRegistry(entries=reg.entries[:2]), cores=CORES, store=store)
        warm.roster()

        full = SuiteRunner(_tiny_registry(), cores=CORES, store=store)
        full.roster()
        assert full.stats.recalled == 2 and full.stats.computed == 1

    def test_rosters_identical_with_and_without_store(self, tmp_path):
        with_store = SuiteRunner(_tiny_registry(), cores=CORES,
                                 store=ResultStore(tmp_path))
        without = SuiteRunner(_tiny_registry(), cores=CORES)
        assert with_store.roster().to_csv() == without.roster().to_csv()

    def test_divergence_detection(self):
        # mislabel a synthetic stream workload as a captured 2c kernel
        w = tracegen.make_suite(refs=REFS)[0]
        impostor = tracegen.Workload(
            name="pal.fake", family=w.family, expected_class="2c",
            ai_ops_per_access=w.ai_ops_per_access,
            instr_per_access=w.instr_per_access, gen=w.gen)
        reg = SuiteRegistry()
        reg.register(impostor, domain="x", source="captured")
        runner = SuiteRunner(reg, cores=CORES)
        bad = runner.divergent(source="captured")
        assert [rec["name"] for rec in bad] == ["pal.fake"]

    def test_captured_entry_flows_through_runner(self):
        runner = SuiteRunner(_tiny_registry(with_captured=True), cores=CORES)
        roster = runner.roster()
        rec = roster.records()[-1]
        assert rec["source"] == "captured"
        assert rec["assigned"] == "1a" == rec["expected"]
        assert rec["match"] == 1
        assert runner.divergent(source="captured") == []

    def test_record_carries_schema_marker(self, tmp_path):
        from repro.suite.registry import SUITE_SCHEMA

        store = ResultStore(tmp_path)
        runner = SuiteRunner(_tiny_registry(), cores=CORES, store=store)
        runner.roster()
        keys = list(store.keys())
        assert len(keys) == 3
        for key in keys:
            assert store.get(key)["schema"] == SUITE_SCHEMA

    def test_corrupt_record_injection_recomputes(self, tmp_path, capsys):
        """A truncated store record is skipped (counted + warned), the
        entry recomputes, and the rewrite heals the store."""
        from repro import obs

        store = ResultStore(tmp_path)
        r1 = SuiteRunner(_tiny_registry(), cores=CORES,
                         store=store).roster()

        # truncate one record mid-object, as a crashed writer would
        victim = sorted(tmp_path.glob("*/*.json"))[0]
        victim.write_text(victim.read_text()[:17])

        obs.reset_counters()
        second = SuiteRunner(_tiny_registry(), cores=CORES, store=store)
        r2 = second.roster()
        assert r2.to_csv() == r1.to_csv()  # result unchanged, just slower
        assert second.stats.recalled == 2 and second.stats.computed == 1
        c = obs.counters()
        assert c["store.corrupt"] == 1
        assert c["store.recall.warm"] == 2 and c["store.recall.cold"] == 1
        assert "skipping corrupt store record" in capsys.readouterr().err

        # the recompute overwrote the damaged record: pure recall now
        obs.reset_counters()
        third = SuiteRunner(_tiny_registry(), cores=CORES, store=store)
        assert third.roster().to_csv() == r1.to_csv()
        assert obs.counters()["store.recall.warm"] == 3
        assert "store.recall.cold" not in obs.counters()

    def test_wrong_shape_record_is_cold_recall(self, tmp_path):
        """A record that parses but has a short row is a cold recall."""
        from repro import obs

        store = ResultStore(tmp_path)
        SuiteRunner(_tiny_registry(), cores=CORES, store=store).roster()
        key = next(iter(store.keys()))
        rec = store.get(key)
        rec["row"] = rec["row"][:-1]
        store.put(key, rec)

        obs.reset_counters()
        second = SuiteRunner(_tiny_registry(), cores=CORES, store=store)
        second.roster()
        assert second.stats.computed == 1 and second.stats.recalled == 2
        assert obs.counters()["store.recall.cold"] == 1


class TestProcessFanOut:
    """Entry-level process-pool characterization (whole entries, not just
    core-sweep cells) must reproduce the sequential roster exactly."""

    @staticmethod
    def _trimmed_registry():
        """A cheap both-source subset that stays worker-reconstructible
        (the refs marker survives; workers rebuild the full default
        registry and characterize these entries by name)."""
        reg = default_registry(refs=REFS)
        keep = {"syn.stream.copy", "syn.chase.64MiB.e8",
                "pal.stream.copy.1MiB"}
        reg.entries = [e for e in reg.entries if e.name in keep]
        assert len(reg.entries) == 3
        return reg

    def test_processes_match_sequential(self, tmp_path):
        reg = self._trimmed_registry()
        seq = SuiteRunner(self._trimmed_registry(), cores=CORES)

        store = ResultStore(tmp_path)
        par = SuiteRunner(reg, cores=CORES, store=store, processes=2)
        # every entry must be eligible for the worker pool (a silent
        # in-process fallback would hide a reconstructibility regression)
        assert all(par._reconstructible(e) for e in reg)
        roster = par.roster()
        assert par.stats.computed == 3 and par.stats.recalled == 0
        assert roster.to_csv() == seq.roster().to_csv()
        # worker rows were persisted by the parent: a rerun recalls all
        rerun = SuiteRunner(reg, cores=CORES, store=store, processes=2)
        assert rerun.roster().to_csv() == roster.to_csv()
        assert rerun.stats.recalled == 3 and rerun.stats.computed == 0

    def test_modified_entries_fall_back_to_in_process(self, tmp_path):
        """Entries a worker's rebuilt registry would not reproduce —
        added names, or a swapped generator under an unchanged name —
        must be characterized in-process, never mischaracterized by the
        pool."""
        reg = self._trimmed_registry()
        # swap one entry's workload generator while keeping its name/params
        victim = reg.entries[0]
        donor = tracegen.make_suite(refs=REFS)[3]
        impostor = tracegen.Workload(
            name=victim.name, family=victim.workload.family,
            expected_class=victim.expected_class,
            ai_ops_per_access=victim.workload.ai_ops_per_access,
            instr_per_access=victim.workload.instr_per_access,
            gen=donor.gen)
        reg.entries[0] = SuiteRegistry().register(
            impostor, domain=victim.domain, source=victim.source,
            **dict(victim.params))
        runner = SuiteRunner(reg, cores=CORES, processes=2)
        assert not runner._reconstructible(reg.entries[0])
        assert runner._reconstructible(reg.entries[1])
        rows = runner.roster()
        # the swapped entry's row reflects the *impostor* generator
        solo = SuiteRunner(reg, cores=CORES)  # fully in-process
        assert rows.to_csv() == solo.roster().to_csv()

    def test_hand_built_registry_rejected(self):
        reg = SuiteRegistry()
        for w in tracegen.make_suite(refs=REFS)[:2]:
            reg.register(w, domain="x", source="synthetic")
        assert reg.refs is None
        runner = SuiteRunner(reg, cores=CORES, processes=2)
        with pytest.raises(ValueError, match="refs"):
            runner.compute_all()

    def test_single_process_value_is_sequential(self):
        reg = SuiteRegistry()
        for w in tracegen.make_suite(refs=REFS)[:2]:
            reg.register(w, domain="x", source="synthetic")
        runner = SuiteRunner(reg, cores=CORES, processes=1)
        assert len(runner.roster()) == 2  # no pickle requirement at 1

    @pytest.mark.parametrize("processes", [2, 4])
    def test_jax_backend_refuses_fan_out(self, processes):
        """The jax scan runs on the accelerator, which one process holds:
        any pool is refused before anything is simulated."""
        runner = SuiteRunner(self._trimmed_registry(), cores=CORES,
                             backend="jax", processes=processes)
        with pytest.raises(ValueError, match="--processes 1"):
            runner.compute_all()
        assert runner.stats.computed == 0

    def test_pool_workers_are_spawned_on_cpu(self, monkeypatch):
        """Workers inherit JAX_PLATFORMS=cpu, so none of them can claim the
        accelerator; the parent's environment is restored afterwards."""
        import os

        from repro.suite import runner as R

        seen = []
        real_pool = R.ProcessPoolExecutor

        def spy(*args, **kwargs):
            seen.append(os.environ.get("JAX_PLATFORMS"))
            return real_pool(*args, **kwargs)

        monkeypatch.setattr(R, "ProcessPoolExecutor", spy)
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        rows = SuiteRunner(self._trimmed_registry(), cores=CORES,
                           processes=2).roster()
        assert len(rows) == 3 and seen == ["cpu"]
        assert "JAX_PLATFORMS" not in os.environ


# --------------------------------------------------------------------------
# Substrate + CLI
# --------------------------------------------------------------------------
class TestSubstrateAndCLI:
    def test_suite_substrate_rows_start_with_name_class(self):
        sub = SuiteSubstrate(runner=SuiteRunner(_tiny_registry(),
                                                cores=CORES))
        assert isinstance(get_substrate("suite"), SuiteSubstrate)
        res = sub.characterize()
        assert res.columns[:2] == ("name", "class")
        assert len(res) == len(sub.items()) == 3
        classes = set(res.column("class"))
        assert classes <= {"1a", "1b", "1c", "2a", "2b", "2c"}

    def test_cli_list(self, capsys):
        from repro.suite.__main__ import main

        assert main(["--list"]) == 0
        out = capsys.readouterr().out
        assert "pal.flashattn.d64.kv20k" in out
        assert "pal.pagedkv.mqa.p32" in out
        assert "pal.moe.cold.64e" in out
        assert "pal.ssm.expand.512.d128" in out
        assert "syn.gemm.1.8xL1" in out
        assert "21 synthetic, 24 captured" in out

    @pytest.mark.slow  # full captured traces through the simulator (~20 s)
    def test_cli_fast_roster_deterministic_and_checked(self, tmp_path):
        from repro.suite.__main__ import main

        out1, out2 = tmp_path / "r1.csv", tmp_path / "r2.csv"
        store = str(tmp_path / "store")
        assert main(["--fast", "--check", "--store", store,
                     "--out", str(out1)]) == 0
        assert main(["--fast", "--check", "--store", store,
                     "--out", str(out2)]) == 0
        assert out1.read_bytes() == out2.read_bytes()
        text = out1.read_text()
        assert text.startswith("## suite_roster")
        assert "## class_histogram" in text
        # >= 45 entries spanning both sources
        roster = text.split("## class_histogram")[0].splitlines()
        assert sum(1 for l in roster if ",synthetic," in l) == 21
        assert sum(1 for l in roster if ",captured," in l) == 24


# --------------------------------------------------------------------------
# Roster sections (--sections scalability,energy)
# --------------------------------------------------------------------------
class TestRosterSections:
    def test_section_columns_appended_in_canonical_order(self):
        from repro.suite import ROSTER_COLUMNS, SECTION_COLUMNS

        # CLI order must not change the layout
        r1 = SuiteRunner(_tiny_registry(), cores=CORES,
                         sections=("energy", "scalability"))
        r2 = SuiteRunner(_tiny_registry(), cores=CORES,
                         sections=("scalability", "energy"))
        expect = ROSTER_COLUMNS + SECTION_COLUMNS["scalability"] \
            + SECTION_COLUMNS["energy"]
        assert r1.columns == r2.columns == expect
        res = r1.roster()
        assert res.columns == expect
        for rec in res.records():
            assert rec["host_speedup"] > 0
            assert rec["ndp_speedup"] > 0
            assert rec["host_mj"] > 0 and rec["ndp_mj"] > 0
            assert rec["ndp_energy_ratio"] == pytest.approx(
                rec["ndp_mj"] / rec["host_mj"], abs=2e-3)

    def test_unknown_section_rejected(self):
        with pytest.raises(ValueError, match="unknown roster section"):
            SuiteRunner(_tiny_registry(), cores=CORES,
                        sections=("bogus",))

    def test_sectioned_rows_get_their_own_store_keys(self, tmp_path):
        """Sectioned and plain rosters must not recall each other's
        records; plain keys are unchanged by the sections feature."""
        store = ResultStore(tmp_path)
        reg = _tiny_registry()
        e = reg.entries[0]
        base = e.fingerprint(seed=0, cores=CORES)
        assert base == e.fingerprint(seed=0, cores=CORES, sections=())
        assert base != e.fingerprint(seed=0, cores=CORES,
                                     sections=("scalability",))

        plain = SuiteRunner(_tiny_registry(), cores=CORES, store=store)
        plain.roster()
        sectioned = SuiteRunner(_tiny_registry(), cores=CORES, store=store,
                                sections=("scalability",))
        sectioned.roster()
        assert sectioned.stats.recalled == 0  # no cross-recall
        # and each rerun recalls only its own flavor
        rerun = SuiteRunner(_tiny_registry(), cores=CORES, store=store,
                            sections=("scalability",))
        rerun.roster()
        assert rerun.stats.recalled == 3 and rerun.stats.computed == 0

    def test_sections_stable_across_recall(self, tmp_path):
        store = ResultStore(tmp_path)
        kw = dict(cores=CORES, store=store, sections=("energy",))
        cold = SuiteRunner(_tiny_registry(), **kw).roster().to_csv()
        warm = SuiteRunner(_tiny_registry(), **kw).roster().to_csv()
        assert cold == warm

    def test_cli_sections_flag(self, capsys, tmp_path):
        from repro.suite.__main__ import main

        assert main(["--refs", str(REFS), "--cores", "1,4", "--no-store",
                     "--sections", "scalability"]) == 0
        out = capsys.readouterr().out
        header = out.splitlines()[1]
        assert header.endswith("lfmr_slope,host_speedup,ndp_speedup")

    def test_cli_rejects_unknown_section(self, capsys):
        from repro.suite.__main__ import main

        with pytest.raises(SystemExit):
            main(["--sections", "nope"])

    def test_cli_filter_requires_models_section(self, capsys):
        from repro.suite.__main__ import main

        assert main(["--filter", "qwen", "--no-store"]) == 2
        err = capsys.readouterr().err
        assert "--filter only applies to the models roster" in err

    def test_cli_filter_with_check_warns_about_unchecked_entries(
            self, capsys, monkeypatch):
        from repro.suite import __main__ as cli

        # stop before any simulation: the warning must be emitted during
        # argument handling, not after the (expensive) roster run
        def boom(*a, **kw):
            raise RuntimeError("stop-after-warning")

        monkeypatch.setattr(cli, "registry_for", boom)
        with pytest.raises(RuntimeError, match="stop-after-warning"):
            cli.main(["--sections", "models", "--filter", "qwen",
                      "--check", "--no-store"])
        err = capsys.readouterr().err
        assert "--check only sees the filtered entries" in err


class TestCapturedPoolFallback:
    def test_hand_registered_captured_entry_runs_in_process(self, tmp_path):
        """A captured entry that default_registry would NOT rebuild (a
        hand-registered extra geometry) must be characterized in-process
        by the pool path, alongside pool-eligible entries, with rows
        identical to a fully sequential run."""
        from repro.capture import captured_workloads
        from repro.kernels.stream import capture as stream_capture
        from repro.core.tracegen import TraceSpec, Workload
        from repro.capture.grid import walk

        def build():
            reg = default_registry(refs=REFS)
            keep = {"syn.stream.copy", "pal.stream.copy.1MiB"}
            reg.entries = [e for e in reg.entries if e.name in keep]

            def gen(cores, rng):
                cap = stream_capture.capture("copy", 2**17, cores=cores)
                return TraceSpec(walk(cap).addresses, l3_factor=1.0,
                                 mlp=8.0, dram_rows_irregular=False)

            extra = Workload(
                name="pal.stream.copy.tiny", family="pallas-stream",
                expected_class="1a", ai_ops_per_access=0.0,
                instr_per_access=2.0, gen=gen)
            reg.register(extra, domain="TPU-kernel/streaming",
                         source="captured", op="copy", n_elems=2**17)
            return reg

        par = SuiteRunner(build(), cores=CORES, processes=2)
        assert not par._reconstructible(
            next(e for e in par.registry
                 if e.name == "pal.stream.copy.tiny"))
        rows = par.roster()
        assert len(rows) == 3
        seq = SuiteRunner(build(), cores=CORES)
        assert rows.to_csv() == seq.roster().to_csv()
        rec = next(r for r in rows.records()
                   if r["name"] == "pal.stream.copy.tiny")
        assert rec["assigned"] == "1a"
