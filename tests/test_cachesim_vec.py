"""Differential harness: vectorized vs reference cache-simulation backend.

The vectorized backend's contract is *counter identity*: for every cell the
pipeline can produce, ``level_hits`` / ``level_misses`` /
``prefetch_issued`` / ``prefetch_useful`` (and the derived LFMR/MPKI) must
equal the reference per-line loop exactly — a fast-but-wrong simulator
would silently corrupt every downstream classification.  The matrix here
sweeps all 7 workload families x {host, host+pf, host+nuca, ndp} x
``l3_factor`` in {1, 1/4, 1/16}, through both the single-cell
``simulate`` entry point and the batched single pass ``simulate_batch``
(which shares level prefixes and caps same-set-count scans at the maximum
requested associativity).
"""

import threading
import time

import numpy as np
import pytest

from repro import obs
from repro.core import cachesim, cachesim_vec, tracegen

REFS = 4_000  # short traces: the matrix is 84 cells x 2 backends

CONFIGS = {
    "host": lambda: cachesim.host_config(4),
    "host+pf": lambda: cachesim.host_config(4, prefetcher=True),
    "host+nuca": lambda: cachesim.host_config(4, nuca_mb_per_core=2.0),
    "ndp": lambda: cachesim.ndp_config(4),
}
L3_FACTORS = (1.0, 1.0 / 4, 1.0 / 16)


def _one_per_family():
    byfam = {}
    for w in tracegen.make_suite(refs=REFS):
        byfam.setdefault(w.family, w)
    assert set(byfam) == set(tracegen.FAMILIES)
    return byfam


_FAMILY_WORKLOADS = _one_per_family()


# The contended family's repeat-heavy traces are the matrix's heaviest
# cells (each runs the reference per-line loop too): slow-marked out of
# the fast local loop; CI (`-m "not timing"`) still runs them.
_FAMILY_PARAMS = [
    pytest.param(f, marks=pytest.mark.slow) if f == "contended" else f
    for f in sorted(tracegen.FAMILIES)
]


class TestDifferentialMatrix:
    @pytest.mark.parametrize("family", _FAMILY_PARAMS)
    @pytest.mark.parametrize("config_name", sorted(CONFIGS))
    @pytest.mark.parametrize("l3_factor", L3_FACTORS)
    def test_counters_identical(self, family, config_name, l3_factor):
        w = _FAMILY_WORKLOADS[family]
        spec = w.trace(4)
        kwargs = dict(
            ai_ops_per_access=w.ai_ops_per_access,
            instr_per_access=w.instr_per_access,
            l3_factor=l3_factor,
        )
        cfg = CONFIGS[config_name]()
        ref = cachesim.simulate(spec.addresses, cfg, backend="reference",
                                **kwargs)
        vec = cachesim.simulate(spec.addresses, cfg, backend="vectorized",
                                **kwargs)
        assert vec.level_hits == ref.level_hits
        assert vec.level_misses == ref.level_misses
        assert vec.prefetch_issued == ref.prefetch_issued
        assert vec.prefetch_useful == ref.prefetch_useful
        assert vec.lines_touched == ref.lines_touched
        assert vec == ref  # dataclass-wide: accesses/instructions/ai/name
        assert vec.lfmr == ref.lfmr and vec.mpki == ref.mpki

    def test_empty_trace(self):
        cfg = cachesim.host_config(1)
        empty = np.empty(0, dtype=np.int64)
        ref = cachesim.simulate(empty, cfg, backend="reference")
        vec = cachesim.simulate(empty, cfg, backend="vectorized")
        assert ref == vec
        assert vec.level_misses == (0, 0, 0)

    def test_single_access(self):
        cfg = cachesim.ndp_config()
        ref = cachesim.simulate(np.array([42]), cfg, backend="reference")
        vec = cachesim.simulate(np.array([42]), cfg, backend="vectorized")
        assert ref == vec

    def test_adversarial_single_set_thrash(self):
        """Every access lands in one L1 set, cycling ways+1 lines: the
        stack-distance path must agree with the reference on pure conflict
        misses (no capacity slack, long scan windows)."""
        cfg = cachesim.host_config(1)
        l1 = cfg.levels[0]
        stride = l1.sets * cachesim.WORDS_PER_LINE
        lines = np.arange(l1.ways + 1) * stride
        addr = np.tile(lines, 200)
        ref = cachesim.simulate(addr, cfg, backend="reference")
        vec = cachesim.simulate(addr, cfg, backend="vectorized")
        assert ref == vec
        assert vec.l1_misses == addr.size  # ways+1-cycle always misses


class TestSimulateBatch:
    """The batched single pass must be counter-identical to per-cell runs
    across the full family x hierarchy x l3_factor matrix."""

    @pytest.mark.parametrize("family", _FAMILY_PARAMS)
    def test_full_matrix_batch_identity(self, family):
        w = _FAMILY_WORKLOADS[family]
        spec = w.trace(4)
        kwargs = dict(
            ai_ops_per_access=w.ai_ops_per_access,
            instr_per_access=w.instr_per_access,
        )
        reqs = [(CONFIGS[name](), f)
                for name in sorted(CONFIGS) for f in L3_FACTORS]
        batch = cachesim.simulate_batch(
            spec.addresses, [cfg for cfg, _ in reqs],
            l3_factor=[f for _, f in reqs],
            backend="vectorized", **kwargs)
        ref_batch = cachesim.simulate_batch(
            spec.addresses, [cfg for cfg, _ in reqs],
            l3_factor=[f for _, f in reqs],
            backend="reference", **kwargs)
        assert len(batch) == len(reqs)
        for (cfg, f), vec, ref in zip(reqs, batch, ref_batch):
            assert vec == ref, (cfg.name, f)
            single = cachesim.simulate(
                spec.addresses, cfg, l3_factor=f,
                backend="reference", **kwargs)
            assert vec == single, (cfg.name, f)

    def test_shared_sets_different_ways_thresholding(self):
        """Two LLC geometries with the same set count but different
        associativity must share one capped scan and still match the
        reference per-config (LRU-inclusion thresholding)."""
        l1 = cachesim.CacheLevelConfig(32 * 1024, 8)
        a = cachesim.HierarchyConfig(
            levels=(l1, cachesim.CacheLevelConfig(8 * 2**20, 16)), name="a")
        b = cachesim.HierarchyConfig(
            levels=(l1, cachesim.CacheLevelConfig(4 * 2**20, 8)), name="b")
        c = cachesim.HierarchyConfig(
            levels=(l1, cachesim.CacheLevelConfig(2 * 2**20, 4)), name="c")
        assert a.levels[1].sets == b.levels[1].sets == c.levels[1].sets

        w = _FAMILY_WORKLOADS["irregular"]
        spec = w.trace(1)
        batch = cachesim_vec.simulate_batch(spec.addresses, [a, b, c])
        for cfg, vec in zip((a, b, c), batch):
            ref = cachesim.simulate(spec.addresses, cfg, backend="reference")
            assert vec == ref, cfg.name

    def test_scalar_and_sequence_l3_factor(self):
        w = _FAMILY_WORKLOADS["stream"]
        spec = w.trace(1)
        cfgs = [cachesim.host_config(1), cachesim.host_config(1)]
        shared = cachesim_vec.simulate_batch(spec.addresses, cfgs,
                                             l3_factor=0.25)
        listed = cachesim_vec.simulate_batch(spec.addresses, cfgs,
                                             l3_factor=[0.25, 0.25])
        assert shared == listed
        with pytest.raises(ValueError, match="l3_factor"):
            cachesim_vec.simulate_batch(spec.addresses, cfgs,
                                        l3_factor=[0.25])

    def test_empty_batch_and_names(self):
        w = _FAMILY_WORKLOADS["stream"]
        spec = w.trace(1)
        assert cachesim_vec.simulate_batch(spec.addresses, []) == []
        out = cachesim_vec.simulate_batch(
            spec.addresses, [cachesim.ndp_config(1)], names=["custom"])
        assert out[0].name == "custom"

    def test_reference_backend_batch_dispatch(self):
        w = _FAMILY_WORKLOADS["chase"]
        spec = w.trace(1)
        cfgs = [cachesim.host_config(1), cachesim.ndp_config(1)]
        ref = cachesim.simulate_batch(spec.addresses, cfgs,
                                      backend="reference")
        vec = cachesim.simulate_batch(spec.addresses, cfgs,
                                      backend="vectorized")
        assert ref == vec
        with pytest.raises(ValueError, match="unknown backend"):
            cachesim.simulate_batch(spec.addresses, cfgs, backend="zsim")


class TestBackendSelection:
    def test_env_var_resolution(self, monkeypatch):
        monkeypatch.setenv("REPRO_SIM_BACKEND", "reference")
        assert cachesim.default_backend() == "reference"
        monkeypatch.setenv("REPRO_SIM_BACKEND", "vectorized")
        assert cachesim.default_backend() == "vectorized"
        monkeypatch.delenv("REPRO_SIM_BACKEND")
        assert cachesim.default_backend() == "vectorized"

    def test_invalid_env_var_rejected(self, monkeypatch):
        monkeypatch.setenv("REPRO_SIM_BACKEND", "zsim")
        with pytest.raises(ValueError, match="REPRO_SIM_BACKEND"):
            cachesim.default_backend()

    def test_invalid_backend_argument_rejected(self):
        with pytest.raises(ValueError, match="unknown backend"):
            cachesim.simulate(np.arange(8), cachesim.host_config(),
                              backend="zsim")

    def test_engine_rejects_unknown_backend(self):
        from repro.study import SimEngine

        with pytest.raises(ValueError, match="unknown backend"):
            SimEngine(backend="zsim")

    def test_engine_backends_agree(self):
        from repro.study import SimEngine

        w = _FAMILY_WORKLOADS["contended"]
        cfg = cachesim.host_config(4)
        ref = SimEngine(backend="reference").simulate(w, 4, cfg)
        vec = SimEngine(backend="vectorized").simulate(w, 4, cfg)
        assert ref == vec


class TestTraceMemo:
    """The keyed profile/miss-stream memo that replaced the L1-filter
    cache: identity-keyed, CRC-revalidated, LRU-bounded, thread-safe."""

    def test_identity_keyed_reuse_is_exact(self):
        """The same trace array through host, NDP and pf hierarchies
        shares level prefixes through the memo; counters still match
        per-config reference runs."""
        w = _FAMILY_WORKLOADS["l1cap"]
        spec = w.trace(1)
        for cfg in (cachesim.host_config(1), cachesim.ndp_config(1),
                    cachesim.host_config(1, prefetcher=True),
                    cachesim.host_config(1, nuca_mb_per_core=2.0)):
            ref = cachesim.simulate(spec.addresses, cfg, backend="reference")
            vec = cachesim.simulate(spec.addresses, cfg, backend="vectorized")
            assert ref == vec, cfg.name

    def test_memo_reuses_shared_prefixes(self):
        """A second hierarchy over the same trace recomputes only the
        levels its geometry prefix does not share."""
        addr = np.arange(50_000, dtype=np.int64) % 9973
        memo_count_before = len(cachesim_vec._MEMOS)
        cachesim_vec.simulate(addr, cachesim.host_config(1))
        memo = next(m for m in cachesim_vec._MEMOS if m.ref is addr)
        levels_after_host = set(memo.levels)
        cachesim_vec.simulate(addr, cachesim.ndp_config(1))
        # NDP's single level is host's L1 prefix: nothing new computed
        assert set(memo.levels) == levels_after_host
        cachesim_vec.simulate(addr, cachesim.host_config(1),
                              l3_factor=0.25)
        # the scaled-LLC variant adds exactly one new level result
        assert len(memo.levels) == len(levels_after_host) + 1
        assert len(cachesim_vec._MEMOS) <= memo_count_before + 1

    def test_memo_is_bounded_by_bytes(self, monkeypatch):
        """The pool is bounded by resident derived bytes, not entry
        count; the most recent trace always survives eviction."""
        monkeypatch.setattr(cachesim_vec, "_MEMOS", [])
        monkeypatch.setattr(cachesim_vec, "_MEMO_BYTES_LAST", 0)
        monkeypatch.setattr(cachesim_vec, "_MEMO_MAX_BYTES", 64 * 1024)
        arrays = [np.arange(2048, dtype=np.int64) * 3 + 512 * i
                  for i in range(8)]
        for a in arrays:
            cachesim_vec.simulate(a, cachesim.host_config(1))
        # re-measure after the last memo filled with derived arrays
        cachesim_vec.simulate(arrays[-1], cachesim.host_config(1))
        resident = sum(m.nbytes() for m in cachesim_vec._MEMOS)
        assert (resident <= cachesim_vec._MEMO_MAX_BYTES
                or len(cachesim_vec._MEMOS) == 1)
        assert cachesim_vec._MEMOS[-1].ref is arrays[-1]

    def test_memo_evicts_under_byte_pressure(self, monkeypatch):
        """Satellite: megaref traces cannot OOM the LRU — a pool past the
        byte budget evicts, counts ``memo.evict`` and keeps the
        ``memo.bytes`` gauge at the post-eviction resident total."""
        from repro import obs

        monkeypatch.setattr(cachesim_vec, "_MEMOS", [])
        monkeypatch.setattr(cachesim_vec, "_MEMO_BYTES_LAST", 0)
        monkeypatch.setattr(cachesim_vec, "_MEMO_MAX_BYTES", 32 * 1024)
        obs.reset_counters()
        arrays = [np.arange(4096, dtype=np.int64) * 5 + 777 * i
                  for i in range(6)]
        for a in arrays:
            cachesim_vec.simulate(a, cachesim.host_config(1))
        c = obs.counters()
        assert c.get("memo.evict", 0) >= 1
        # the gauge equals the pool total measured at the last lookup
        assert c.get("memo.bytes", 0) == cachesim_vec._MEMO_BYTES_LAST
        assert (cachesim_vec._MEMO_BYTES_LAST
                <= cachesim_vec._MEMO_MAX_BYTES)

    def test_in_place_mutation_recomputes(self):
        """Mutating an address array between calls must not serve stale
        counters from the identity-keyed memo (CRC revalidation)."""
        addr = np.arange(4096, dtype=np.int64)
        cfg = cachesim.host_config(1)
        first = cachesim_vec.simulate(addr, cfg)
        addr[:] = 0  # same object, new content: one line, all hits
        second = cachesim_vec.simulate(addr, cfg)
        assert second != first
        assert second == cachesim.simulate(addr, cfg, backend="reference")
        assert second.lines_touched == 1

    def test_single_element_mutation_recomputes(self):
        """The full-buffer fingerprint catches a one-element change at an
        arbitrary (non-grid) index."""
        addr = np.arange(4096, dtype=np.int64)
        cfg = cachesim.host_config(1)
        first = cachesim_vec.simulate(addr, cfg)
        addr[17] = 10_000_000  # one extra distinct line
        second = cachesim_vec.simulate(addr, cfg)
        assert second.lines_touched == first.lines_touched + 1
        assert second == cachesim.simulate(addr, cfg, backend="reference")

    def test_mutation_recomputes_on_batch_path(self):
        """The CRC path guards simulate_batch exactly like simulate."""
        addr = (np.arange(8192, dtype=np.int64) * 7) % 4096
        cfgs = [cachesim.host_config(1), cachesim.ndp_config(1)]
        cachesim_vec.simulate_batch(addr, cfgs)
        addr[123] = 99_999_999
        second = cachesim_vec.simulate_batch(addr, cfgs)
        for cfg, vec in zip(cfgs, second):
            assert vec == cachesim.simulate(addr, cfg, backend="reference")

    def test_thread_safety_under_sweep_parallel(self):
        """Concurrent engine sweeps over many traces (and concurrent
        batches over the *same* trace) must neither corrupt counters nor
        grow the memo past its bound."""
        from repro.study import SimEngine

        w = _FAMILY_WORKLOADS["blocked"]
        expected = {
            c: cachesim.simulate(
                w.trace(c).addresses, cachesim.host_config(c),
                ai_ops_per_access=w.ai_ops_per_access,
                instr_per_access=w.instr_per_access,
                l3_factor=w.trace(c).l3_factor, backend="reference")
            for c in (1, 4, 16)
        }

        engine = SimEngine(backend="vectorized")
        spec = w.trace(4)
        same_trace_out: list = []

        def hammer_same_trace():
            out = cachesim_vec.simulate_batch(
                spec.addresses,
                [cachesim.host_config(4), cachesim.ndp_config(4)],
                l3_factor=spec.l3_factor)
            same_trace_out.append(out)

        threads = [threading.Thread(target=hammer_same_trace)
                   for _ in range(4)]
        for t in threads:
            t.start()
        sims = engine.sweep_parallel(w, (1, 4, 16), cachesim.host_config,
                                     max_workers=4)
        for t in threads:
            t.join()

        for c, sim in zip((1, 4, 16), sims):
            assert (sim.level_hits, sim.level_misses) == (
                expected[c].level_hits, expected[c].level_misses)
        ref_host4 = cachesim.simulate(spec.addresses, cachesim.host_config(4),
                                      l3_factor=spec.l3_factor,
                                      backend="reference")
        for out in same_trace_out:
            assert out[0].level_hits == ref_host4.level_hits
            assert out[0].level_misses == ref_host4.level_misses
        # pool invariant after a fresh lookup re-measures the pool:
        # within the byte budget, or a single over-budget survivor
        cachesim_vec.simulate_batch(spec.addresses,
                                    [cachesim.host_config(4)],
                                    l3_factor=spec.l3_factor)
        resident = sum(m.nbytes() for m in cachesim_vec._MEMOS)
        assert (resident <= cachesim_vec._MEMO_MAX_BYTES
                or len(cachesim_vec._MEMOS) == 1)


# --------------------------------------------------------------------------
# Stage spans: where the profile build and the scan spend their time
# --------------------------------------------------------------------------
class TestStageSpans:
    def _run(self, traced):
        """One segmented ``simulate_many`` on the jax scan, over fresh
        copies of three families' traces (every profile is built)."""
        reqs = [(_FAMILY_WORKLOADS[f].trace(4).addresses.copy(),
                 [cachesim.host_config(4), cachesim.ndp_config(4)], {})
                for f in ("contended", "irregular", "stream")]
        if traced is None:
            return cachesim_vec.simulate_many(reqs, scan="jax")
        obs.enable(traced)
        try:
            return cachesim_vec.simulate_many(reqs, scan="jax")
        finally:
            obs.disable()

    def test_stages_nest_in_their_layer_and_change_nothing(self, tmp_path):
        pytest.importorskip("jax")
        from _obs_spans import parents_by_name, span_events

        off = self._run(None)
        trace = tmp_path / "t.jsonl"
        on = self._run(trace)
        assert [[(s.level_hits, s.level_misses, s.lines_touched)
                 for s in sims] for sims in on] == \
            [[(s.level_hits, s.level_misses, s.lines_touched)
              for s in sims] for sims in off]
        parents = parents_by_name(span_events(trace))
        want = {"sim.profile.collapse": {"sim.profile"},
                "sim.profile.order": {"sim.profile"},
                "sim.profile.prev": {"sim.profile"},
                "sim.scan.layout": {"sim.scan"},
                "sim.scan.launch": {"sim.scan"},
                "sim.scan.wait": {"sim.scan.launch"}}
        for name, allowed in want.items():
            assert parents.get(name) == allowed, name


@pytest.mark.slow
@pytest.mark.timing  # wall-clock ratio: flaky on shared CI runners
def test_vectorized_speedup_60k_host_cell():
    """Acceptance: a 60k-ref host-config cell runs >= 10x faster on the
    vectorized backend than on the reference loop."""
    w = next(x for x in tracegen.make_suite(refs=60_000)
             if x.family == "stream")
    spec = w.trace(1)
    cfg = cachesim.host_config(1)

    cachesim.simulate(spec.addresses, cfg, backend="vectorized")  # warm
    t_vec = min(
        _timed(lambda: cachesim_vec.simulate(
            np.array(spec.addresses), cfg))  # fresh array: no L1-cache hit
        for _ in range(3)
    )
    t_ref = min(
        _timed(lambda: cachesim.simulate(spec.addresses, cfg,
                                         backend="reference"))
        for _ in range(2)
    )
    assert t_vec < 1.0, f"vectorized 60k cell took {t_vec:.2f}s"
    assert t_ref / t_vec >= 10.0, (
        f"speedup {t_ref / t_vec:.1f}x < 10x (ref {t_ref*1e3:.0f}ms, "
        f"vec {t_vec*1e3:.0f}ms)")


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0
