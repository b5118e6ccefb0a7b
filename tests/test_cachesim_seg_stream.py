"""Differential matrix for segmented + streaming cache simulation.

Three layers of the segmented/streaming StreamProfile rebuild are pinned
here against the per-trace in-memory backend (itself differentially
gated against the reference loop in ``test_cachesim_vec.py``):

- ``cachesim_vec.simulate_many``: many traces in one segmented pass —
  counter-identical to per-trace ``simulate_batch`` over the full
  workload-family x hierarchy matrix;
- ``cachesim_stream.simulate_chunked``: fixed-memory chunk streaming —
  counter-identical to the in-memory path for any chunk size, spill
  budget or input form (ndarray or block generator), and bounded-memory
  on a 10M-ref megaref trace;
- ``scan="jax"``: the jitted window scan — counter-identical to the
  NumPy scan, skipped cleanly when jax is absent;

plus the engine-level contract: ``SimEngine.simulate_cells`` equals
per-cell ``simulate``, shares core-invariant traces, and shares cells
across engines through a content-addressed profile store.
"""

import numpy as np
import pytest

from repro import obs
from repro.core import cachesim, cachesim_vec, tracegen
from repro.core.cachesim_stream import simulate_chunked
from repro.core.tracegen import TraceSpec, Workload

REFS = 4_000

CONFIGS = {
    "host": lambda: cachesim.host_config(4),
    "host+pf": lambda: cachesim.host_config(4, prefetcher=True),
    "host+nuca": lambda: cachesim.host_config(4, nuca_mb_per_core=2.0),
    "ndp": lambda: cachesim.ndp_config(4),
}


def _one_per_family():
    byfam = {}
    for w in tracegen.make_suite(refs=REFS):
        byfam.setdefault(w.family, w)
    assert set(byfam) == set(tracegen.FAMILIES)
    return byfam


_FAMILY_WORKLOADS = _one_per_family()


def _counters(sim):
    return (sim.level_hits, sim.level_misses, sim.lines_touched,
            sim.prefetch_issued, sim.prefetch_useful, sim.accesses,
            sim.instructions)


# --------------------------------------------------------------------------
# Segmented batching: one simulate_many pass over every family at once
# --------------------------------------------------------------------------
class TestSegmentedMany:
    def _requests(self):
        """One request per family, all four hierarchies per request.
        Fresh array copies: every trace misses the memo pool, so the
        segmented (not the warm per-trace) path does the work."""
        reqs, expected_args = [], []
        for i, family in enumerate(sorted(_FAMILY_WORKLOADS)):
            w = _FAMILY_WORKLOADS[family]
            addr = w.trace(4).addresses.copy()
            configs = [CONFIGS[k]() for k in sorted(CONFIGS)]
            opts = {
                "ai_ops_per_access": w.ai_ops_per_access,
                "instr_per_access": w.instr_per_access,
                # distinct factors across requests: segmented grouping
                # must keep per-request LLC scalings apart
                "l3_factor": (1.0, 0.25, 1.0, 1.0 / 16),
            }
            reqs.append((addr, configs, opts))
            expected_args.append((addr, configs, opts))
        return reqs, expected_args

    def test_matrix_identical_to_per_trace_batch(self):
        reqs, expected_args = self._requests()
        got = cachesim_vec.simulate_many(reqs)
        assert len(got) == len(reqs)
        for (addr, configs, opts), sims in zip(expected_args, got):
            want = cachesim_vec.simulate_batch(addr.copy(), configs, **opts)
            assert [_counters(s) for s in sims] == \
                [_counters(s) for s in want]
            assert [s.lfmr for s in sims] == [s.lfmr for s in want]
            assert [s.mpki for s in sims] == [s.mpki for s in want]

    def test_segmented_profiles_cover_unique_geometries_once(self):
        reqs, _ = self._requests()
        obs.reset_counters()
        cachesim_vec.simulate_many(reqs)
        c = obs.counters()
        # the pinned perf shape: profiles are built per unique geometry
        # group, never per trace
        assert 0 < c["profile.scan"] <= c["profile.geom"]
        assert c["profile.scan"] < len(reqs) * 3  # < one per trace-level

    def test_empty_and_single_requests(self):
        assert cachesim_vec.simulate_many([]) == []
        w = _FAMILY_WORKLOADS[sorted(_FAMILY_WORKLOADS)[0]]
        addr = w.trace(4).addresses.copy()
        cfg = cachesim.host_config(4)
        [sims] = cachesim_vec.simulate_many([(addr, [cfg], {})])
        [want] = [cachesim.simulate(addr.copy(), cfg,
                                    backend="vectorized")]
        assert _counters(sims[0]) == _counters(want)

    def test_reference_spot_check(self):
        """One segmented cell against the per-line reference loop: the
        identity chain bottoms out at the scalar simulator."""
        w = _FAMILY_WORKLOADS["stream"]
        addr = w.trace(4).addresses.copy()
        cfg = cachesim.host_config(4, prefetcher=True)
        [sims] = cachesim_vec.simulate_many([(addr, [cfg], {})])
        ref = cachesim.simulate(addr.copy(), cfg, backend="reference")
        assert _counters(sims[0]) == _counters(ref)


# --------------------------------------------------------------------------
# Chunk streaming: fixed memory, any chunk size, any input form
# --------------------------------------------------------------------------
class TestChunkedStreaming:
    @pytest.mark.parametrize("config_name", sorted(CONFIGS))
    @pytest.mark.parametrize("family", sorted(tracegen.FAMILIES))
    def test_chunked_matches_in_memory(self, family, config_name):
        w = _FAMILY_WORKLOADS[family]
        addr = w.trace(4).addresses
        cfg = CONFIGS[config_name]()
        kwargs = dict(ai_ops_per_access=w.ai_ops_per_access,
                      instr_per_access=w.instr_per_access,
                      l3_factor=0.5 if cfg.shared_llc else 1.0)
        want = cachesim.simulate(addr.copy(), cfg, backend="vectorized",
                                 **kwargs)
        got = simulate_chunked(addr.copy(), cfg, chunk=997, **kwargs)
        assert _counters(got) == _counters(want)
        assert got.lfmr == want.lfmr and got.mpki == want.mpki

    @pytest.mark.parametrize("chunk", [1, 63, 4_096, 10**9])
    def test_chunk_size_invariance(self, chunk):
        w = _FAMILY_WORKLOADS["irregular"]
        addr = w.trace(4).addresses
        cfg = cachesim.host_config(4)
        want = cachesim.simulate(addr.copy(), cfg, backend="vectorized")
        got = simulate_chunked(addr.copy(), cfg, chunk=chunk)
        assert _counters(got) == _counters(want)

    def test_spill_to_disk_preserves_counters(self):
        w = _FAMILY_WORKLOADS["contended"]
        addr = w.trace(4).addresses
        cfg = cachesim.host_config(4, prefetcher=True)
        want = cachesim.simulate(addr.copy(), cfg, backend="vectorized")
        got = simulate_chunked(addr.copy(), cfg, chunk=512, spill_bytes=1)
        assert _counters(got) == _counters(want)

    def test_generator_input_never_materializes(self):
        w = _FAMILY_WORKLOADS["stream"]
        addr = w.trace(4).addresses
        cfg = cachesim.ndp_config(4)
        want = cachesim.simulate(addr.copy(), cfg, backend="vectorized")

        def blocks():
            for lo in range(0, addr.size, 777):
                yield addr[lo:lo + 777].copy()

        got = simulate_chunked(blocks(), cfg, chunk=777)
        assert _counters(got) == _counters(want)

    def test_empty_trace(self):
        cfg = cachesim.host_config(1)
        got = simulate_chunked(np.empty(0, dtype=np.int64), cfg)
        assert got.accesses == 0
        assert got.level_misses == (0, 0, 0)


# --------------------------------------------------------------------------
# jax-jitted window scan (skips cleanly without jax)
# --------------------------------------------------------------------------
class TestJaxScan:
    def test_jax_backend_counter_identical(self):
        pytest.importorskip("jax")
        w = _FAMILY_WORKLOADS["contended"]
        addr = w.trace(4).addresses
        for cfg in (cachesim.host_config(4),
                    cachesim.host_config(4, prefetcher=True)):
            want = cachesim.simulate(addr.copy(), cfg,
                                     backend="vectorized")
            got = cachesim.simulate(addr.copy(), cfg, backend="jax")
            assert _counters(got) == _counters(want)

    def test_chunked_jax_scan(self):
        pytest.importorskip("jax")
        w = _FAMILY_WORKLOADS["irregular"]
        addr = w.trace(4).addresses
        cfg = cachesim.host_config(4)
        want = simulate_chunked(addr.copy(), cfg, chunk=1_024)
        got = simulate_chunked(addr.copy(), cfg, chunk=1_024, scan="jax")
        assert _counters(got) == _counters(want)

    def test_segmented_jax_scan(self):
        pytest.importorskip("jax")
        reqs = []
        for family in ("stream", "irregular"):
            w = _FAMILY_WORKLOADS[family]
            reqs.append((w.trace(4).addresses.copy(),
                         [cachesim.host_config(4)], {}))
        plain = cachesim_vec.simulate_many(
            [(a.copy(), c, o) for a, c, o in reqs])
        jaxed = cachesim_vec.simulate_many(reqs, scan="jax")
        for ps, js in zip(plain, jaxed):
            assert [_counters(s) for s in ps] == [_counters(s) for s in js]

    def test_stream_past_int32_guard_raises(self, monkeypatch):
        """A stream too long for int32 window offsets is refused, never
        handed to the NumPy scan in silence."""
        pytest.importorskip("jax")
        monkeypatch.setattr(cachesim_vec, "_JAX_MAX_M", 16)
        addr = _FAMILY_WORKLOADS["contended"].trace(4).addresses
        with pytest.raises(ValueError, match="scan='jax'"):
            cachesim.simulate(addr.copy(), cachesim.host_config(4),
                              backend="jax")

    def test_failed_jax_import_raises(self, monkeypatch):
        """Without jax the jax scan raises instead of running NumPy."""
        import sys

        monkeypatch.setattr(cachesim_vec, "_JAX_SCAN", [])
        monkeypatch.setitem(sys.modules, "jax", None)
        addr = _FAMILY_WORKLOADS["contended"].trace(4).addresses
        with pytest.raises(ImportError):
            cachesim.simulate(addr.copy(), cachesim.host_config(4),
                              backend="jax")

    def test_clear_memo_forces_a_fresh_scan(self):
        """After clear_memo the same trace array is profiled again (so a
        second backend's run is its own), and the memo.bytes gauge
        returns to zero."""
        addr = _FAMILY_WORKLOADS["contended"].trace(4).addresses.copy()
        cfg = cachesim.host_config(4)
        cachesim_vec.clear_memo()
        obs.reset_counters()
        want = cachesim.simulate(addr, cfg, backend="vectorized")
        cachesim.simulate(addr, cfg, backend="vectorized")
        assert obs.counters().get("memo.hit", 0) >= 1
        cachesim_vec.clear_memo()
        assert obs.counters().get("memo.bytes", 0) == 0
        obs.reset_counters()
        got = cachesim.simulate(addr, cfg, backend="vectorized")
        assert obs.counters().get("memo.miss", 0) == 1
        assert obs.counters().get("memo.hit", 0) == 0
        assert _counters(got) == _counters(want)


# --------------------------------------------------------------------------
# Megaref traces: fixed memory over 10M+ refs
# --------------------------------------------------------------------------
def _megaref_trace(n: int, seed: int = 0) -> np.ndarray:
    """Deterministic mixed-locality word stream: strided sweeps over a
    bounded footprint (the megaref shape — refs grow, the working set
    does not) with a hot reuse set, so every pass of the streaming
    pipeline sees conflict traffic."""
    rng = np.random.default_rng(seed)
    footprint = 1 << 19                 # distinct lines stay O(footprint)
    sweep = (np.arange(n, dtype=np.int64) * 3) % footprint
    hot = rng.integers(0, 4_096, n, dtype=np.int64)
    pick = rng.random(n) < 0.3
    return np.where(pick, hot, sweep) * 8


class TestMegaref:
    def test_truncated_prefix_identity(self):
        """The streaming path over a megaref prefix equals the in-memory
        path over the same prefix — counters are length-invariant."""
        addr = _megaref_trace(200_000)
        cfg = cachesim.host_config(4)
        want = cachesim.simulate(addr.copy(), cfg, backend="vectorized")
        got = simulate_chunked(addr.copy(), cfg, chunk=1 << 14)
        assert _counters(got) == _counters(want)

    @pytest.mark.slow
    @pytest.mark.timing
    def test_10m_refs_fixed_memory(self):
        """A 10M-ref trace simulates under a fixed resident ceiling: the
        streaming path's peak traced allocation stays far below the
        in-memory profile's ~50-80 bytes/ref working set."""
        import tracemalloc

        n = 10_000_000
        addr = _megaref_trace(n)
        cfg = cachesim.host_config(4)
        tracemalloc.start()
        tracemalloc.reset_peak()
        got = simulate_chunked(addr, cfg, chunk=1 << 18,
                               spill_bytes=8 * 2**20)
        _, peak = tracemalloc.get_traced_memory()
        tracemalloc.reset_peak()
        want = cachesim.simulate(addr, cfg, backend="vectorized")
        _, peak_mem = tracemalloc.get_traced_memory()
        tracemalloc.stop()
        assert got.accesses == n
        assert _counters(got) == _counters(want)
        # the streaming ceiling is a small multiple of chunk + distinct +
        # spill budget — fixed as n grows — while the in-memory profile
        # holds ~50-80 bytes per collapsed ref
        assert peak < 256 * 2**20, f"peak {peak / 2**20:.0f} MiB"
        assert peak < peak_mem / 2, (
            f"streaming {peak / 2**20:.0f} MiB vs "
            f"in-memory {peak_mem / 2**20:.0f} MiB")


# --------------------------------------------------------------------------
# Zoo walk_stream -> simulate_chunked: the streamed whole-model data path
# --------------------------------------------------------------------------
_STREAM_TARGET = 60_000

# Three zoo configs (dense / SSM / audio — the audio cells exercise the
# extra-embed capture paths) x three capture modes; batches picked to
# keep captures small, chunk sizes swept per cell.
_STREAM_CELLS = [
    ("qwen2.5-14b", "decode", 8),
    ("qwen2.5-14b", "train", 4),
    ("qwen2.5-14b", "prefill", 1),
    ("mamba2-780m", "decode", 8),
    ("mamba2-780m", "train", 4),
    ("mamba2-780m", "prefill", 1),
    ("whisper-large-v3", "decode", 8),
    ("whisper-large-v3", "train", 4),
    ("whisper-large-v3", "prefill", 1),
]


class TestWalkStreamDifferential:
    """The generator-fed streaming path is counter-identical to the
    in-memory ``walk_window`` -> ``simulate_batch`` path on every
    differential cell: same centered window, block boundaries and chunk
    size must be invisible."""

    @pytest.mark.parametrize("config,mode,batch", _STREAM_CELLS,
                             ids=[f"{c}-{m}" for c, m, _ in _STREAM_CELLS])
    def test_streamed_counter_identical(self, config, mode, batch):
        pytest.importorskip("jax")
        from repro.capture.zoo import get_capture

        mc = get_capture(config, mode, batch)
        addr = mc.walk_window(_STREAM_TARGET).addresses
        cfg = cachesim.host_config(4)
        [want] = cachesim_vec.simulate_batch(addr.copy(), [cfg])
        for chunk in (997, 1 << 16):
            got = simulate_chunked(mc.walk_stream(_STREAM_TARGET), cfg,
                                   chunk=chunk)
            assert _counters(got) == _counters(want), (config, mode, chunk)
            assert got.lfmr == want.lfmr and got.mpki == want.mpki

    def test_streamed_full_walk_and_ndp_hierarchy(self):
        pytest.importorskip("jax")
        from repro.capture.zoo import get_capture

        mc = get_capture("qwen2.5-14b", "decode", 1)
        addr = mc.walk().addresses
        for cfg in (cachesim.host_config(4), cachesim.ndp_config(4)):
            [want] = cachesim_vec.simulate_batch(addr.copy(), [cfg])
            got = simulate_chunked(mc.walk_stream(), cfg, chunk=1 << 14)
            assert _counters(got) == _counters(want)

    @pytest.mark.slow
    def test_bs64_megaref_streams_under_fixed_ceiling(self):
        """The bs64 deep-cache walk (5M+ refs, ~40 MiB as one array; the
        in-memory profile would hold ~50-80 bytes/ref on top) simulates
        through walk_stream under a fixed ceiling, with zero
        concatenated-trace materializations."""
        pytest.importorskip("jax")
        import tracemalloc

        from repro.capture.zoo import capture_for

        mc = capture_for("model.qwen2.5-14b.decode.bs64.c1024")
        whole = mc.walk(count_only=True).refs
        assert whole > 4_000_000
        cfg = cachesim.host_config(4)

        obs.reset_counters()
        tracemalloc.start()
        tracemalloc.reset_peak()
        got = simulate_chunked(mc.walk_stream(), cfg, chunk=1 << 18)
        _, peak = tracemalloc.get_traced_memory()
        tracemalloc.stop()
        c = obs.counters()

        assert got.accesses == whole
        assert c["capture.model.stream_blocks"] > 0
        assert c["stream.gen.blocks"] > 0
        assert "capture.model.concat" not in c
        # chunk working arrays dominate the ceiling; it is fixed as refs
        # grow, far under the in-memory profile's per-ref working set
        assert peak < 128 * 2**20, f"peak {peak / 2**20:.0f} MiB"


class TestChunkedStageSpans:
    def test_passes_nest_in_sim_chunked_and_change_nothing(self, tmp_path):
        """One span per pass of each level; the walks that feed the first
        level run inside its collapse pass, and the scan's stages inside
        the scan pass.  Counters are the same with tracing on."""
        pytest.importorskip("jax")
        from repro.capture.zoo import get_capture

        from _obs_spans import parents_by_name, span_events

        mc = get_capture("qwen2.5-14b", "decode", 1)
        cfg = cachesim.host_config(4)
        want = simulate_chunked(mc.walk_stream(_STREAM_TARGET), cfg,
                                chunk=997, scan="jax")
        trace = tmp_path / "t.jsonl"
        obs.enable(trace)
        try:
            got = simulate_chunked(mc.walk_stream(_STREAM_TARGET), cfg,
                                   chunk=997, scan="jax")
        finally:
            obs.disable()
        assert _counters(got) == _counters(want)
        events = span_events(trace)
        parents = parents_by_name(events)
        for stage in ("collapse", "route", "scan", "emit"):
            assert parents[f"sim.chunked.{stage}"] == {"sim.chunked"}
            # one span per pass of each of the three levels
            assert sum(e["name"] == f"sim.chunked.{stage}"
                       for e in events) == len(cfg.levels)
        assert parents["capture.walk"] == {"sim.chunked.collapse"}
        for name in ("sim.scan.layout", "sim.scan.launch"):
            assert parents[name] == {"sim.chunked.scan"}
        assert parents["sim.scan.wait"] == {"sim.scan.launch"}


# --------------------------------------------------------------------------
# Engine contract: simulate_cells, trace sharing, profile store
# --------------------------------------------------------------------------
def _invariant_workload(name: str = "seg-inv") -> Workload:
    def gen(cores: int, rng: np.random.Generator) -> TraceSpec:
        del cores, rng
        addr = (np.arange(3_000, dtype=np.int64) * 24) % 8_192
        return TraceSpec(addr * 8, l3_factor=1.0, mlp=2.0,
                         dram_rows_irregular=False)

    return Workload(name=name, family="stream", expected_class="1a",
                    ai_ops_per_access=0.25, instr_per_access=2.0,
                    gen=gen, core_invariant=True)


class TestEngineCells:
    def test_cells_equal_per_cell_simulate(self):
        from repro.study.engine import SimEngine
        ws = [_FAMILY_WORKLOADS[f] for f in sorted(_FAMILY_WORKLOADS)][:4]
        items = [(w, c, cachesim.host_config(c))
                 for w in ws for c in (1, 4)]
        batch = SimEngine().simulate_cells(items)
        single = SimEngine()
        want = [single.simulate(w, c, h) for w, c, h in items]
        assert [_counters(s) for s in batch] == \
            [_counters(s) for s in want]

    def test_core_invariant_trace_generated_once(self):
        from repro.study.engine import SimEngine
        eng = SimEngine()
        w = _invariant_workload()
        eng.simulate_cells([(w, c, cachesim.host_config(c))
                            for c in (1, 2, 4, 8)])
        assert eng.stats.trace_runs == 1

    def test_profile_store_shares_cells_across_engines(self, tmp_path):
        from repro.study.engine import SimEngine
        from repro.suite.store import ResultStore
        store = ResultStore(tmp_path)
        w = _invariant_workload("seg-store")
        items = [(w, 4, cachesim.host_config(4)),
                 (w, 4, cachesim.ndp_config(4))]

        obs.reset_counters()
        first = SimEngine(profile_store=store).simulate_cells(items)
        c = obs.counters()
        assert c["store.profile.miss"] == 2
        assert "store.profile.hit" not in c

        obs.reset_counters()
        second = SimEngine(profile_store=store).simulate_cells(items)
        c = obs.counters()
        assert c["store.profile.hit"] == 2
        assert "store.profile.miss" not in c
        assert c.get("engine.sim.run") is None  # nothing re-simulated
        assert [_counters(s) for s in second] == \
            [_counters(s) for s in first]
