"""Tests for the Pallas-kernel trace capture subsystem (repro.capture).

Covers the grid walker's pipeline semantics (revisit-skip fetches,
write-back-on-last-visit stores), footprint/coverage identity against the
declared launch geometry, determinism, and — when jax is importable — the
consistency of the mirrored fallback geometry with the real kernels.
The jaxpr-vs-mirror differential gate lives in ``test_capture_jaxpr.py``.
"""

import numpy as np
import pytest

from repro.capture import CAPTURED_KERNELS, captured_workloads, walk
from repro.capture.grid import GridCapture, OperandSpec
from repro.kernels.flash_attention import capture as flash_capture
from repro.kernels.stream import capture as stream_capture
from repro.kernels.token_gather import capture as gather_capture


# --------------------------------------------------------------------------
# Walker semantics
# --------------------------------------------------------------------------
class TestWalker:
    def test_stream_copy_covers_both_arrays_exactly_once(self):
        cap = stream_capture.capture("copy", 2**17)  # 2 tiles
        res = walk(cap)
        n_words = 2**17 // 2
        assert res.loads == n_words and res.stores == n_words
        assert res.refs == 2 * n_words
        assert res.footprint_words == 2 * n_words
        # every array word appears exactly once: distinct addresses == refs
        assert np.unique(res.addresses).size == res.refs

    def test_scalar_operand_fetched_once(self):
        cap = stream_capture.capture("scale", 2**18)
        res = walk(cap)
        # q is 1 word; array loads + q + stores
        n_words = 2**18 // 2
        assert res.loads == n_words + 1
        assert res.stores == n_words

    def test_output_written_back_once_per_block(self):
        cap = stream_capture.capture("add", 2**17)
        res = walk(cap)
        n_words = 2**17 // 2
        assert res.stores == n_words  # one write-back per output word

    def test_flash_q_fetched_once_per_q_tile(self):
        cap = flash_capture.capture(sq=256, sk=512, d=64)
        res = walk(cap)
        n_q, n_kv = 2, 4
        q_words = 128 * 64 // 2
        kv_words = 128 * 64 // 2
        # q: once per qi (revisit-skip across the kv axis); k+v every step;
        # o: one write-back per q tile.
        assert res.loads == n_q * q_words + n_q * n_kv * 2 * kv_words
        assert res.stores == n_q * q_words

    def test_gather_rows_follow_indices(self):
        rng = np.random.default_rng(7)
        cap = gather_capture.capture(1024, 128, 16, rng=rng)
        res = walk(cap)
        row_words = 128 // 2
        # idx (16 int32 -> 8 words) + 16 table rows + 16 out rows
        assert res.loads == 8 + 16 * row_words
        assert res.stores == 16 * row_words
        # the table-row loads land at the captured indices' offsets
        idx_op = cap.operands[1]
        idx = [idx_op.index_map(i)[0] for i in range(16)]
        assert all(0 <= i < 1024 for i in idx)

    def test_count_only_walk_matches_full_walk(self):
        rng = np.random.default_rng(5)
        for cap in (stream_capture.capture("triad", 2**18),
                    flash_capture.capture(sq=256, sk=512, d=64),
                    gather_capture.capture(1024, 128, 16, rng=rng)):
            full = walk(cap)
            fast = walk(cap, count_only=True)
            assert (fast.loads, fast.stores) == (full.loads, full.stores)
            assert fast.refs == full.refs == full.addresses.size
            assert fast.flops_per_ref == full.flops_per_ref
            assert fast.addresses.size == 0

    def test_unaligned_row_stride_rejected(self):
        with pytest.raises(ValueError, match="last dim"):
            OperandSpec("x", "in", (4, 5), (2, 5), lambda i: (0, 0))

    def test_walk_deterministic(self):
        cap = flash_capture.capture(sq=256, sk=512, d=64)
        a, b = walk(cap), walk(cap)
        assert np.array_equal(a.addresses, b.addresses)
        assert (a.loads, a.stores, a.flops) == (b.loads, b.stores, b.flops)

    def test_operand_validation(self):
        with pytest.raises(ValueError, match="role"):
            OperandSpec("x", "inout", (8,), (8,), lambda i: (0,))
        with pytest.raises(ValueError, match="rank"):
            OperandSpec("x", "in", (8, 8), (8,), lambda i: (0,))

    def test_empty_grid(self):
        res = walk(GridCapture("empty", (0,), operands=(
            OperandSpec("a", "in", (8, 128), (8, 128), lambda i: (0, 0)),)))
        assert res.refs == 0 and res.grid_steps == 0


# --------------------------------------------------------------------------
# Vectorized walker vs scalar reference (the gate promised in grid.py)
# --------------------------------------------------------------------------
class TestWalkStageSpans:
    def test_stages_nest_in_capture_walk_and_change_nothing(self,
                                                            tmp_path):
        """A gridded walk opens its schedule and emit stages inside
        ``capture.walk``; a count-only one emits nothing; addresses are
        byte-identical with tracing on and off."""
        from _obs_spans import parents_by_name, span_events

        from repro import obs

        cap = flash_capture.capture(sq=1024, sk=1024, d=64)
        off = walk(cap)
        trace = tmp_path / "t.jsonl"
        obs.enable(trace)
        try:
            on = walk(cap)
            counted = walk(cap, count_only=True)
        finally:
            obs.disable()
        assert on.addresses.tobytes() == off.addresses.tobytes()
        assert (on.loads, on.stores) == (off.loads, off.stores)
        assert counted.refs == off.refs
        events = span_events(trace)
        assert [e["name"] for e in events] == [
            "capture.walk.schedule", "capture.walk.emit", "capture.walk",
            "capture.walk.schedule", "capture.walk"]
        parents = parents_by_name(events)
        assert parents["capture.walk.schedule"] == {"capture.walk"}
        assert parents["capture.walk.emit"] == {"capture.walk"}


# The captured-kernel geometries the vectorized-vs-loop gate walks.
_DIFF_GEOMETRIES = {
    **{f"stream.{v}": (lambda v=v: stream_capture.capture(v, 2**17))
       for v in ("copy", "scale", "add", "triad")},
    "flash.256x512": lambda: flash_capture.capture(sq=256, sk=512, d=64),
    "flash.512x1024": lambda: flash_capture.capture(sq=512, sk=1024, d=64),
    "gather": lambda: gather_capture.capture(
        1024, 128, 64, rng=np.random.default_rng(11)),
}


class TestWalkDifferential:
    """``_walk`` (vectorized) must be byte-identical to ``_walk_loop``
    (the scalar reference) over the captured-kernel roster — addresses,
    counters and footprints, in both full and count-only modes."""

    def _captures(self):
        return [build() for build in _DIFF_GEOMETRIES.values()]

    def test_full_walk_byte_identical(self):
        from repro.capture.grid import _walk, _walk_loop
        for cap in self._captures():
            vec = _walk(cap, count_only=False, bases=None)
            ref = _walk_loop(cap, count_only=False, bases=None)
            assert np.array_equal(vec.addresses, ref.addresses), cap.name
            assert (vec.loads, vec.stores, vec.flops, vec.grid_steps,
                    vec.footprint_words) == (
                ref.loads, ref.stores, ref.flops, ref.grid_steps,
                ref.footprint_words), cap.name

    def test_count_only_byte_identical(self):
        from repro.capture.grid import _walk, _walk_loop
        for cap in self._captures():
            vec = _walk(cap, count_only=True, bases=None)
            ref = _walk_loop(cap, count_only=True, bases=None)
            assert vec.addresses.size == ref.addresses.size == 0
            assert (vec.loads, vec.stores, vec.refs) == (
                ref.loads, ref.stores, ref.refs), cap.name

    def test_shared_name_aliasing_matches(self):
        # two input operands under one name: the fetch decision consults
        # the merged same-name sequence — the exact semantics the
        # vectorized masks must reproduce
        from repro.capture.grid import _walk, _walk_loop
        cap = GridCapture("alias", (4, 4), operands=(
            OperandSpec("t", "in", (64, 128), (8, 128),
                        lambda i, j: (i % 2, 0)),
            OperandSpec("t", "in", (64, 128), (8, 128),
                        lambda i, j: (j % 3, 0)),
            OperandSpec("o", "out", (64, 128), (8, 128),
                        lambda i, j: (i, 0)),
        ))
        vec = _walk(cap, count_only=False, bases=None)
        ref = _walk_loop(cap, count_only=False, bases=None)
        assert np.array_equal(vec.addresses, ref.addresses)
        assert (vec.loads, vec.stores) == (ref.loads, ref.stores)


def _qwen_ffn_dot() -> GridCapture:
    """A Qwen2.5-14B decode FFN projection as the whole-model walker
    lowers it (bf16, batch 64, 128-wide tiles, k innermost), at a reduced
    grid (1, 1, 12, 8) in place of (1, 1, 108, 40)."""
    g, m, k, n = 1, 64, 1024, 1536
    return GridCapture("dot_general", (g, 1, n // 128, k // 128), operands=(
        OperandSpec("lhs", "in", (g, m, k), (1, m, 128),
                    lambda gg, i, j, kk: (gg, i, kk), elems_per_word=4),
        OperandSpec("rhs", "in", (g, k, n), (1, 128, 128),
                    lambda gg, i, j, kk: (gg, kk, j), elems_per_word=4),
        OperandSpec("out", "out", (g, m, n), (1, m, 128),
                    lambda gg, i, j, kk: (gg, i, j), elems_per_word=4),
    ))


def _tiny_launch() -> GridCapture:
    """12 step-operand pairs: walked by ``_walk_loop``."""
    return GridCapture("tiny", (2, 2), operands=(
        OperandSpec("x", "in", (16, 128), (8, 128), lambda i, j: (i, 0)),
        OperandSpec("y", "in", (16, 128), (8, 128), lambda i, j: (j, 0)),
        OperandSpec("o", "out", (16, 128), (8, 128), lambda i, j: (i, 0)),
    ))


_SPAN_GEOMETRIES = {**_DIFF_GEOMETRIES, "qwen.ffn.dot": _qwen_ffn_dot,
                    "tiny.loop": _tiny_launch}
_SPANS = ("whole", "prefix", "suffix", "inside_block", "block_boundary",
          "write_back", "past_end", "empty")


def _span(kind: str, cap: GridCapture, full) -> tuple[int, int]:
    """A span of ``kind`` in ``full``, the walk of ``cap`` with each name
    based at its own multiple of 2**32."""
    n = full.refs
    first = _block_words(cap.operands[0])          # the first event's block
    out = next(op for op in cap.operands if op.role == "out")
    at = int(np.flatnonzero(full.addresses >> 32 == _names(cap).index(
        out.name))[0])                              # first write-back
    return {
        "whole": (0, n),
        "prefix": (0, n // 3),
        "suffix": (n - n // 3, n),
        "inside_block": (at + 1, at + _block_words(out) - 1),
        "block_boundary": (first - 1, first + 1),
        "write_back": (at - 1, at + _block_words(out) + 1),
        "past_end": (n // 2, n + 1000),
        "empty": (n // 2, n // 2),
    }[kind]


def _names(cap: GridCapture) -> list[str]:
    return list(dict.fromkeys(op.name for op in cap.operands))


def _block_words(op: OperandSpec) -> int:
    return int(np.prod(op.block_shape)) // op.elems_per_word


class TestWindowedEmission:
    """``walk(cap, span=(lo, hi))`` is the full walk's ``[lo:hi]`` slice,
    byte for byte, with loads and stores counted over the slice by role,
    on the vectorized path and on the tiny-launch loop path."""

    @pytest.mark.parametrize("kind", _SPANS)
    @pytest.mark.parametrize("geometry", list(_SPAN_GEOMETRIES))
    def test_span_is_slice_of_full_walk(self, geometry, kind):
        from repro import obs

        cap = _SPAN_GEOMETRIES[geometry]()
        names = _names(cap)
        bases = {name: i << 32 for i, name in enumerate(names)}
        full = walk(cap, bases=bases)
        lo, hi = _span(kind, cap, full)
        assert 0 <= lo <= hi
        obs.reset_counters()
        got = walk(cap, bases=bases, span=(lo, hi))
        c = obs.counters()
        want = full.addresses[lo:hi]
        assert got.addresses.tobytes() == want.tobytes()
        role = {op.name: op.role for op in cap.operands}
        loads = sum(int(np.count_nonzero(want >> 32 == i))
                    for i, name in enumerate(names) if role[name] == "in")
        assert (got.loads, got.stores) == (loads, want.size - loads)
        assert got.refs == got.loads + got.stores == got.addresses.size
        assert c["capture.walk.refs"] == want.size
        if want.size < full.refs:
            assert c["capture.walk.window_calls"] == 1
            assert c["capture.walk.skipped_refs"] == full.refs - want.size
        else:
            assert "capture.walk.window_calls" not in c
            assert "capture.walk.skipped_refs" not in c

    def test_qwen_dot_is_vectorized_and_tiny_launch_is_not(self):
        # the two added geometries take the two paths
        for build, steps in ((_qwen_ffn_dot, 96), (_tiny_launch, 4)):
            cap = build()
            assert walk(cap).grid_steps == steps
            assert (steps * len(cap.operands) > 64) == (build is _qwen_ffn_dot)

    def test_bad_spans_rejected(self):
        cap = _qwen_ffn_dot()
        with pytest.raises(ValueError):
            walk(cap, count_only=True, span=(0, 8))
        for span in ((-1, 8), (9, 8)):
            with pytest.raises(ValueError):
                walk(cap, span=span)

    def test_full_walk_counts_no_window(self):
        from repro import obs

        cap = _qwen_ffn_dot()
        obs.reset_counters()
        full = walk(cap)
        walk(cap, span=(0, full.refs))
        c = obs.counters()
        assert c["capture.walk.calls"] == 2
        assert "capture.walk.window_calls" not in c


def _runs_rank1() -> GridCapture:
    """Rank-1 operands, one element a word, rows of 64, 48 and 40 words."""
    return GridCapture("runs.rank1", (32,), operands=(
        OperandSpec("x", "in", (4096,), (64,), lambda i: (i,),
                    elems_per_word=1),
        OperandSpec("y", "in", (4096,), (48,), lambda i: (i % 5,),
                    elems_per_word=1),
        OperandSpec("o", "out", (2048,), (40,), lambda i: (i // 4,),
                    elems_per_word=1),
    ))


def _runs_rank2() -> GridCapture:
    """Rank-2 operands, two elements a word, rows of 3 and 2 words."""
    return GridCapture("runs.rank2", (8, 6), operands=(
        OperandSpec("a", "in", (64, 256), (8, 6), lambda i, j: (i, j)),
        OperandSpec("b", "in", (32, 64), (4, 4), lambda i, j: (j, i)),
        OperandSpec("o", "out", (64, 256), (8, 6), lambda i, j: (i, 0)),
    ))


def _runs_rank3() -> GridCapture:
    """Rank-3 bf16 operands, rows of 32, 16 and 6 words."""
    return GridCapture("runs.rank3", (2, 4, 4), operands=(
        OperandSpec("x", "in", (2, 64, 512), (1, 8, 128),
                    lambda b, i, k: (b, i, k), elems_per_word=4),
        OperandSpec("y", "in", (2, 512, 64), (1, 16, 64),
                    lambda b, i, k: (b, k, 0), elems_per_word=4),
        OperandSpec("o", "out", (2, 64, 64), (1, 8, 24),
                    lambda b, i, k: (b, i, 0), elems_per_word=4),
    ))


def _runs_rank4() -> GridCapture:
    """Rank-4 bf16 operands, rows of 12 and 8 words."""
    return GridCapture("runs.rank4", (2, 3, 4), operands=(
        OperandSpec("x", "in", (2, 3, 32, 96), (1, 1, 8, 48),
                    lambda a, b, c: (a, b, c, c % 2), elems_per_word=4),
        OperandSpec("y", "in", (2, 4, 16, 64), (1, 2, 4, 32),
                    lambda a, b, c: (a, 0, c, b % 2), elems_per_word=4),
        OperandSpec("o", "out", (2, 3, 32, 96), (1, 1, 8, 48),
                    lambda a, b, c: (a, b, 0, 1), elems_per_word=4),
    ))


def _runs_mixed() -> GridCapture:
    """Ranks 1, 4 and 2 in one launch, rows of 5, 8 and 7 words."""
    return GridCapture("runs.mixed", (4, 8), operands=(
        OperandSpec("s", "in", (512,), (10,), lambda i, j: (j,)),
        OperandSpec("t", "in", (2, 2, 16, 64), (1, 1, 4, 16),
                    lambda i, j: (i % 2, i // 2, j % 4, j // 4)),
        OperandSpec("o", "out", (8, 64), (2, 14), lambda i, j: (i, 0)),
    ))


# geometry -> (builder, gcd of its operands' row widths in words)
_RUN_GEOMETRIES = {
    "rank1.epw1.g8": (_runs_rank1, 8),
    "rank2.epw2.g1": (_runs_rank2, 1),
    "rank3.epw4.g2": (_runs_rank3, 2),
    "rank4.epw4.g4": (_runs_rank4, 4),
    "mixed.epw2.g1": (_runs_mixed, 1),
}
_RUN_SPANS = {
    "whole": lambda n: (0, n),
    "cut_first_and_last": lambda n: (3, n - 3),
    "middle_third": lambda n: (n // 3 + 1, 2 * n // 3 + 1),
    "inside_one_block": lambda n: (n // 2 + 1, n // 2 + 2),
}


class TestWordRuns:
    """The vectorized walk emits each block as word runs, one per block
    row, and places them at the granularity of the row widths' gcd:
    byte-identical to the scalar walker and to the full walk's slice for
    any word size, rank and mix of row widths."""

    @pytest.mark.parametrize("span", list(_RUN_SPANS))
    @pytest.mark.parametrize("geometry", list(_RUN_GEOMETRIES))
    def test_window_matches_loop_and_full_slice(self, geometry, span):
        from repro.capture.grid import _walk_loop

        build, g = _RUN_GEOMETRIES[geometry]
        cap = build()
        assert np.prod(cap.grid) * len(cap.operands) > 64   # vectorized
        widths = [op.block_shape[-1] // op.elems_per_word
                  for op in cap.operands]
        assert np.gcd.reduce(widths) == g < min(widths)
        full = walk(cap)
        ref_full = _walk_loop(cap, count_only=False, bases=None)
        assert full.addresses.tobytes() == ref_full.addresses.tobytes()
        assert (full.loads, full.stores) == (ref_full.loads, ref_full.stores)
        lo, hi = _RUN_SPANS[span](full.refs)
        got = walk(cap, span=(lo, hi))
        ref = _walk_loop(cap, count_only=False, bases=None, span=(lo, hi))
        assert got.addresses.tobytes() == ref.addresses.tobytes()
        assert got.addresses.tobytes() == full.addresses[lo:hi].tobytes()
        assert (got.loads, got.stores) == (ref.loads, ref.stores)
        assert got.refs == hi - lo

    def test_unaligned_block_row_rejected(self):
        cap = GridCapture("unaligned", (32,), operands=(
            OperandSpec("x", "in", (4096,), (6,), lambda i: (i,),
                        elems_per_word=4),
            OperandSpec("o", "out", (4096,), (8,), lambda i: (i,),
                        elems_per_word=4),
            OperandSpec("y", "in", (4096,), (8,), lambda i: (i,),
                        elems_per_word=4),
        ))
        with pytest.raises(ValueError, match="block rows must be word-aligned"):
            walk(cap)

    @pytest.mark.parametrize("kind", ["whole", "window", "inside_block"])
    def test_emit_runs_counts_block_rows_placed(self, kind):
        from repro import obs

        cap = _qwen_ffn_dot()
        # Its schedule: every step fetches lhs (64 rows) and rhs (128
        # rows); the last k step of each output column writes back out
        # (64 rows).  Every row is 128 bf16 elements, 32 words.
        rows = np.array([r for _ in range(12) for kk in range(8)
                         for r in (64, 128) + ((64,) if kk == 7 else ())])
        ends = np.cumsum(rows * 32)
        starts = ends - rows * 32
        assert ends[-1] == walk(cap).refs
        lo, hi = {"whole": (0, int(ends[-1])),
                  "window": (int(ends[3]) - 5, int(ends[40]) + 7),
                  "inside_block": (int(starts[10]) + 1,
                                   int(starts[10]) + 2)}[kind]
        obs.reset_counters()
        got = walk(cap) if kind == "whole" else walk(cap, span=(lo, hi))
        placed = int(rows[(starts < hi) & (ends > lo)].sum())
        assert obs.counters()["capture.walk.emit_runs"] == placed
        assert got.refs == hi - lo
        if kind == "whole":
            assert placed == 12 * (8 * (64 + 128) + 64)


# --------------------------------------------------------------------------
# Captured workloads (the suite's `captured` source)
# --------------------------------------------------------------------------
class TestCapturedWorkloads:
    def test_roster_shape(self):
        ws = captured_workloads()
        assert len(ws) == len(CAPTURED_KERNELS) == 24
        assert len({w.name for w in ws}) == 24
        kernels = {s.kernel for s in CAPTURED_KERNELS}
        assert kernels == {"stream", "gather", "flashattn",
                           "pagedkv", "moe", "ssm"}
        # every new family contributes >= 2 geometry points
        for kernel in kernels:
            assert sum(s.kernel == kernel for s in CAPTURED_KERNELS) >= 2
        for spec in CAPTURED_KERNELS:
            assert spec.expected_class in ("1a", "1b", "1c")

    def test_traces_deterministic_across_builds(self):
        for ws in (captured_workloads(), captured_workloads()):
            w = next(x for x in ws if x.name == "pal.gather.64kx128")
            a = w.trace(4, seed=0).addresses
        b = next(x for x in captured_workloads()
                 if x.name == "pal.gather.64kx128").trace(4, seed=0).addresses
        assert np.array_equal(a, b)

    def test_gather_trace_seed_sensitivity(self):
        w = next(x for x in captured_workloads()
                 if x.name == "pal.gather.64kx128")
        assert not np.array_equal(w.trace(1, seed=0).addresses,
                                  w.trace(1, seed=1).addresses)

    def test_target_refs_normalization(self):
        w = next(x for x in captured_workloads()
                 if x.name == "pal.flashattn.d128.kv2k")
        for cores in (1, 16, 256):
            assert w.trace(cores).addresses.size == 300_000

    def test_kv_split_shrinks_per_core_footprint(self):
        w = next(x for x in captured_workloads()
                 if x.name == "pal.flashattn.d64.kv20k")
        lines1 = np.unique(w.trace(1).addresses // 8).size
        lines64 = np.unique(w.trace(64).addresses // 8).size
        assert lines64 < lines1 / 8  # flash-decoding chunking
        assert w.trace(64).l3_factor == pytest.approx(1 / 64)

    def test_stream_capture_classifies_1a(self):
        """One cheap end-to-end check: the captured copy kernel recovers
        the paper's STREAM verdict (full captured-class coverage runs in
        the suite CLI / CI smoke leg)."""
        from repro.core import classify

        w = next(x for x in captured_workloads()
                 if x.name == "pal.stream.copy.1MiB")
        m = classify.measure(w)
        assert classify.classify(m) == "1a"
        assert m.temporal < 0.1 and m.mpki > 11


def test_capture_and_suite_importable_without_jax():
    """Acceptance: capture requires neither a TPU nor jax — a blocked-jax
    interpreter can still build the registry and classify a captured
    kernel."""
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env["PYTHONPATH"] = os.path.abspath(src)
    code = (
        "import sys; sys.modules['jax'] = None\n"
        "from repro.suite import default_registry\n"
        "from repro.core import classify\n"
        "reg = default_registry(refs=2000)\n"
        "w = reg.by_source('captured')[0].workload\n"
        "m = classify.measure(w, cores=(1,))\n"
        "print(len(reg), classify.classify(m))\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, check=True,
    )
    assert out.stdout.split() == ["45", "1a"]


# --------------------------------------------------------------------------
# Mirrored-geometry consistency against the real kernels (needs jax)
# --------------------------------------------------------------------------
class TestKernelConsistency:
    def test_stream_constants_match_kernel(self):
        kernel = pytest.importorskip("repro.kernels.stream.kernel")
        assert stream_capture.LANES == kernel.LANES
        assert stream_capture.DEFAULT_BLOCK_ROWS == kernel.DEFAULT_BLOCK_ROWS

    def test_gather_capture_matches_interpret_kernel(self):
        """The captured index->row mapping is the one the Pallas kernel
        implements (interpret mode, no TPU)."""
        pytest.importorskip("jax")
        import jax.numpy as jnp

        from repro.kernels.token_gather.kernel import gather_rows

        rng = np.random.default_rng(3)
        cap = gather_capture.capture(64, 128, 8, rng=rng)
        idx_map = cap.operands[1].index_map
        idx = np.array([idx_map(i)[0] for i in range(8)])

        table = jnp.arange(64 * 128, dtype=jnp.float32).reshape(64, 128)
        out = gather_rows(table, jnp.asarray(idx, dtype=jnp.int32),
                          interpret=True)
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(table)[idx])

    def test_flash_capture_mirrors_real_pallas_call(self, monkeypatch):
        """Intercept the kernel's actual ``pl.pallas_call`` and assert the
        capture hook mirrors its grid, block shapes, and index maps — a
        grid-order or index-map change in kernel.py fails here."""
        pytest.importorskip("jax")
        import jax.numpy as jnp

        from repro.kernels.flash_attention import kernel as fk

        seen = {}
        real = fk.pl.pallas_call

        def spy(body, *, grid=None, in_specs=None, out_specs=None, **kw):
            seen.update(grid=grid, in_specs=in_specs, out_specs=out_specs)
            return real(body, grid=grid, in_specs=in_specs,
                        out_specs=out_specs, **kw)

        monkeypatch.setattr(fk.pl, "pallas_call", spy)
        # unique shapes: forces a fresh jit trace so the spy fires
        sq, sk, d = 384, 640, 64
        q = jnp.ones((1, sq, 1, d), jnp.float32)
        k = v = jnp.ones((1, sk, 1, d), jnp.float32)
        fk.flash_attention(q, k, v, causal=False, interpret=True)
        assert "grid" in seen, "pallas_call not traced"

        cap = flash_capture.capture(sq=sq, sk=sk, d=d)
        assert tuple(seen["grid"]) == cap.grid == (1, 3, 5)
        kernel_specs = list(seen["in_specs"]) + [seen["out_specs"]]
        for spec, op in zip(kernel_specs, cap.operands):
            assert tuple(spec.block_shape) == op.block_shape, op.name
            for step in np.ndindex(*cap.grid):
                assert tuple(spec.index_map(*step)) == \
                    tuple(op.index_map(*step)), (op.name, step)
