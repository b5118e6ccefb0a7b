"""Differential tests for the jaxpr-walking capture path (repro.capture.jaxpr).

The zero-mirroring contract: for every captured suite entry, tracing the
kernel's real ``pallas_call`` and walking its jaxpr must emit a DMA word
stream **byte-identical** to the retained mirrored-geometry fallback —
same addresses, same load/store/flop counters, same footprint.  Plus edge
cases the roster never exercises (degenerate 1x1 grids, single-block
operands) and the ``from_jaxpr`` error surface.
"""

import os

import numpy as np
import pytest

from repro.capture import CAPTURED_KERNELS, walk
from repro.capture.jaxpr import PATHS, capture_path, clear_memo

jax = pytest.importorskip("jax")


def _build_both(spec, cores, monkeypatch):
    """One captured entry's GridCapture via each path, same rng stream."""
    caps = {}
    for path in ("jaxpr", "mirror"):
        monkeypatch.setenv("REPRO_CAPTURE_PATH", path)
        caps[path] = spec.builder(cores, np.random.default_rng(0))
    monkeypatch.delenv("REPRO_CAPTURE_PATH")
    return caps["jaxpr"], caps["mirror"]


# --------------------------------------------------------------------------
# The differential gate: every captured entry, both paths, byte-identical.
# --------------------------------------------------------------------------
@pytest.mark.parametrize(
    "spec", CAPTURED_KERNELS, ids=[s.name for s in CAPTURED_KERNELS])
def test_jaxpr_matches_mirror_byte_identical(spec, monkeypatch):
    for cores in (1, 4):
        traced, mirror = _build_both(spec, cores, monkeypatch)
        assert traced.grid == mirror.grid, spec.name
        assert len(traced.operands) == len(mirror.operands)
        a, b = walk(traced), walk(mirror)
        assert np.array_equal(a.addresses, b.addresses), (spec.name, cores)
        assert (a.loads, a.stores, a.flops, a.footprint_words,
                a.grid_steps) == (b.loads, b.stores, b.flops,
                                  b.footprint_words, b.grid_steps)
        # the count-only fast path agrees with both full walks
        fast = walk(traced, count_only=True)
        assert (fast.loads, fast.stores) == (a.loads, a.stores)


def test_jaxpr_block_geometry_matches_mirror(monkeypatch):
    """Beyond the stream: the traced block shapes and per-step block
    indices are the mirrored ones, operand for operand (one entry per
    kernel family keeps this cheap)."""
    by_kernel = {}
    for spec in CAPTURED_KERNELS:
        by_kernel.setdefault(spec.kernel, spec)
    for spec in by_kernel.values():
        traced, mirror = _build_both(spec, 1, monkeypatch)
        for top, mop in zip(traced.operands, mirror.operands):
            assert top.role == mop.role, spec.name
            assert top.shape == mop.shape, (spec.name, mop.name)
            assert top.block_shape == mop.block_shape, (spec.name, mop.name)
            for step in list(np.ndindex(*traced.grid))[:64]:
                assert top.index_map(*step) == mop.index_map(*step), \
                    (spec.name, mop.name, step)


# --------------------------------------------------------------------------
# Degenerate grids.
# --------------------------------------------------------------------------
class TestDegenerateGrids:
    def test_single_block_grid(self):
        """A whole-array kernel (grid of one step) captures as one fetch
        plus one write-back."""
        from repro.kernels.stream import capture as sc

        cap = sc.capture("copy", 512 * 128, path="jaxpr")  # exactly 1 tile
        assert cap.grid == (1,)
        res = walk(cap)
        n_words = 512 * 128 // 2
        assert res.loads == n_words and res.stores == n_words
        assert np.unique(res.addresses).size == res.refs

    def test_1x1_grid_flash(self):
        """One q tile x one kv tile: every operand fetched exactly once."""
        from repro.kernels.flash_attention import capture as fc

        for path in ("jaxpr", "mirror"):
            cap = fc.capture(sq=128, sk=128, d=128, path=path)
            assert cap.grid == (1, 1, 1)
            res = walk(cap)
            tile = 128 * 128 // 2
            assert res.loads == 3 * tile and res.stores == tile
        a = walk(fc.capture(sq=128, sk=128, d=128, path="jaxpr"))
        b = walk(fc.capture(sq=128, sk=128, d=128, path="mirror"))
        assert np.array_equal(a.addresses, b.addresses)

    def test_single_token_gather(self):
        """m=1: one prefetched index word, one row in, one row out."""
        from repro.kernels.token_gather import capture as gc

        for path in ("jaxpr", "mirror"):
            cap = gc.capture(64, 128, 1, rng=np.random.default_rng(3),
                             path=path)
            res = walk(cap)
            assert res.loads == 1 + 64 and res.stores == 64

    def test_single_chunk_ssm(self):
        """seq_len == chunk: the scan degenerates to one grid step."""
        from repro.kernels.ssm_scan import capture as sc

        a = walk(sc.capture("ema", seq_len=128, d=128, chunk=128,
                            path="jaxpr"))
        b = walk(sc.capture("ema", seq_len=128, d=128, chunk=128,
                            path="mirror"))
        assert np.array_equal(a.addresses, b.addresses)
        assert a.grid_steps == 1

    def test_gridless_pallas_call(self):
        """A pallas_call with no grid (one implicit step, whole-array
        blocks) captures as one fetch + one write-back per operand."""
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        from repro.capture import from_jaxpr

        def k(a_ref, o_ref):
            o_ref[...] = a_ref[...] * 2

        def gridless(a):
            return pl.pallas_call(
                k, out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype))(a)

        cap = from_jaxpr(gridless,
                         (jax.ShapeDtypeStruct((8, 128), jnp.float32),))
        assert cap.grid == ()
        res = walk(cap)
        n_words = 8 * 128 // 2
        assert res.loads == n_words and res.stores == n_words
        assert res.grid_steps == 1

    def test_oversubscribed_cores_clamp_to_one_tile(self, monkeypatch):
        """More cores than tiles: the per-thread slice clamps to one tile
        on both paths."""
        from repro.kernels.stream import capture as sc

        for path in ("jaxpr", "mirror"):
            cap = sc.capture("add", 2**17, cores=1024, path=path)
            assert cap.grid == (1,), path


# --------------------------------------------------------------------------
# from_jaxpr error surface + path resolution.
# --------------------------------------------------------------------------
class TestFromJaxpr:
    def test_requires_a_pallas_call(self):
        import jax.numpy as jnp

        from repro.capture import from_jaxpr

        with pytest.raises(ValueError, match="pallas_call"):
            from_jaxpr(lambda a: a + 1,
                       (jax.ShapeDtypeStruct((8,), jnp.float32),))

    def test_scalar_prefetch_values_required(self):
        import jax.numpy as jnp

        from repro.capture import from_jaxpr
        from repro.kernels.token_gather.kernel import gather_rows

        table = jax.ShapeDtypeStruct((64, 128), jnp.float32)
        idx = jax.ShapeDtypeStruct((8,), jnp.int32)
        with pytest.raises(ValueError, match="scalar-prefetch"):
            from_jaxpr(gather_rows, (table, idx))  # values not supplied

    def test_flops_and_name_pass_through(self):
        import jax.numpy as jnp

        from repro.capture import from_jaxpr
        from repro.kernels.stream.kernel import stream_copy

        a = jax.ShapeDtypeStruct((512 * 128,), jnp.float32)
        cap = from_jaxpr(stream_copy, (a,), flops=123.0, name="xyz")
        assert cap.name == "xyz" and cap.flops == 123.0

    def test_capture_path_resolution(self, monkeypatch):
        assert capture_path("jaxpr") == "jaxpr"
        assert capture_path("mirror") == "mirror"
        assert capture_path("auto") == "jaxpr"  # jax importable here
        monkeypatch.setenv("REPRO_CAPTURE_PATH", "mirror")
        assert capture_path("auto") == "mirror"
        assert capture_path("jaxpr") == "jaxpr"  # explicit beats env
        monkeypatch.setenv("REPRO_CAPTURE_PATH", "bogus")
        with pytest.raises(ValueError, match="REPRO_CAPTURE_PATH"):
            capture_path("auto")
        with pytest.raises(ValueError, match="capture path"):
            capture_path("bogus")
        assert set(PATHS) == {"auto", "jaxpr", "mirror"}

    def test_memo_hit_returns_same_capture(self):
        from repro.kernels.flash_attention import capture as fc

        clear_memo()
        a = fc.capture(sq=256, sk=256, d=128, path="jaxpr")
        b = fc.capture(sq=256, sk=256, d=128, path="jaxpr")
        assert a is b  # geometry-keyed memo, not a re-trace

    def test_memo_key_includes_scalar_values(self):
        """Two different index vectors must never share a capture."""
        from repro.kernels.token_gather import capture as gc

        a = gc.capture(64, 128, 8, rng=np.random.default_rng(0),
                       path="jaxpr")
        b = gc.capture(64, 128, 8, rng=np.random.default_rng(1),
                       path="jaxpr")
        ia = [a.operands[1].index_map(i)[0] for i in range(8)]
        ib = [b.operands[1].index_map(i)[0] for i in range(8)]
        assert ia != ib


# --------------------------------------------------------------------------
# The one supported jax and its block-dim API.
# --------------------------------------------------------------------------
class TestBlockDims:
    def test_installed_jax_is_0_9(self):
        """The capture reads jax 0.9's BlockMapping dims; other releases
        put other objects there (plain ints or ``None`` before 0.9)."""
        assert jax.__version__.startswith("0.9."), jax.__version__

    @pytest.mark.parametrize("dim, size", [
        ("blocked", 8), ("squeezed", 1), ("none", 1)])
    def test_block_dim_size(self, dim, size):
        from jax.experimental import pallas as pl

        from repro.capture.jaxpr import block_dim_size

        obj = {"blocked": pl.Blocked(8), "squeezed": pl.Squeezed(),
               "none": None}[dim]
        assert block_dim_size(obj) == size

    def test_element_dims_are_refused(self):
        from jax.experimental import pallas as pl

        from repro.capture.jaxpr import block_dim_size

        with pytest.raises(NotImplementedError, match="Element"):
            block_dim_size(pl.Element(8))

    def test_none_block_dim_captures_as_one(self):
        """A ``None`` block dim reaches the BlockMapping as ``Squeezed``
        and captures as a one-element dim."""
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        from repro.capture import from_jaxpr

        def k(a_ref, o_ref):
            o_ref[...] = a_ref[...]

        def rows(a):
            spec = pl.BlockSpec((None, 8, 128), lambda i: (i, 0, 0))
            return pl.pallas_call(
                k, grid=(a.shape[0],), in_specs=[spec], out_specs=spec,
                out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype))(a)

        cap = from_jaxpr(rows, (jax.ShapeDtypeStruct((4, 8, 128),
                                                     jnp.float32),))
        assert [op.block_shape for op in cap.operands] == [(1, 8, 128)] * 2
        assert walk(cap).loads == 4 * 8 * 128 // 2


def test_default_path_is_jaxpr_with_jax_present():
    """With jax importable and no env override, hooks resolve to the
    traced path (the zero-mirroring default)."""
    assert os.environ.get("REPRO_CAPTURE_PATH") in (None, "", "auto") or True
    assert capture_path() in ("jaxpr", "mirror")
    if os.environ.get("REPRO_CAPTURE_PATH") in (None, "", "auto"):
        assert capture_path() == "jaxpr"
