"""Launch-layer tests: sharding resolution on production-shaped meshes,
cell plans, analytic cost model sanity, and a miniature lower+compile of
the cells on the in-process device set.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import analytic, hlo_analysis
from repro.launch.cells import all_cells, plan_for
from repro.models import LM
from repro.models.config import SHAPES
from repro.models.sharding import DEFAULT_RULES, logical_to_spec


class TestCells:
    def test_cell_inventory(self):
        cells = all_cells()
        # 11 archs x 3 shapes + 2 sub-quadratic archs x long_500k = 35
        # (the remaining 9 long_500k cells are assignment-mandated skips)
        assert len(cells) == 35
        by_arch = {}
        for c in cells:
            by_arch.setdefault(c.arch, []).append(c.shape.name)
        assert set(by_arch) == set(configs.ARCHS)
        assert "long_500k" in by_arch["mamba2-780m"]
        assert "long_500k" in by_arch["zamba2-7b"]
        assert "long_500k" not in by_arch["qwen2.5-14b"]

    def test_kinds(self):
        assert plan_for("qwen2.5-14b", "train_4k").kind == "train"
        assert plan_for("qwen2.5-14b", "prefill_32k").kind == "prefill"
        assert plan_for("qwen2.5-14b", "decode_32k").kind == "decode"


class TestShardingResolution:
    """Resolution math against abstract production meshes (no devices)."""

    def _fake_mesh(self, shape, axes):
        # AbstractMesh resolves shapes without real devices; the helper
        # papers over the constructor change across jax releases.
        from repro.launch.mesh import make_abstract_mesh
        return make_abstract_mesh(shape, axes)

    def test_divisibility_fallbacks_16x16(self):
        mesh = self._fake_mesh((16, 16), ("data", "model"))
        P = jax.sharding.PartitionSpec
        # qwen: 40 heads NOT divisible by 16 -> replicate that dim
        assert logical_to_spec(mesh, ("fsdp", "heads", None),
                               (5120, 40, 128)) == P("data")
        # nemotron: 96 heads divisible
        assert logical_to_spec(mesh, ("fsdp", "heads", None),
                               (18432, 96, 192)) == P("data", "model")
        # ffn always divisible for assigned archs
        for arch in configs.ARCHS:
            cfg = configs.get(arch)
            if cfg.d_ff:
                spec = logical_to_spec(mesh, ("fsdp", "ffn"),
                                       (cfg.d_model, cfg.d_ff))
                assert spec[1] == "model", arch

    def test_experts_shard_over_model(self):
        mesh = self._fake_mesh((16, 16), ("data", "model"))
        spec = logical_to_spec(mesh, ("experts", "fsdp", "expert_ffn"),
                               (64, 2048, 1408))
        assert spec[0] == "model"

    def test_multipod_fsdp_joins_pod_and_data(self):
        mesh = self._fake_mesh((2, 16, 16), ("pod", "data", "model"))
        spec = logical_to_spec(mesh, ("fsdp", "ffn"), (18432, 73728))
        assert spec[0] == ("pod", "data")

    def test_batch_1_replicates(self):
        mesh = self._fake_mesh((16, 16), ("data", "model"))
        spec = logical_to_spec(mesh, ("batch", None), (1, 1))
        assert spec == jax.sharding.PartitionSpec()


class TestAnalyticCosts:
    def test_train_flops_close_to_6nd(self):
        for arch in ("qwen2.5-14b", "granite-20b", "deepseek-moe-16b"):
            cfg = configs.get(arch)
            shape = SHAPES["train_4k"]
            c = analytic.cell_cost(cfg, shape, kind="train", microbatches=1,
                                   data_shards=16, model_shards=16)
            model = cfg.model_flops(shape.global_batch * shape.seq_len)
            # within 2x of 6·N·D (attention + head add on top)
            assert 0.8 < c.flops / model < 2.0, (arch, c.flops / model)

    def test_decode_memory_dominated_by_kv(self):
        cfg = configs.get("granite-20b")
        c = analytic.cell_cost(cfg, SHAPES["decode_32k"], kind="decode",
                               microbatches=1, data_shards=16,
                               model_shards=16)
        assert c.notes["kv_traffic"] > 0
        # decode arithmetic intensity must be tiny (memory-bound)
        assert c.flops / c.hbm_bytes < 300

    def test_moe_decode_expert_coverage(self):
        cfg = configs.get("deepseek-moe-16b")
        c_small = analytic.cell_cost(
            cfg, SHAPES["long_500k"], kind="decode", microbatches=1,
            data_shards=16, model_shards=16)
        c_big = analytic.cell_cost(
            cfg, SHAPES["decode_32k"], kind="decode", microbatches=1,
            data_shards=16, model_shards=16)
        # batch-1 decode touches ~top_k+shared experts, batch-128 nearly all
        assert c_small.notes["p_touch"] < 0.35 * c_small.notes["p_total"]
        assert c_big.notes["p_touch"] > 0.9 * c_big.notes["p_total"]


class TestHloAnalysis:
    def test_collective_parser_on_synthetic_hlo(self):
        txt = """
  %ar = f32[1024,128]{1,0} all-reduce(f32[1024,128]{1,0} %p0)
  %rs = bf16[64,64]{1,0} reduce-scatter(bf16[512,64]{1,0} %x)
  %a2a = f32[32,32]{1,0} all-to-all(f32[32,32]{1,0} %y)
  %cp = f32[16]{0} collective-permute(f32[16]{0} %z)
  %add = f32[999]{0} add(f32[999] %a, f32[999] %b)
"""
        st = hlo_analysis.collective_stats(txt)
        assert st.count == 4
        assert st.by_op["all-reduce"] == 1024 * 128 * 4
        assert st.by_op["reduce-scatter"] == 64 * 64 * 2
        assert "add" not in st.by_op

    def test_roofline_classification(self):
        hw = hlo_analysis.TPU_V5E
        # compute-bound: high AI
        rt = hlo_analysis.RooflineTerms(
            name="x", chips=1, hlo_flops=1e15, hlo_bytes=1e9,
            collective_bytes=0, model_flops=1e15, hw=hw)
        assert rt.bottleneck_class == "compute"
        assert rt.mfu_bound == pytest.approx(1.0)
        # memory-bound
        rt = hlo_analysis.RooflineTerms(
            name="x", chips=1, hlo_flops=1e12, hlo_bytes=1e12,
            collective_bytes=0, hw=hw)
        assert rt.bottleneck_class == "hbm"
        # latency: sub-100us step
        rt = hlo_analysis.RooflineTerms(
            name="x", chips=256, hlo_flops=1e9, hlo_bytes=1e6,
            collective_bytes=0, hw=hw)
        assert rt.bottleneck_class == "latency"

    def test_peak_table_is_keyed_by_device_kind(self):
        assert hlo_analysis.device_spec("TPU v5 lite") is hlo_analysis.TPU_V5E

    def test_unknown_device_kind_is_an_error(self):
        """No peak is assumed: an unknown kind, and by default the device
        the tests run on (a CPU), raise rather than read as a v5e."""
        with pytest.raises(ValueError, match="no peak table entry"):
            hlo_analysis.device_spec("TPU v9 imaginary")
        if jax.devices()[0].device_kind not in hlo_analysis.PEAKS:
            with pytest.raises(ValueError, match="no peak table entry"):
                hlo_analysis.RooflineTerms(
                    name="x", chips=1, hlo_flops=1.0, hlo_bytes=1.0,
                    collective_bytes=0)


@pytest.mark.slow
class TestMiniDryrun:
    """End-to-end lower+compile on the in-process (1-device) mesh, smoke
    configs, through the build_cell path every cell plan lowers with.
    (~20 s of XLA compilation: slow-marked out of the fast local loop.)"""

    @pytest.mark.parametrize("arch", ["qwen2.5-14b", "deepseek-moe-16b",
                                      "mamba2-780m"])
    @pytest.mark.parametrize("shape", ["train_4k", "decode_32k"])
    def test_lower_compile_smoke(self, arch, shape):
        import dataclasses
        from repro.launch.cells import CellPlan
        from repro.launch.mesh import make_local_mesh
        from repro.launch.specs import build_cell
        from repro.models.config import ShapeSpec

        cfg = configs.get_smoke(arch)
        small = ShapeSpec("t", 64, 4, SHAPES[shape].kind)
        plan = CellPlan(arch=arch, shape=small, cfg=cfg, microbatches=2
                        if SHAPES[shape].kind == "train" else 1,
                        kind=SHAPES[shape].kind)
        mesh = make_local_mesh()
        fn, args, shardings, donate, rules = build_cell(plan, mesh)
        with mesh:
            lowered = jax.jit(fn, in_shardings=shardings,
                              donate_argnums=donate).lower(*args)
            compiled = lowered.compile()
        assert compiled.cost_analysis() is not None
