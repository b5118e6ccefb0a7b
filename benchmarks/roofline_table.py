"""§Roofline: the per-(arch x shape x mesh) three-term table.

Every row is computed from the analytic cost model (no compile) for the
256-chip (16x16) and 512-chip (2x16x16) v5e pod meshes the cells model.
"""

from __future__ import annotations

from repro import configs
from repro.core import analytic, hlo_analysis
from repro.launch.cells import all_cells

HEADER = ("arch", "shape", "mesh", "t_compute_s", "t_memory_s",
          "t_collective_s", "dominant", "class", "mfu_bound",
          "useful_ratio", "roofline_fraction")


def _analytic_entry(plan, mesh_name: str) -> dict:
    chips = 512 if mesh_name == "2x16x16" else 256
    model_shards = 16
    data_shards = chips // model_shards
    c = analytic.cell_cost(plan.cfg, plan.shape, kind=plan.kind,
                           microbatches=plan.microbatches,
                           data_shards=data_shards,
                           model_shards=model_shards,
                           infer_fsdp=plan.infer_fsdp)
    tokens = plan.shape.global_batch * (
        plan.shape.seq_len if plan.kind != "decode" else 1)
    rt = hlo_analysis.RooflineTerms(
        hw=hlo_analysis.TPU_V5E,  # the pod these cells model
        name=f"{plan.name}@{mesh_name}", chips=chips,
        hlo_flops=c.flops, hlo_bytes=c.hbm_bytes,
        collective_bytes=c.collective_bytes,
        model_flops=plan.cfg.model_flops(tokens,
                                         training=plan.kind == "train"))
    return {"arch": plan.arch, "shape": plan.shape.name, "mesh": mesh_name,
            **rt.summary()}


def rows():
    out = []
    for plan in all_cells():
        for mesh_name in ("16x16", "2x16x16"):
            d = _analytic_entry(plan, mesh_name)
            out.append((d["arch"], d["shape"], d["mesh"],
                        f"{d['t_compute_s']:.3e}", f"{d['t_memory_s']:.3e}",
                        f"{d['t_collective_s']:.3e}", d["dominant"],
                        d["class"], round(d["mfu_bound"], 3),
                        round(d["useful_compute_ratio"], 3),
                        round(d["roofline_fraction"], 3)))
    # assignment-mandated skips, for table completeness
    for arch in configs.ARCHS:
        if "long_500k" not in configs.shapes_for(arch):
            out.append((arch, "long_500k", "-", "-", "-", "-", "-",
                        "skipped (full attention)", "-", "-", "-"))
    return out, HEADER
