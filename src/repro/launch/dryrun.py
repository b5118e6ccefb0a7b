import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ^ MUST precede every other import (jax locks device count on first init).
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this produces, WITHOUT allocating any real buffer:

- ``compiled.memory_analysis()``  -> proves the cell fits per-device HBM
- ``compiled.cost_analysis()``    -> FLOPs / bytes for §Roofline
- collective bytes parsed from the optimized HLO -> the ICI roofline term

Results append to a JSON file consumed by ``benchmarks/roofline_table.py``
and EXPERIMENTS.md §Dry-run / §Roofline.

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --arch mamba2-780m \
        --shape decode_32k [--multi-pod] [--out results/dryrun]
    PYTHONPATH=src python -m repro.launch.dryrun --all
"""

import argparse
import json
import time
import traceback

import jax

from .. import configs
from ..core import analytic, hlo_analysis
from ..models import sharding as shardlib
from .cells import all_cells, plan_for
from .mesh import make_production_mesh
from .specs import build_cell

__all__ = ["run_cell", "main"]


def _memory_stats(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    return out


def _cost(compiled) -> dict:
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return {k: float(v) for k, v in ca.items()
            if isinstance(v, (int, float)) and k in
            ("flops", "bytes accessed", "optimal_seconds",
             "bytes accessed output")}


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             save_hlo: str | None = None) -> dict:
    plan = plan_for(arch, shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    t0 = time.time()

    fn, args, shardings, donate, rules = build_cell(plan, mesh)
    with mesh, shardlib.activate(mesh, rules):
        jitted = jax.jit(fn, in_shardings=shardings, donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    hlo_text = compiled.as_text()
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(hlo_text)

    cost = _cost(compiled)
    coll = hlo_analysis.collective_stats(hlo_text)
    tokens = plan.shape.global_batch * (
        plan.shape.seq_len if plan.kind != "decode" else 1)
    model_flops = plan.cfg.model_flops(
        tokens, training=plan.kind == "train")

    # Analytic model is the primary roofline source (XLA cost_analysis does
    # not multiply through while-loop trip counts); HLO-derived numbers are
    # kept as per-iteration schedule evidence.
    model_shards = mesh.shape["model"]
    data_shards = chips // model_shards
    costs = analytic.cell_cost(
        plan.cfg, plan.shape, kind=plan.kind,
        microbatches=plan.microbatches,
        data_shards=data_shards, model_shards=model_shards,
        infer_fsdp=plan.infer_fsdp,
    )
    rt = hlo_analysis.RooflineTerms(
        hw=hlo_analysis.TPU_V5E,  # the pod these cells model
        name=f"{plan.name}@{'2pod' if multi_pod else '1pod'}",
        chips=chips,
        hlo_flops=costs.flops,
        hlo_bytes=costs.hbm_bytes,
        collective_bytes=costs.collective_bytes,
        model_flops=model_flops,
    )
    entry = {
        "arch": arch,
        "shape": shape_name,
        "kind": plan.kind,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "microbatches": plan.microbatches,
        "status": "ok",
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": _memory_stats(compiled),
        "hlo_cost_analysis": cost,
        "hlo_collective_bytes_per_iter": coll.total_bytes,
        "hlo_collective_by_op": coll.by_op,
        "tokens": tokens,
        "analytic_notes": {k: float(v) for k, v in costs.notes.items()},
        **rt.summary(),
    }
    return entry


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)

    if args.all:
        cells = [(p.arch, p.shape.name) for p in all_cells()]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    meshes = [False, True] if (args.both_meshes or args.all) else [args.multi_pod]

    for arch, shape in cells:
        for mp in meshes:
            tag = f"{arch}__{shape}__{'2pod' if mp else '1pod'}"
            path = os.path.join(args.out, tag + ".json")
            if os.path.exists(path):
                print(f"[skip] {tag} (exists)")
                continue
            print(f"[run ] {tag} ...", flush=True)
            try:
                entry = run_cell(arch, shape, multi_pod=mp)
            except Exception as e:  # noqa: BLE001
                entry = {
                    "arch": arch, "shape": shape,
                    "mesh": "2x16x16" if mp else "16x16",
                    "status": "error",
                    "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-2000:],
                }
            with open(path, "w") as f:
                json.dump(entry, f, indent=1)
            status = entry["status"]
            extra = ""
            if status == "ok":
                extra = (f" compile={entry['compile_s']}s "
                         f"class={entry['class']} "
                         f"tc={entry['t_compute_s']:.3e} "
                         f"tm={entry['t_memory_s']:.3e} "
                         f"tx={entry['t_collective_s']:.3e}")
            print(f"[done] {tag}: {status}{extra}", flush=True)

    # Note the assignment-mandated skips so the table is complete.
    skips = []
    for arch in configs.ARCHS:
        have = set(configs.shapes_for(arch))
        for shape in ("train_4k", "prefill_32k", "decode_32k", "long_500k"):
            if shape not in have:
                skips.append({
                    "arch": arch, "shape": shape, "status": "skipped",
                    "reason": "long_500k requires sub-quadratic attention; "
                              "full-attention arch (DESIGN.md §5)",
                })
    with open(os.path.join(args.out, "_skips.json"), "w") as f:
        json.dump(skips, f, indent=1)


if __name__ == "__main__":
    main()
