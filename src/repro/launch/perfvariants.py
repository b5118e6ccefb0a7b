import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""§Perf hillclimb variants for the three chosen cells.

Each variant = (cell, sharding-rule/knob change).  For every variant we
re-lower, re-compile, and record (a) the analytic three-term roofline under
the changed configuration and (b) the compiled evidence (per-iteration HLO
collective bytes + per-device memory), appended to results/perf/.

Chosen cells (from the baseline §Roofline table):

1. deepseek-moe-16b:train_4k   — worst-class representative of the paper's
   own technique (compute-near-shard MoE); collective-bound (frac 0.12).
   Variant A: EP-only sharding — experts stay on the model axis, attention/
   shared-MLP/vocab go data-parallel (no TP activation all-reduces).
   Variant B: A + int8 error-feedback gradient compression.
2. nemotron-4-340b:train_4k    — most collective-bound absolute (tx 84 s).
   Variant A: microbatches 16 -> 4 (enabled by the sequence-parallel
   activation savings of perf iterations 1-3).
   Variant B: A + int8-EF gradient compression.
3. zamba2-7b:long_500k         — worst roofline fraction (hbm-bound decode).
   Variant A: shard the shared-attention KV cache length over the model
   axis (already INFER default — measured against a no-cache-len-sharding
   ablation to quantify it).
"""

import dataclasses
import json
import time

import jax

from ..core import analytic, hlo_analysis
from ..models import sharding as shardlib
from .cells import plan_for
from .mesh import make_production_mesh
from .specs import build_cell

# EP-only: replicate attention/MLP weights over the model axis (no TP
# activation all-reduces); experts + vocab stay model-sharded.
EP_ONLY = (("heads", None), ("kv_heads", None), ("qkv", None),
           ("ffn", None), ("ssm_inner", None), ("ssm_heads", None),
           ("seq_residual", None))


def run_variant(tag, arch, shape, *, rules_override=(), microbatches=None,
                compress=None, multi_pod=False, model_shards_for_analytic=16,
                tp_layers=True, out_dir="results/perf"):
    os.makedirs(out_dir, exist_ok=True)
    plan = plan_for(arch, shape)
    if microbatches is not None:
        plan = dataclasses.replace(plan, microbatches=microbatches)
    if rules_override:
        plan = dataclasses.replace(
            plan, rules_override=plan.rules_override + tuple(rules_override))
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size

    t0 = time.time()
    fn, args, shardings, donate, rules = build_cell(plan, mesh)
    if compress:
        from ..train import AdamWConfig, build_train_step, init_train_state
        lm_params = args[0]
        from ..models.model import LM
        lm = LM(plan.cfg)
        opt_cfg = AdamWConfig()
        opt_shapes = jax.eval_shape(
            lambda p: init_train_state(lm, p, opt_cfg, compress=compress),
            lm_params)
        from ..models.sharding import tree_shardings
        from ..train import train_state_axes
        opt_sh = tree_shardings(mesh, opt_shapes,
                                train_state_axes(lm.axes(), compress=compress),
                                rules)
        fn = build_train_step(lm, opt_cfg, microbatches=plan.microbatches,
                              compress=compress)
        args = (args[0], opt_shapes, args[2])
        shardings = (shardings[0], opt_sh, shardings[2])

    with mesh, shardlib.activate(mesh, rules):
        lowered = jax.jit(fn, in_shardings=shardings,
                          donate_argnums=donate).lower(*args)
        compiled = lowered.compile()
    hlo = compiled.as_text()
    coll = hlo_analysis.collective_stats(hlo)
    ma = compiled.memory_analysis()

    model_shards = model_shards_for_analytic if tp_layers else 1
    costs = analytic.cell_cost(
        plan.cfg, plan.shape, kind=plan.kind,
        microbatches=plan.microbatches,
        data_shards=chips // 16, model_shards=16,
        infer_fsdp=plan.infer_fsdp)
    if not tp_layers:
        # EP-only: remove the TP activation all-reduce term; keep MoE a2a +
        # FSDP (params no longer model-sharded -> larger fsdp gathers).
        tokens = plan.shape.global_batch * plan.shape.seq_len
        act_row = plan.cfg.d_model * 2
        passes = 3.0
        tp_term = (4.0 * (tokens / (chips // 16)) * act_row
                   * plan.cfg.n_layers * passes) * chips
        p_nonexpert = costs.notes["p_total"] - (
            plan.cfg.n_layers * plan.cfg.n_routed_experts * 3
            * plan.cfg.d_model * (plan.cfg.d_ff_expert or plan.cfg.d_ff))
        extra_fsdp = (plan.microbatches * 2.0 + 1.0) * p_nonexpert * 2 * (
            1 - 1 / 16) * chips
        costs = dataclasses.replace(
            costs, collective_bytes=costs.collective_bytes - tp_term
            + extra_fsdp)
    if compress == "int8_ef":
        # grad reduce-scatter payload drops 4x vs bf16 x2... int8 = /2 vs bf16
        p_loc = costs.notes["p_total"] / 16 * 2
        costs = dataclasses.replace(
            costs, collective_bytes=costs.collective_bytes - 0.5 * p_loc * chips)

    tokens = plan.shape.global_batch * (
        plan.shape.seq_len if plan.kind != "decode" else 1)
    rt = hlo_analysis.RooflineTerms(
        hw=hlo_analysis.TPU_V5E,  # the pod these cells model
        name=tag, chips=chips, hlo_flops=costs.flops,
        hlo_bytes=costs.hbm_bytes, collective_bytes=costs.collective_bytes,
        model_flops=plan.cfg.model_flops(tokens,
                                         training=plan.kind == "train"))
    entry = {
        "tag": tag, "arch": arch, "shape": shape,
        "microbatches": plan.microbatches, "compress": compress,
        "rules_override": [list(x) for x in plan.rules_override],
        "compile_s": round(time.time() - t0, 1),
        "hlo_collective_bytes_per_iter": coll.total_bytes,
        "hlo_collective_by_op": coll.by_op,
        "temp_gb": ma.temp_size_in_bytes / 1e9,
        "arg_gb": ma.argument_size_in_bytes / 1e9,
        **rt.summary(),
    }
    path = os.path.join(out_dir, tag + ".json")
    with open(path, "w") as f:
        json.dump(entry, f, indent=1)
    print(f"[perf] {tag}: class={entry['class']} mfu={entry['mfu_bound']:.3f} "
          f"tc={entry['t_compute_s']:.3e} tm={entry['t_memory_s']:.3e} "
          f"tx={entry['t_collective_s']:.3e} temp={entry['temp_gb']:.1f}GB "
          f"hlo_coll/iter={coll.total_bytes/1e9:.2f}GB", flush=True)
    return entry


def main():
    # Cell 1: deepseek-moe train
    run_variant("ds_train_base", "deepseek-moe-16b", "train_4k")
    run_variant("ds_train_ep_only", "deepseek-moe-16b", "train_4k",
                rules_override=EP_ONLY, tp_layers=False)
    run_variant("ds_train_ep_int8", "deepseek-moe-16b", "train_4k",
                rules_override=EP_ONLY, tp_layers=False, compress="int8_ef")
    # Cell 2: nemotron train
    run_variant("nmt_train_mb4", "nemotron-4-340b", "train_4k",
                microbatches=4)
    run_variant("nmt_train_mb4_int8", "nemotron-4-340b", "train_4k",
                microbatches=4, compress="int8_ef")
    # Cell 3: zamba2 long-context decode — cache-len sharding ablation
    run_variant("zmb_long_base", "zamba2-7b", "long_500k")
    run_variant("zmb_long_nocachelen", "zamba2-7b", "long_500k",
                rules_override=(("cache_len", None),))


if __name__ == "__main__":
    main()
