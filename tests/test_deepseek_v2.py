"""DeepSeek-V2 in the program against its plain float32 reference
(``bench/ref_models/deepseek_v2.py``), at a small size on the CPU, with
every mechanism of the published config on: the query's low-rank path,
the latent KV cache, YaRN, a leading dense layer, group-limited routing
(4 groups, the best 2) with gates not renormalized and scaled, 2 shared
experts, and a chip's share of the routed experts."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.ref_models import deepseek_v2 as ref
from repro import configs
from repro.models import LM
from repro.models import moe as M

KEY = jax.random.PRNGKey(0)
B, S, T0 = 2, 12, 8            # batch, sequence, prompt before decoding


def smoke(**kw):
    # dropless: a held expert's buffer takes every token, as the reference
    return configs.get_smoke("deepseek-v2").replace(
        remat=False, expert_capacity=B * S, **kw)


def hf_config(cfg) -> dict:
    """The reference's config (HF ``config.json`` keys) of a program
    configuration."""
    return {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "q_lora_rank": cfg.q_lora_rank, "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.nope_head_dim,
        "qk_rope_head_dim": cfg.rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "intermediate_size": cfg.d_ff,
        "moe_intermediate_size": cfg.d_ff_expert,
        "n_routed_experts": cfg.router_experts,
        "n_shared_experts": cfg.n_shared_experts,
        "num_experts_per_tok": cfg.top_k, "n_group": cfg.n_group,
        "topk_group": cfg.topk_group, "norm_topk_prob": cfg.norm_topk_prob,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "first_k_dense_replace": cfg.first_dense_layers,
        "num_hidden_layers": cfg.n_layers, "rope_theta": cfg.rope_theta,
        "rope_scaling": cfg.rope_scaling, "rms_norm_eps": cfg.norm_eps,
        "vocab_size": cfg.vocab,
    }


def random_params(lm, seed=1):
    """The program's parameters at their seeded initialization, with every
    norm scale drawn about 1, so that a norm applied with the wrong weight
    shows."""
    params = jax.jit(lm.init)(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)

    def draw(path, a):
        if "norm" in jax.tree_util.keystr(path) or path[-1].key == "scale":
            return a + 0.2 * rng.standard_normal(a.shape, np.float32)
        return a

    return jax.tree_util.tree_map_with_path(draw, params)


def to_reference(params, cfg) -> dict:
    """The program's parameters in the reference's layout."""
    def attn(a):
        return {"wq_a": a["wq_a"], "q_norm": a["q_norm"],
                "wq_b": a["wq_b"].reshape(cfg.q_lora_rank, -1),
                "wkv_a": a["wkv_a"], "kv_norm": a["kv_norm"],
                "wkv_b": a["wkv_b"].reshape(cfg.kv_lora_rank, -1),
                "wo": a["wo"].reshape(-1, cfg.d_model)}

    layers = []
    for key in ("dense_layers", "layers"):
        stack = params[key]
        for i in range(jax.tree.leaves(stack)[0].shape[0]):
            lp = jax.tree.map(lambda a: a[i], stack)
            layers.append(dict(attn(lp["attn"]),
                               attn_norm=lp["ln1"]["scale"],
                               ffn_norm=lp["ln2"]["scale"],
                               ffn=lp["mixer"]))
    return {"embed": params["embed"], "head": params["head"],
            "final_norm": params["ln_f"]["scale"], "layers": layers}


def tokens():
    return jax.random.randint(jax.random.PRNGKey(7), (B, S), 1, 512)


def worst(got, want) -> float:
    """Largest difference, as a share of the largest reference logit."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / np.abs(want).max())


# Float32 on both sides: the two differ only in the order of float32 sums
# and the reading of the same formulas (absorbed vs expanded attention in
# decode, a gather-scatter dispatch vs a dense gate matrix), about 1e-6 of
# the logits' scale; 1e-4 leaves a hundred times that and is still a
# hundred times below what bf16 rounding gives (next tolerance).
F32_TOL = 1e-4
# bf16 in the program (weights and activations rounded to 8 mantissa
# bits, 2e-3 each, through 3 layers whose routed experts' gates are scaled
# 16x) against the float32 reference.  Rounding flips a near-tied expert
# choice at a few tokens (1 to 3 of 24 here), and those tokens' logits
# move wholesale, as a bf16 deployment's would; so the bf16 case bounds
# the median over positions, which reads 1.3e-2 to 2.4e-2 of the logits'
# scale on seeds 1 to 3: 5e-2.
BF16_TOL = 5e-2


@pytest.fixture(scope="module")
def f32_case():
    cfg = smoke(dtype="float32")
    lm = LM(cfg)
    params = random_params(lm)
    want = ref.forward(to_reference(params, cfg), tokens(), hf_config(cfg))
    return cfg, lm, params, want


def test_forward_matches_reference(f32_case):
    cfg, lm, params, want = f32_case
    got, _ = jax.jit(lm.forward)(params, tokens())
    assert worst(got, want) < F32_TOL


def test_prefill_then_decode_through_latent_cache_matches_reference(f32_case):
    cfg, lm, params, want = f32_case
    toks = tokens()
    cache = lm.init_cache(B, 16, dtype="float32")
    assert set(cache) == {"dense_attn", "attn"}
    assert cache["attn"]["c_kv"].shape == (2, B, 16, cfg.kv_lora_rank)
    logits, cache, pos = jax.jit(lm.prefill)(params, toks[:, :T0], cache)
    errs = [worst(logits[:, 0], want[:, T0 - 1])]
    step = jax.jit(lm.decode_step)
    for t in range(T0, S):
        logits, cache = step(params, toks[:, t:t + 1], cache, pos)
        pos = pos + 1
        errs.append(worst(logits[:, 0], want[:, t]))
    assert max(errs) < F32_TOL, errs


def test_bf16_forward_matches_reference_loosely(f32_case):
    cfg, _, params, want = f32_case
    got, _ = jax.jit(LM(cfg.replace(dtype="bfloat16")).forward)(params,
                                                                 tokens())
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    per_position = np.abs(got - want).max(-1) / np.abs(want).max()
    assert np.median(per_position) < BF16_TOL
    # the float32 tolerance tells bf16 apart at every position
    assert per_position.min() > F32_TOL


@pytest.mark.parametrize("change", [
    {"rope_scaling": None},
    {"n_group": 1, "topk_group": 1},
    {"norm_topk_prob": True},
])
def test_each_mechanism_moves_the_logits_beyond_the_tolerance(f32_case,
                                                              change):
    """The reference with one mechanism left out differs from the program
    by more than the float32 tolerance, so the comparison sees each."""
    cfg, _, params, want = f32_case
    other = ref.forward(to_reference(params, cfg), tokens(),
                        dict(hf_config(cfg), **change))
    assert worst(other, want) > 10 * F32_TOL


def test_held_expert_shares_sum_to_the_whole_layer(f32_case):
    """Eight chips' shares of one MoE layer (2 of the router's 16 experts
    each, through the program's layer), with the shared experts counted
    once, add up to the reference's whole layer; each share is the
    reference's share."""
    cfg, _, params, _ = f32_case
    chips, per = 8, cfg.router_experts // 8
    p = jax.tree.map(lambda a: a[0], params["layers"])["mixer"]
    x = jax.random.normal(jax.random.PRNGKey(3), (B * S, cfg.d_model))
    hf = hf_config(cfg)
    with jax.default_matmul_precision("highest"):
        whole = jax.jit(lambda p, x: ref.moe(p, x, hf))(p, x)
        shared = jax.jit(ref.swiglu)(p["shared"], x)
    atol = F32_TOL * float(jnp.abs(whole).max())
    total = -(chips - 1) * shared
    for c in range(chips):
        lo, hi = c * per, (c + 1) * per
        share = cfg.replace(n_routed_experts=per,
                            n_router_experts=cfg.router_experts,
                            first_held_expert=lo)
        part = {k: (v[lo:hi] if k in ("w_gate", "w_up", "w_down") else v)
                for k, v in p.items()}
        y, _ = jax.jit(lambda p, x: M.moe_fwd(p, share, x[None]))(part, x)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(lambda p, x: ref.moe(p, x, hf, (lo, hi)))(part, x)
        np.testing.assert_allclose(y[0], want, rtol=0, atol=atol)
        total = total + y[0]
    assert worst(total, whole) < F32_TOL


def test_published_numbers():
    cfg = configs.get("deepseek-v2")
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.vocab) == \
        (60, 5120, 128, 102_400)
    assert (cfg.first_dense_layers, cfg.d_ff) == (1, 12_288)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.nope_head_dim,
            cfg.rope_head_dim, cfg.v_head_dim) == (1536, 512, 128, 64, 128)
    assert cfg.rope_scaling["factor"] == 40
    assert cfg.rope_scaling["mscale"] == 0.707
    assert cfg.rope_scaling["original_max_position_embeddings"] == 4096
    assert (cfg.router_experts, cfg.d_ff_expert, cfg.top_k,
            cfg.n_shared_experts) == (160, 1536, 6, 2)
    assert (cfg.n_group, cfg.topk_group, cfg.norm_topk_prob,
            cfg.routed_scaling_factor) == (8, 3, False, 16.0)
    assert cfg.dtype == "bfloat16"
    # the paper's 236B parameters, 21B of them active for each token
    assert round(cfg.param_count() / 1e9) == 236
    assert round(cfg.active_param_count() / 1e9) == 21
