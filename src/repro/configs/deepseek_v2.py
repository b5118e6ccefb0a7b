"""deepseek-v2 [arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2]

60L d_model=5120 128H vocab=102400.  MLA on every layer: q through a
1536-rank bottleneck, a 512-rank latent KV cache, 128 + 64 (rope) query /
key head size, 128 value head size; YaRN rope (factor 40 over 4096).  The
first layer is dense (d_ff=12288); the other 59 are MoE: 2 shared + 160
routed experts of width 1536, top-6 by group-limited greedy routing (8
groups, the best 3), gates not renormalized and scaled by 16.
"""

from ..models.config import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2",
    family="moe",
    n_layers=60,
    d_model=5120,
    n_heads=128,
    n_kv_heads=128,
    d_ff=12_288,
    vocab=102_400,
    mlp_kind="swiglu",
    norm_eps=1e-6,
    rope_theta=10_000.0,
    rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 40,
                  "mscale": 0.707, "mscale_all_dim": 0.707,
                  "original_max_position_embeddings": 4096, "type": "yarn"},
    first_dense_layers=1,
    n_routed_experts=160,
    n_shared_experts=2,
    top_k=6,
    d_ff_expert=1536,
    n_group=8,
    topk_group=3,
    norm_topk_prob=False,
    routed_scaling_factor=16.0,
    q_lora_rank=1536,
    kv_lora_rank=512,
    rope_head_dim=64,
    nope_head_dim=128,
    v_head_dim=128,
)

# Every mechanism of CONFIG at CPU size: a dense layer then two MoE layers,
# 4 groups of 4 experts with the best 2 groups, YaRN as published.
SMOKE = CONFIG.replace(
    name="deepseek-v2-smoke",
    n_layers=3,
    d_model=64,
    n_heads=4,
    n_kv_heads=4,
    d_ff=128,
    vocab=512,
    n_routed_experts=16,
    top_k=3,
    d_ff_expert=32,
    n_group=4,
    topk_group=2,
    q_lora_rank=48,
    kv_lora_rank=32,
    rope_head_dim=8,
    nope_head_dim=16,
    v_head_dim=16,
    attn_chunk=64,
)
