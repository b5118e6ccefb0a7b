"""DeepSeek-V2's forward pass, written plainly: the reference that the
program's ``deepseek-v2`` model is compared with.

From arXiv:2405.04434 (section 2.1, multi-head latent attention; section
2.2, DeepSeekMoE) and the model's published ``config.json``, whose keys
``config`` uses.  Float32 throughout, under the highest matmul precision;
a whole sequence at once, causal, with no cache, no kernels and no
batching tricks.  Nothing of the program is imported.

- Attention: the query through its low-rank bottleneck (``wq_a``, RMSNorm,
  ``wq_b``); the key and value from the compressed latent (``wkv_a`` gives
  the latent and the one rope key shared by every head, RMSNorm on the
  latent, ``wkv_b`` expands it to per-head keys and values); the score of a
  head is its no-rope part plus its rope part, times 1/sqrt(128 + 64) and
  YaRN's mscale squared.
- YaRN: the rope frequencies mixed between kept and divided by ``factor``
  over the ramp ``beta_fast``..``beta_slow``, cos/sin times the ratio of
  the two mscales (1 as published).
- The first ``first_k_dense_replace`` layers have a SwiGLU MLP of
  ``intermediate_size``; the others are MoE: softmax over the router's
  ``n_routed_experts``, group-limited greedy top-``num_experts_per_tok``
  (``n_group`` groups ranked by their best expert, the best ``topk_group``
  kept), gates renormalized only if ``norm_topk_prob``, else times
  ``routed_scaling_factor``; plus ``n_shared_experts`` shared experts as
  one SwiGLU MLP of ``n_shared_experts * moe_intermediate_size``.

Departures, each deliberate:

- Rope rotates the two halves of a head's rope part; the published
  weights rotate interleaved pairs.  That is the same map after a fixed
  permutation of the rope columns of ``wq_b`` and ``wkv_a``, and the
  weights here are random.
- ``held=(start, stop)`` computes only the routed experts ``start..stop-1``
  (one chip's share under expert parallelism): the router still ranks all
  of them, and the others' part of the output is left out.  None holds all.
- Every routed token reaches its expert: there is no capacity and no drop.
- Matrices are stored ``[in, out]``; per-head weights are flattened
  head-major (``[in, heads * size]``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def swiglu(p, x):
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_tables(config: dict, positions: np.ndarray):
    """cos and sin, ``[S, qk_rope_head_dim / 2]``, YaRN applied."""
    dim = config["qk_rope_head_dim"]
    base = config["rope_theta"]
    inv = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    scale = 1.0
    yarn = config.get("rope_scaling")
    if yarn:
        factor = yarn["factor"]
        orig = yarn["original_max_position_embeddings"]

        def correction_dim(rotations):
            return (dim * math.log(orig / (rotations * 2 * math.pi))
                    / (2 * math.log(base)))

        low = max(math.floor(correction_dim(yarn["beta_fast"])), 0)
        high = min(math.ceil(correction_dim(yarn["beta_slow"])), dim - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
        extrapolate = 1.0 - ramp
        inv = inv / factor * (1.0 - extrapolate) + inv * extrapolate
        scale = (yarn_mscale(factor, yarn["mscale"])
                 / yarn_mscale(factor, yarn["mscale_all_dim"]))
    angles = np.asarray(positions, np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(angles) * scale, jnp.float32),
            jnp.asarray(np.sin(angles) * scale, jnp.float32))


def rope(x, cos, sin):
    """x: [B, S, H, D]; rotates (x[:D/2], x[D/2:]) by the position's angles."""
    half = x.shape[-1] // 2
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def attention(p, x, config: dict, cos, sin):
    b, s, _ = x.shape
    h = config["num_attention_heads"]
    dn, dr = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    dv, r = config["v_head_dim"], config["kv_lora_rank"]
    eps = config["rms_norm_eps"]

    q = rms_norm(x @ p["wq_a"], p["q_norm"], eps) @ p["wq_b"]
    q = q.reshape(b, s, h, dn + dr)
    q_nope, q_pe = q[..., :dn], rope(q[..., dn:], cos, sin)

    kv_a = x @ p["wkv_a"]
    c_kv = rms_norm(kv_a[..., :r], p["kv_norm"], eps)
    k_pe = rope(kv_a[..., None, r:], cos, sin)            # one head, shared
    kv = (c_kv @ p["wkv_b"]).reshape(b, s, h, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]

    scale = (dn + dr) ** -0.5
    yarn = config.get("rope_scaling")
    if yarn and yarn.get("mscale_all_dim"):
        scale *= yarn_mscale(yarn["factor"], yarn["mscale_all_dim"]) ** 2
    scores = (jnp.einsum("bshd,bthd->bhst", q_nope, k_nope)
              + jnp.einsum("bshd,btd->bhst", q_pe, k_pe[:, :, 0])) * scale
    causal = np.tril(np.ones((s, s), dtype=bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", w, v).reshape(b, s, h * dv)
    return out @ p["wo"]


def gates(p, x, config: dict):
    """[tokens, n_routed_experts]: each expert's gate for each token, zero
    where it was not chosen."""
    scores = jax.nn.softmax(x @ p["router"], axis=-1)
    n, e = scores.shape
    g = config["n_group"]
    group_best = scores.reshape(n, g, e // g).max(axis=-1)
    _, top_groups = jax.lax.top_k(group_best, config["topk_group"])
    in_group = jnp.zeros((n, g), bool).at[
        jnp.arange(n)[:, None], top_groups].set(True)
    allowed = jnp.repeat(in_group, e // g, axis=1)
    weight, idx = jax.lax.top_k(jnp.where(allowed, scores, 0.0),
                                config["num_experts_per_tok"])
    if config["norm_topk_prob"]:
        weight = weight / (weight.sum(-1, keepdims=True) + 1e-20)
    else:
        weight = weight * config["routed_scaling_factor"]
    return jnp.zeros((n, e)).at[jnp.arange(n)[:, None], idx].add(weight)


def moe(p, x, config: dict, held=None):
    """One MoE layer over tokens ``x`` [n, d]: the held routed experts'
    part plus the shared experts.  ``p["w_gate"]`` etc. hold the experts
    ``held[0]..held[1]-1`` (all of the router's where ``held`` is None)."""
    e = config["n_routed_experts"]
    start, stop = held if held is not None else (0, e)
    gate = gates(p, x, config)[:, start:stop]             # [n, held]
    act = jax.nn.silu(jnp.einsum("nd,edf->nef", x, p["w_gate"])) \
        * jnp.einsum("nd,edf->nef", x, p["w_up"])
    routed = jnp.einsum("nef,efd->ned", act, p["w_down"])
    return jnp.einsum("ne,ned->nd", gate, routed) + swiglu(p["shared"], x)


def forward(params, tokens, config: dict, held=None):
    """Logits ``[B, S, vocab]`` of ``tokens`` ``[B, S]`` (compiled whole,
    so that it runs as one program and not op by op)."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, t: _forward(p, t, config, held))(params,
                                                                  tokens)


def _forward(params, tokens, config: dict, held):
    with jax.default_matmul_precision("highest"):
        eps = config["rms_norm_eps"]
        x = jnp.asarray(params["embed"], jnp.float32)[tokens]
        cos, sin = rope_tables(config, np.arange(tokens.shape[1]))
        for i, lp in enumerate(params["layers"]):
            x = x + attention(lp, rms_norm(x, lp["attn_norm"], eps), config,
                              cos, sin)
            h = rms_norm(x, lp["ffn_norm"], eps)
            if i < config["first_k_dense_replace"]:
                x = x + swiglu(lp["ffn"], h)
            else:
                b, s, d = h.shape
                x = x + moe(lp["ffn"], h.reshape(b * s, d), config,
                            held).reshape(b, s, d)
        return rms_norm(x, params["final_norm"], eps) @ params["head"]
