"""Fine-grained Mixture-of-Experts (DeepSeek-MoE style).

Top-k routing over the router's ``router_experts`` fine-grained experts
plus ``n_shared_experts`` always-on shared experts.

Dispatch is the linear-memory permute/scatter formulation (not the GShard
[n, e, cap] one-hot, whose dispatch tensor is quadratic in tokens): token
replicas are slotted into a static [e, cap, d] buffer via scatter-add,
expert FFNs run as one batched [e, cap, *] matmul, and results gather back
weighted by their gates.  With the expert dimension sharded over the
"model" mesh axis this is expert parallelism: XLA inserts the token
all-to-alls, moving tokens to the chips that hold the experts —
compute-near-shard, the cluster-scale analogue of DAMOV's NDP insight.

Routing is a softmax over the router's outputs and then top-k, with the
gates renormalized; or DeepSeek-V2's group-limited greedy top-k (``n_group``
groups, the best ``topk_group`` of them by their best expert), with the
gates kept as the softmax gave them (``norm_topk_prob`` off) and scaled by
``routed_scaling_factor``.

Under expert parallelism a chip holds ``n_routed_experts`` of the router's
``router_experts`` (those from ``first_held_expert`` on): it routes over
all of them, drops the choices of absent experts from its buffer, and
returns its own experts' part of the output (the shared experts in full).

Returns the switch-style load-balance auxiliary loss alongside the output.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import Params, _dtype, _init
from .sharding import constrain

__all__ = ["moe_init", "moe_axes", "moe_fwd", "CAPACITY_FACTOR"]

CAPACITY_FACTOR = 1.25


def moe_init(key, cfg: ModelConfig) -> Params:
    d = cfg.d_model
    f = cfg.d_ff_expert or cfg.d_ff
    e = cfg.n_routed_experts
    ks = jax.random.split(key, 5)
    p: Params = {
        "router": _init(ks[0], (d, cfg.router_experts), d ** -0.5),
        "w_gate": _init(ks[1], (e, d, f), d ** -0.5),
        "w_up": _init(ks[2], (e, d, f), d ** -0.5),
        "w_down": _init(ks[3], (e, f, d), f ** -0.5),
    }
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        ks2 = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": _init(ks2[0], (d, fs), d ** -0.5),
            "w_up": _init(ks2[1], (d, fs), d ** -0.5),
            "w_down": _init(ks2[2], (fs, d), fs ** -0.5),
        }
    return p


def moe_axes(cfg: ModelConfig) -> Params:
    p: Params = {
        "router": ("fsdp", None),
        "w_gate": ("experts", "fsdp", "expert_ffn"),
        "w_up": ("experts", "fsdp", "expert_ffn"),
        "w_down": ("experts", "expert_ffn", "fsdp"),
    }
    if cfg.n_shared_experts:
        p["shared"] = {
            "w_gate": ("fsdp", "ffn"),
            "w_up": ("fsdp", "ffn"),
            "w_down": ("ffn", "fsdp"),
        }
    return p


def _route(probs: jax.Array, cfg: ModelConfig) -> tuple[jax.Array, jax.Array]:
    """(gates, expert ids), each [n, top_k], from the router's softmax."""
    k = cfg.top_k
    if cfg.n_group > 1:
        n = probs.shape[0]
        grouped = probs.reshape(n, cfg.n_group, -1)
        _, groups = jax.lax.top_k(grouped.max(-1), cfg.topk_group)
        chosen = jax.nn.one_hot(groups, cfg.n_group, dtype=jnp.bool_).any(1)
        probs = jnp.where(chosen[:, :, None], grouped, 0.0).reshape(n, -1)
    gate, idx = jax.lax.top_k(probs, k)                            # [n, k]
    if cfg.norm_topk_prob:
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    if cfg.routed_scaling_factor != 1.0:
        gate = gate * cfg.routed_scaling_factor
    return gate, idx


def moe_fwd(p: Params, cfg: ModelConfig, x) -> tuple[jax.Array, jax.Array]:
    """x: [B, S, d] -> (y, aux_loss)."""
    dt = _dtype(cfg)
    b, s, d = x.shape
    n = b * s
    e, k = cfg.n_routed_experts, cfg.top_k
    e_all = cfg.router_experts
    nk = n * k
    # flattening (batch, seq) -> tokens mixes two sharded dims; pin the
    # token sharding explicitly or SPMD replicates the whole [n, d] matrix
    xt = constrain(x.reshape(n, d), "tokens", None)

    logits = (xt @ p["router"].astype(dt)).astype(jnp.float32)     # [n, e_all]
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = _route(probs, cfg)

    # Load-balance aux loss (Switch/GShard): e * mean(frac_tokens * frac_prob).
    assign = jnp.zeros((e_all,), jnp.float32).at[idx.reshape(-1)].add(1.0) / nk
    aux = e_all * jnp.sum(assign * probs.mean(0)) * cfg.router_aux_coef

    # ---- permute: slot every (token, choice) into its expert's buffer ----
    cap_f = cfg.moe_capacity_factor or CAPACITY_FACTOR
    cap = cfg.expert_capacity or max(1, int(cap_f * n * k / e))
    flat_e = idx.reshape(-1)                                       # [nk]
    rows = e
    if e_all != e:
        # a share of the experts is held here: a choice of an absent one
        # goes to row e, which is dropped with the trash slot
        flat_e = flat_e - cfg.first_held_expert
        flat_e = jnp.where((flat_e >= 0) & (flat_e < e), flat_e, e)
        rows = e + 1
    order = jnp.argsort(flat_e)                                    # stable
    sorted_e = flat_e[order]
    counts = jnp.zeros((rows,), jnp.int32).at[flat_e].add(1)
    starts = jnp.cumsum(counts) - counts                           # exclusive
    slot_sorted = jnp.arange(nk, dtype=jnp.int32) - starts[sorted_e]
    slot = jnp.zeros((nk,), jnp.int32).at[order].set(slot_sorted)
    keep = slot < cap
    if rows != e:
        keep = keep & (flat_e < e)
    safe_slot = jnp.where(keep, slot, cap)                         # row `cap` = trash

    tok = jnp.arange(nk, dtype=jnp.int32) // k
    x_rep = constrain(xt[tok].astype(dt), "tokens", None)
    expert_in = (
        jnp.zeros((rows, cap + 1, d), dt)
        .at[flat_e, safe_slot]
        .add(x_rep)
    )[:e, :cap]
    # EP boundary: the scatter above is the token all-to-all once `experts`
    # maps to the model axis.
    expert_in = constrain(expert_in, "experts", None, None)

    # ---- expert FFNs: one batched matmul over the expert dimension -------
    gate_act = jax.nn.silu(
        jnp.einsum("ecd,edf->ecf", expert_in, p["w_gate"].astype(dt))
    )
    up = jnp.einsum("ecd,edf->ecf", expert_in, p["w_up"].astype(dt))
    out = jnp.einsum("ecf,efd->ecd", gate_act * up, p["w_down"].astype(dt))
    out = constrain(out, "experts", None, None)

    # ---- unpermute: gather outputs back and combine with gates -----------
    y_rep = out[jnp.minimum(flat_e, e - 1) if rows != e else flat_e,
                jnp.minimum(slot, cap - 1)]                        # [nk, d]
    y_rep = constrain(y_rep, "tokens", None)
    w = (gate.reshape(-1) * keep.astype(jnp.float32)).astype(dt)
    y = jnp.zeros((n, d), dt).at[tok].add(y_rep * w[:, None])
    y = constrain(y, "tokens", None)

    if cfg.n_shared_experts:
        sp = p["shared"]
        act = jax.nn.silu(xt @ sp["w_gate"].astype(dt)) * (
            xt @ sp["w_up"].astype(dt))
        y = y + act @ sp["w_down"].astype(dt)
    return y.reshape(b, s, d), aux
