"""Core transformer layers: norms, RoPE, GQA/MLA attention, MLPs.

Functional style: parameters are plain dict pytrees; a parallel pytree of
logical-axis tuples (see ``sharding.py``) is produced by the matching
``*_axes`` helpers.  All matmuls run in the config compute dtype (bf16 by
default) with f32 softmax/normalization.

Attention implementations:

- ``naive``:   materialized [S, S] scores — reference semantics.
- ``chunked``: online-softmax scan over KV chunks — numerically identical,
  O(S * chunk) live memory; this is what long-sequence prefill lowers to
  (and the jnp oracle for the Pallas flash kernel).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig

__all__ = [
    "rms_norm", "rms_norm_init", "rms_norm_axes",
    "apply_rope",
    "attention_init", "attention_axes", "attention_fwd", "attention_decode",
    "mla_init", "mla_axes", "mla_fwd", "mla_decode",
    "mlp_init", "mlp_axes", "mlp_fwd",
]

Params = dict[str, Any]


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _init(key, shape, scale, dtype=jnp.float32):
    return (scale * jax.random.truncated_normal(key, -2.0, 2.0, shape)).astype(dtype)


# --------------------------------------------------------------------------
# RMSNorm
# --------------------------------------------------------------------------
def rms_norm_init(d: int) -> Params:
    return {"scale": jnp.ones((d,), jnp.float32)}


def rms_norm_axes() -> Params:
    return {"scale": ("embed",)}


def rms_norm(x: jax.Array, p: Params, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * p["scale"]
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# RoPE (rotate-half convention)
# --------------------------------------------------------------------------
def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature for a context stretched ``factor``
    times (DeepSeek-V2's ``yarn_get_mscale``)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn(freq: np.ndarray, d: int, theta: float,
          scaling: dict) -> tuple[np.ndarray, float]:
    """YaRN (arXiv:2309.00071, as DeepSeek-V2 applies it): frequencies
    that turn fewer than ``beta_slow`` times over the original context are
    divided by ``factor``, those that turn more than ``beta_fast`` times
    are kept, with a linear ramp between; and cos/sin are scaled by the
    ratio of the two mscales."""
    factor = scaling["factor"]
    orig = scaling["original_max_position_embeddings"]

    def dim_of(rotations: float) -> float:
        return (d * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(scaling["beta_fast"])), 0)
    high = min(math.ceil(dim_of(scaling["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2, dtype=np.float32) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    freq = freq / factor * ramp + freq * (1.0 - ramp)
    mscale = (yarn_mscale(factor, scaling.get("mscale", 1.0))
              / yarn_mscale(factor, scaling.get("mscale_all_dim", 1.0)))
    return freq.astype(np.float32), mscale


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               scaling: dict | None = None) -> jax.Array:
    """x: [..., S, H, D] (D even); positions: broadcastable to [..., S];
    ``scaling``: a YaRN ``rope_scaling`` group, or None."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-np.arange(0, half, dtype=np.float32) / half)
    mscale = 1.0
    if scaling:
        freq, mscale = _yarn(freq, d, theta, scaling)
    angles = positions.astype(jnp.float32)[..., None] * freq  # [..., S, half]
    angles = angles[..., None, :]                             # [..., S, 1, half]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# GQA attention
# --------------------------------------------------------------------------
def attention_init(key, cfg: ModelConfig) -> Params:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    s = d ** -0.5
    p: Params = {
        "wq": _init(ks[0], (d, h, hd), s),
        "wk": _init(ks[1], (d, kv, hd), s),
        "wv": _init(ks[2], (d, kv, hd), s),
        "wo": _init(ks[3], (h, hd, d), (h * hd) ** -0.5),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, hd), jnp.float32)
        p["bk"] = jnp.zeros((kv, hd), jnp.float32)
        p["bv"] = jnp.zeros((kv, hd), jnp.float32)
    return p


def attention_axes(cfg: ModelConfig) -> Params:
    p: Params = {
        "wq": ("fsdp", "heads", None),
        "wk": ("fsdp", "kv_heads", None),
        "wv": ("fsdp", "kv_heads", None),
        "wo": ("heads", None, "fsdp"),
    }
    if cfg.qkv_bias:
        p["bq"] = ("heads", None)
        p["bk"] = ("kv_heads", None)
        p["bv"] = ("kv_heads", None)
    return p


def _project_qkv(p: Params, cfg: ModelConfig, x, positions):
    dt = _dtype(cfg)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _sdpa_naive(q, k, v, *, causal: bool, scale: float,
                q_offset: int | jax.Array = 0):
    """q,k: [B,S,*,D]; v: [B,Sk,G,Dv] (Dv may differ, e.g. MLA)."""
    b, sq, h, d = q.shape
    g = k.shape[2]
    dv = v.shape[-1]
    rep = h // g
    qh = q.reshape(b, sq, g, rep, d)
    scores = jnp.einsum("bsgrd,btgd->bgrst", qh, k).astype(jnp.float32) * scale
    if causal:
        qpos = q_offset + jnp.arange(sq)
        kpos = jnp.arange(k.shape[1])
        mask = qpos[:, None] >= kpos[None, :]
        scores = jnp.where(mask, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bgrst,btgd->bsgrd", w, v)
    return out.reshape(b, sq, h, dv)


def _sdpa_chunked(q, k, v, *, causal: bool, scale: float, chunk: int):
    """Online-softmax scan over KV chunks: identical math, bounded memory."""
    from .sharding import constrain

    b, sq, h, d = q.shape
    sk = k.shape[1]
    g = k.shape[2]
    dv = v.shape[-1]
    rep = h // g
    chunk = min(chunk, sk)
    n_chunks = sk // chunk
    rem = sk - n_chunks * chunk

    # SPMD sharding hints: remat'd scan bodies lose propagated shardings,
    # leaving batch-replicated [.., sq, chunk] score buffers on every chip
    # (§Perf iteration 2).  "kv_heads"/"qkv" shard the group/rep dims when
    # divisible; "batch" always shards.
    qh = constrain(q.reshape(b, sq, g, rep, d),
                   "batch", None, "kv_heads", "qkv", None)
    qpos = jnp.arange(sq)

    # NOTE: the chunk body is rematerialized (flash-attention-backward
    # style): without this, autodiff of the scan stacks every chunk's
    # [.., sq, chunk] score tensor — the full attention matrix in f32,
    # *worse* than naive attention (§Perf iteration 1 in EXPERIMENTS.md).
    @jax.checkpoint
    def one_chunk(carry, inputs):
        m, l, acc = carry
        kc, vc, start = inputs
        s = jnp.einsum("bsgrd,btgd->bgrst", qh, kc).astype(jnp.float32) * scale
        if causal:
            kpos = start + jnp.arange(kc.shape[1])
            mask = qpos[:, None] >= kpos[None, :]
            s = jnp.where(mask, s, -1e30)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p_ = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + p_.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bgrst,btgd->bgrsd", p_.astype(q.dtype), vc
        ).astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    carry_axes = ("batch", "kv_heads", "qkv", None)
    m0 = constrain(jnp.full((b, g, rep, sq), -1e30, jnp.float32), *carry_axes)
    l0 = constrain(jnp.zeros((b, g, rep, sq), jnp.float32), *carry_axes)
    a0 = constrain(jnp.zeros((b, g, rep, sq, dv), jnp.float32),
                   *carry_axes, None)

    kc = k[:, : n_chunks * chunk].reshape(b, n_chunks, chunk, g, d)
    vc = v[:, : n_chunks * chunk].reshape(b, n_chunks, chunk, g, dv)
    kv_axes = (None, "batch", None, "kv_heads", None)
    kc = constrain(kc.transpose(1, 0, 2, 3, 4), *kv_axes)
    vc = constrain(vc.transpose(1, 0, 2, 3, 4), *kv_axes)
    starts = jnp.arange(n_chunks) * chunk
    (m, l, acc), _ = jax.lax.scan(one_chunk, (m0, l0, a0), (kc, vc, starts))
    if rem:
        (m, l, acc), _ = one_chunk(
            (m, l, acc),
            (k[:, n_chunks * chunk:], v[:, n_chunks * chunk:],
             jnp.asarray(n_chunks * chunk)),
        )
    out = acc / jnp.maximum(l[..., None], 1e-30)
    out = out.astype(q.dtype).transpose(0, 3, 1, 2, 4)  # b s g r dv
    return out.reshape(b, sq, h, dv)


def attention_fwd(p: Params, cfg: ModelConfig, x, positions, *,
                  causal: bool = True,
                  kv_override: tuple | None = None,
                  return_kv: bool = False):
    """Full-sequence attention (training / prefill).

    ``kv_override``: (k, v) for cross-attention (encoder-decoder); RoPE is
    skipped on overridden KV.
    ``return_kv``: also return the (roped) K/V for prefill cache writes.
    """
    dt = _dtype(cfg)
    hd = cfg.resolved_head_dim
    if kv_override is None:
        q, k, v = _project_qkv(p, cfg, x, positions)
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
        if cfg.qkv_bias:
            q = q + p["bq"].astype(dt)
        q = apply_rope(q, positions, cfg.rope_theta)
        k, v = kv_override
    scale = hd ** -0.5
    if cfg.attn_impl == "chunked" and kv_override is None:
        out = _sdpa_chunked(q, k, v, causal=causal, scale=scale,
                            chunk=cfg.attn_chunk)
    else:
        out = _sdpa_naive(q, k, v, causal=causal, scale=scale)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))
    if return_kv:
        return y, (k, v)
    return y


def attention_decode(p: Params, cfg: ModelConfig, x, cache: dict, pos) -> tuple:
    """Single-token decode with a preallocated KV cache.

    x: [B, 1, d]; cache: {"k","v": [B, L, KV, hd]}; pos: [B] current index.
    """
    dt = _dtype(cfg)
    positions = pos[:, None]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    def upd(buf, new):
        return jax.vmap(
            lambda b, n, i: jax.lax.dynamic_update_slice(
                b, n.astype(b.dtype), (i, 0, 0))
        )(buf, new, pos)

    k_cache = upd(cache["k"], k)
    v_cache = upd(cache["v"], v)

    b, _, h, d = q.shape
    g = k_cache.shape[2]
    rep = h // g
    qh = q.reshape(b, g, rep, d)
    scores = jnp.einsum("bgrd,btgd->bgrt", qh, k_cache).astype(jnp.float32)
    scores *= d ** -0.5
    valid = jnp.arange(k_cache.shape[1])[None, :] <= pos[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(dt)
    out = jnp.einsum("bgrt,btgd->bgrd", w, v_cache).reshape(b, 1, h, d)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))
    return y, {"k": k_cache, "v": v_cache}


# --------------------------------------------------------------------------
# MLA (DeepSeek-V2 multi-head latent attention)
# --------------------------------------------------------------------------
def mla_init(key, cfg: ModelConfig) -> Params:
    d, h = cfg.d_model, cfg.n_heads
    r = cfg.kv_lora_rank
    dn, dr, dv = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 5)
    s = d ** -0.5
    p: Params = {
        "wq": _init(ks[0], (d, h, dn + dr), s),
        "wkv_a": _init(ks[1], (d, r + dr), s),
        "kv_norm": jnp.ones((r,), jnp.float32),
        "wkv_b": _init(ks[2], (r, h, dn + dv), r ** -0.5),
        "wo": _init(ks[3], (h, dv, d), (h * dv) ** -0.5),
    }
    if cfg.q_lora_rank:
        # the query through a low-rank bottleneck: wq_a, RMSNorm, wq_b
        rq = cfg.q_lora_rank
        del p["wq"]
        p["wq_a"] = _init(ks[0], (d, rq), s)
        p["q_norm"] = jnp.ones((rq,), jnp.float32)
        p["wq_b"] = _init(ks[4], (rq, h, dn + dr), rq ** -0.5)
    return p


def mla_axes(cfg: ModelConfig) -> Params:
    p: Params = {
        "wq": ("fsdp", "heads", None),
        "wkv_a": ("fsdp", "kv_lora"),
        "kv_norm": ("kv_lora",),
        "wkv_b": ("kv_lora", "heads", None),
        "wo": ("heads", None, "fsdp"),
    }
    if cfg.q_lora_rank:
        del p["wq"]
        p["wq_a"] = ("fsdp", "q_lora")
        p["q_norm"] = ("q_lora",)
        p["wq_b"] = ("q_lora", "heads", None)
    return p


def _mla_scale(cfg: ModelConfig) -> float:
    """The softmax scale: 1/sqrt(query head size), times YaRN's mscale
    squared where the rope scaling states ``mscale_all_dim``."""
    scale = (cfg.nope_head_dim + cfg.rope_head_dim) ** -0.5
    yarn = cfg.rope_scaling or {}
    if yarn.get("mscale_all_dim"):
        scale *= yarn_mscale(yarn["factor"], yarn["mscale_all_dim"]) ** 2
    return scale


def _mla_project(p: Params, cfg: ModelConfig, x, positions):
    dt = _dtype(cfg)
    dn, dr = cfg.nope_head_dim, cfg.rope_head_dim
    r = cfg.kv_lora_rank
    if cfg.q_lora_rank:
        c_q = jnp.einsum("bsd,dr->bsr", x, p["wq_a"].astype(dt))
        c_q = rms_norm(c_q, {"scale": p["q_norm"]}, cfg.norm_eps)
        q = jnp.einsum("bsr,rhk->bshk", c_q, p["wq_b"].astype(dt))
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, cfg.rope_scaling)

    kv_a = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"].astype(dt))
    c_kv, k_rope = kv_a[..., :r], kv_a[..., r:]
    c_kv = rms_norm(c_kv, {"scale": p["kv_norm"]}, cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta,
                        cfg.rope_scaling)
    return q_nope, q_rope, c_kv, k_rope[:, :, 0, :]


def _mla_attend(p: Params, cfg: ModelConfig, q_nope, q_rope, c_kv, k_rope,
                *, causal: bool, q_offset=0, valid_len=None):
    """Attention in latent space: absorb wkv_b into the query (the paper's
    inference trick) so the cache stays [B, S, r + dr]."""
    dt = _dtype(cfg)
    dn, dv = cfg.nope_head_dim, cfg.v_head_dim
    wkv_b = p["wkv_b"].astype(dt)          # [r, h, dn+dv]
    wk_b, wv_b = wkv_b[..., :dn], wkv_b[..., dn:]
    # score = q_nope . (c_kv @ wk_b) + q_rope . k_rope  ->  absorb wk_b:
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, wk_b)
    s1 = jnp.einsum("bshr,btr->bhst", q_lat, c_kv)
    s2 = jnp.einsum("bshk,btk->bhst", q_rope, k_rope)
    scores = (s1 + s2).astype(jnp.float32) * _mla_scale(cfg)
    sq, sk = scores.shape[2], scores.shape[3]
    if causal:
        qpos = q_offset + jnp.arange(sq)
        mask = qpos[:, None] >= jnp.arange(sk)[None, :]
        scores = jnp.where(mask, scores, -1e30)
    if valid_len is not None:
        ok = jnp.arange(sk)[None, :] <= valid_len[:, None]
        scores = jnp.where(ok[:, None, None, :], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(dt)
    ctx = jnp.einsum("bhst,btr->bshr", w, c_kv)          # latent context
    out = jnp.einsum("bshr,rhv->bshv", ctx, wv_b)        # [b,s,h,dv]
    return jnp.einsum("bshv,hvd->bsd", out, p["wo"].astype(dt))


def mla_fwd(p: Params, cfg: ModelConfig, x, positions, *, causal=True,
            return_kv: bool = False):
    """Full-sequence MLA.

    Training/prefill expands the latent KV to per-head K/V and runs the
    online-softmax chunked attention (O(S·chunk) memory — the absorbed
    latent form materializes [S, S] scores, fine for decode, fatal for a
    32k prefill); decode (mla_decode) keeps the absorbed form so the cache
    stays [S, r + dr].
    """
    dt = _dtype(cfg)
    dn, dv = cfg.nope_head_dim, cfg.v_head_dim
    q_nope, q_rope, c_kv, k_rope = _mla_project(p, cfg, x, positions)

    wkv_b = p["wkv_b"].astype(dt)                       # [r, h, dn+dv]
    k_nope = jnp.einsum("btr,rhk->bthk", c_kv, wkv_b[..., :dn])
    v = jnp.einsum("btr,rhv->bthv", c_kv, wkv_b[..., dn:])
    h = cfg.n_heads
    k_rope_h = jnp.broadcast_to(k_rope[:, :, None, :],
                                k_rope.shape[:2] + (h, cfg.rope_head_dim))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)      # [b,s,h,dn+dr]
    k = jnp.concatenate([k_nope, k_rope_h], axis=-1)
    scale = _mla_scale(cfg)
    if cfg.attn_impl == "chunked":
        out = _sdpa_chunked(q, k, v, causal=causal, scale=scale,
                            chunk=cfg.attn_chunk)
    else:
        out = _sdpa_naive(q, k, v, causal=causal, scale=scale)
    y = jnp.einsum("bshv,hvd->bsd", out, p["wo"].astype(dt))
    if return_kv:
        return y, (c_kv, k_rope)
    return y


def mla_decode(p: Params, cfg: ModelConfig, x, cache: dict, pos):
    """cache: {"c_kv": [B, L, r], "k_rope": [B, L, dr]}"""
    q_nope, q_rope, c_kv_new, k_rope_new = _mla_project(
        p, cfg, x, pos[:, None]
    )

    def upd(buf, new):
        return jax.vmap(
            lambda b, n, i: jax.lax.dynamic_update_slice(
                b, n.astype(b.dtype), (i, 0))
        )(buf, new, pos)

    c_kv = upd(cache["c_kv"], c_kv_new)
    k_rope = upd(cache["k_rope"], k_rope_new)
    y = _mla_attend(p, cfg, q_nope, q_rope, c_kv, k_rope,
                    causal=False, valid_len=pos)
    return y, {"c_kv": c_kv, "k_rope": k_rope}


# --------------------------------------------------------------------------
# Dense MLPs
# --------------------------------------------------------------------------
def mlp_init(key, cfg: ModelConfig, d_ff: int | None = None) -> Params:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    p: Params = {
        "w_up": _init(ks[0], (d, f), d ** -0.5),
        "w_down": _init(ks[1], (f, d), f ** -0.5),
    }
    if cfg.mlp_kind == "swiglu":
        p["w_gate"] = _init(ks[2], (d, f), d ** -0.5)
    return p


def mlp_axes(cfg: ModelConfig) -> Params:
    p: Params = {"w_up": ("fsdp", "ffn"), "w_down": ("ffn", "fsdp")}
    if cfg.mlp_kind == "swiglu":
        p["w_gate"] = ("fsdp", "ffn")
    return p


def mlp_fwd(p: Params, cfg: ModelConfig, x) -> jax.Array:
    dt = _dtype(cfg)
    up = x @ p["w_up"].astype(dt)
    if cfg.mlp_kind == "swiglu":
        act = jax.nn.silu(x @ p["w_gate"].astype(dt)) * up
    elif cfg.mlp_kind == "relu2":
        r = jax.nn.relu(up)
        act = r * r
    else:
        act = jax.nn.gelu(up)
    return act @ p["w_down"].astype(dt)
