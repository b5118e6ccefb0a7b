"""Unified model assembly for every assigned architecture.

One :class:`LM` wraps config-driven blocks:

- ``dense``  — [attn + MLP] x L decoder (qwen2.5, phi4-mini, nemotron-4,
  granite; granite is MQA via n_kv_heads=1, nemotron uses squared-ReLU).
- ``moe``    — [attn|MLA + fine-grained MoE] x L (deepseek-moe, deepseek-v2-lite,
  deepseek-v2), behind ``first_dense_layers`` [attn|MLA + MLP] layers
  (deepseek-v2's first layer) scanned as a stack of their own.
- ``ssm``    — [Mamba2/SSD] x L, attention-free (mamba2-780m).
- ``hybrid`` — Zamba2: groups of SSM blocks with ONE shared attention+MLP
  block applied between groups (weight reuse across its applications).
- ``audio``  — Whisper enc-dec: non-causal encoder over (stub) frame
  embeddings; decoder with self- + cross-attention.
- ``vlm``    — PaliGemma: (stub) patch embeddings prepended to token
  embeddings, Gemma-style decoder.

Layer stacks are ``lax.scan``-ed (stacked params on a leading axis) with
optional rematerialization; the logical-axes pytree mirrors the param
pytree for sharding resolution.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from . import layers as L
from . import moe as M
from . import ssm as S
from .config import ModelConfig

Params = dict[str, Any]

__all__ = ["LM"]


def _stack_init(init_fn, key, n: int):
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys)


def _stack_axes(axes: Params) -> Params:
    return jax.tree.map(
        lambda a: ("layers",) + a,
        axes,
        is_leaf=lambda x: isinstance(x, tuple)
        and all(isinstance(e, (str, type(None))) for e in x),
    )


class LM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ------------------------------------------------------------------
    # Block definitions (attention variant + mixer variant per family).
    # ------------------------------------------------------------------
    def _attn_init(self, key):
        cfg = self.cfg
        if cfg.kv_lora_rank:
            return L.mla_init(key, cfg)
        return L.attention_init(key, cfg)

    def _attn_axes(self):
        cfg = self.cfg
        return L.mla_axes(cfg) if cfg.kv_lora_rank else L.attention_axes(cfg)

    def _mixer_init(self, key, dense: bool = False):
        cfg = self.cfg
        if cfg.is_moe and not dense:
            return M.moe_init(key, cfg)
        return L.mlp_init(key, cfg)

    def _mixer_axes(self, dense: bool = False):
        cfg = self.cfg
        if cfg.is_moe and not dense:
            return M.moe_axes(cfg)
        return L.mlp_axes(cfg)

    def _tf_stacks(self) -> tuple[tuple[str, str, bool, int], ...]:
        """The scanned stacks of transformer layers in order, each as
        (params key, cache key, dense, layers): an MoE model's leading
        dense layers, then the main stack."""
        cfg = self.cfg
        k = cfg.first_dense_layers if cfg.is_moe else 0
        main = ("layers", "attn", False, cfg.n_layers - k)
        return (("dense_layers", "dense_attn", True, k), main) if k \
            else (main,)

    def _tf_layer_init(self, key, *, cross: bool = False,
                       dense: bool = False):
        cfg = self.cfg
        ks = jax.random.split(key, 6)
        p = {
            "ln1": L.rms_norm_init(cfg.d_model),
            "attn": self._attn_init(ks[0]),
            "ln2": L.rms_norm_init(cfg.d_model),
            "mixer": self._mixer_init(ks[1], dense),
        }
        if cross:
            p["ln_x"] = L.rms_norm_init(cfg.d_model)
            p["xattn"] = L.attention_init(ks[2], cfg)
        return p

    def _tf_layer_axes(self, *, cross: bool = False, dense: bool = False):
        p = {
            "ln1": L.rms_norm_axes(),
            "attn": self._attn_axes(),
            "ln2": L.rms_norm_axes(),
            "mixer": self._mixer_axes(dense),
        }
        if cross:
            p["ln_x"] = L.rms_norm_axes()
            p["xattn"] = L.attention_axes(self.cfg)
        return p

    def _tf_layer_fwd(self, p, x, positions, *, causal=True, aux=None,
                      cross_kv=None, return_kv=False, dense=False):
        from .sharding import constrain
        cfg = self.cfg
        kv = None
        # Residual stream sequence-sharded between layers (Megatron-SP);
        # no-op when seq is indivisible (decode) or no mesh is active.
        x = constrain(x, "batch", "seq_residual", None)
        h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
        if cfg.kv_lora_rank:
            y = L.mla_fwd(p["attn"], cfg, h, positions, causal=causal,
                          return_kv=return_kv)
            if return_kv:
                y, (c_kv, k_rope) = y
                # length-shard the prefill KV so the stacked scan outputs
                # match the (flash-decode-sharded) cache layout
                kv = {"c_kv": constrain(c_kv, "batch", "cache_len", None),
                      "k_rope": constrain(k_rope, "batch", "cache_len", None)}
            x = x + y
        else:
            y = L.attention_fwd(p["attn"], cfg, h, positions, causal=causal,
                                return_kv=return_kv)
            if return_kv:
                y, (k, v) = y
                kv = {"k": constrain(k, "batch", "cache_len", "kv_heads",
                                     None),
                      "v": constrain(v, "batch", "cache_len", "kv_heads",
                                     None)}
            x = x + y
        if cross_kv is not None:
            h = L.rms_norm(x, p["ln_x"], cfg.norm_eps)
            x = x + L.attention_fwd(p["xattn"], cfg, h, positions,
                                    causal=False, kv_override=cross_kv)
        h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
        if cfg.is_moe and not dense:
            y, a = M.moe_fwd(p["mixer"], cfg, h)
            x = x + y
            aux = (aux + a) if aux is not None else a
        else:
            x = x + L.mlp_fwd(p["mixer"], cfg, h)
        # exit constraint: the scan saves the *returned* carry; make sure
        # the stacked saved activations are sequence-sharded too.
        x = constrain(x, "batch", "seq_residual", None)
        if return_kv:
            return x, aux, kv
        return x, aux

    def _ssm_layer_init(self, key):
        return {
            "ln": L.rms_norm_init(self.cfg.d_model),
            "ssm": S.ssm_init(key, self.cfg),
        }

    def _ssm_layer_axes(self):
        return {"ln": L.rms_norm_axes(), "ssm": S.ssm_axes(self.cfg)}

    def _ssm_layer_fwd(self, p, x):
        from .sharding import constrain
        x = constrain(x, "batch", "seq_residual", None)
        h = L.rms_norm(x, p["ln"], self.cfg.norm_eps)
        return constrain(x + S.ssm_fwd(p["ssm"], self.cfg, h),
                         "batch", "seq_residual", None)

    # ------------------------------------------------------------------
    # Hybrid (Zamba2) layout.
    # ------------------------------------------------------------------
    @property
    def _hybrid_layout(self) -> tuple[int, int, int]:
        """(n_groups, ssm_per_group, trailing_ssm)."""
        cfg = self.cfg
        g = cfg.n_layers // cfg.attn_every
        per = cfg.attn_every - 1
        trailing = cfg.n_layers - g * cfg.attn_every
        return g, per, trailing

    # ------------------------------------------------------------------
    # init / axes
    # ------------------------------------------------------------------
    def init(self, key) -> Params:
        cfg = self.cfg
        ks = jax.random.split(key, 8)
        p: Params = {
            "embed": (jax.random.normal(ks[0], (cfg.vocab, cfg.d_model))
                      * cfg.d_model ** -0.5).astype(jnp.float32),
            "ln_f": L.rms_norm_init(cfg.d_model),
        }
        if not cfg.tie_embeddings:
            p["head"] = (jax.random.normal(ks[1], (cfg.d_model, cfg.vocab))
                         * cfg.d_model ** -0.5).astype(jnp.float32)

        if cfg.family in ("dense", "moe", "vlm"):
            for key, (pk, _, dense, n) in zip(ks[2:], self._tf_stacks()):
                p[pk] = _stack_init(partial(self._tf_layer_init, dense=dense),
                                    key, n)
        elif cfg.family == "ssm":
            p["layers"] = _stack_init(self._ssm_layer_init, ks[2], cfg.n_layers)
        elif cfg.family == "hybrid":
            g, per, trailing = self._hybrid_layout
            p["ssm_groups"] = _stack_init(
                lambda k: _stack_init(self._ssm_layer_init, k, per), ks[2], g
            )
            p["shared_attn"] = self._tf_layer_init(ks[3])
            if trailing:
                p["ssm_tail"] = _stack_init(self._ssm_layer_init, ks[4], trailing)
        elif cfg.family == "audio":
            p["enc_layers"] = _stack_init(
                self._tf_layer_init, ks[2], cfg.n_enc_layers
            )
            p["enc_ln_f"] = L.rms_norm_init(cfg.d_model)
            p["layers"] = _stack_init(
                partial(self._tf_layer_init, cross=True), ks[3], cfg.n_layers
            )
        else:
            raise ValueError(cfg.family)
        return p

    def axes(self) -> Params:
        cfg = self.cfg
        p: Params = {
            "embed": ("vocab", "fsdp"),
            "ln_f": L.rms_norm_axes(),
        }
        if not cfg.tie_embeddings:
            p["head"] = ("fsdp", "vocab")
        if cfg.family in ("dense", "moe", "vlm"):
            for pk, _, dense, _ in self._tf_stacks():
                p[pk] = _stack_axes(self._tf_layer_axes(dense=dense))
        elif cfg.family == "ssm":
            p["layers"] = _stack_axes(self._ssm_layer_axes())
        elif cfg.family == "hybrid":
            g, per, trailing = self._hybrid_layout
            p["ssm_groups"] = _stack_axes(_stack_axes(self._ssm_layer_axes()))
            p["shared_attn"] = self._tf_layer_axes()
            if trailing:
                p["ssm_tail"] = _stack_axes(self._ssm_layer_axes())
        elif cfg.family == "audio":
            p["enc_layers"] = _stack_axes(self._tf_layer_axes())
            p["enc_ln_f"] = L.rms_norm_axes()
            p["layers"] = _stack_axes(self._tf_layer_axes(cross=True))
        return p

    # ------------------------------------------------------------------
    # forward (teacher forcing / prefill)
    # ------------------------------------------------------------------
    def _maybe_remat(self, fn):
        return jax.checkpoint(fn) if self.cfg.remat else fn

    def _embed(self, params, tokens):
        cfg = self.cfg
        e = params["embed"].astype(jnp.dtype(cfg.dtype))
        from .sharding import constrain
        return constrain(jnp.take(e, tokens, axis=0), "batch", None, None)

    def _unembed(self, params, x):
        cfg = self.cfg
        x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
        w = (params["embed"].T if cfg.tie_embeddings else params["head"])
        return x @ w.astype(x.dtype)

    def _encoder(self, params, enc_embed):
        """Whisper encoder over (stub) frame embeddings."""
        cfg = self.cfg
        positions = jnp.arange(enc_embed.shape[1])[None, :]
        body = self._maybe_remat(
            lambda x, lp: (self._tf_layer_fwd(
                lp, x, positions, causal=False)[0], None)
        )
        x, _ = jax.lax.scan(body, enc_embed, params["enc_layers"])
        return L.rms_norm(x, params["enc_ln_f"], cfg.norm_eps)

    def forward(self, params, tokens, *, extra_embed=None,
                return_hidden: bool = False):
        """Logits (or final hidden states) for a full sequence.

        ``extra_embed``: [B, T, d] — VLM patch embeddings (prepended) or
        Whisper frame embeddings (encoder input).
        ``return_hidden``: return post-final-norm hidden states instead of
        logits (the chunked loss computes the unembedding itself).
        """
        cfg = self.cfg
        x = self._embed(params, tokens)
        aux = jnp.zeros((), jnp.float32)

        cross_kv = None
        if cfg.family == "vlm" and extra_embed is not None:
            x = jnp.concatenate([extra_embed.astype(x.dtype), x], axis=1)
        if cfg.family == "audio":
            assert extra_embed is not None, "audio family needs frame embeddings"
            y_enc = self._encoder(params, extra_embed.astype(x.dtype))

        positions = jnp.arange(x.shape[1])[None, :]

        if cfg.family in ("dense", "moe", "vlm"):
            for pk, _, dense, _ in self._tf_stacks():
                def body(carry, lp, dense=dense):
                    h, a = carry
                    h, a = self._tf_layer_fwd(lp, h, positions, aux=a,
                                              dense=dense)
                    return (h, a), None
                (x, aux), _ = jax.lax.scan(
                    self._maybe_remat(body), (x, aux), params[pk]
                )
        elif cfg.family == "ssm":
            def body(h, lp):
                return self._ssm_layer_fwd(lp, h), None
            x, _ = jax.lax.scan(self._maybe_remat(body), x, params["layers"])
        elif cfg.family == "hybrid":
            shared = params["shared_attn"]

            def group(h, gp):
                def inner(hh, lp):
                    return self._ssm_layer_fwd(lp, hh), None
                h, _ = jax.lax.scan(inner, h, gp)
                h, _ = self._tf_layer_fwd(shared, h, positions)
                return h, None
            x, _ = jax.lax.scan(self._maybe_remat(group), x, params["ssm_groups"])
            if "ssm_tail" in params:
                def tail(h, lp):
                    return self._ssm_layer_fwd(lp, h), None
                x, _ = jax.lax.scan(self._maybe_remat(tail), x, params["ssm_tail"])
        elif cfg.family == "audio":
            def body(carry, lp):
                h, a = carry
                dt = h.dtype
                k = jnp.einsum("bsd,dhk->bshk", y_enc, lp["xattn"]["wk"].astype(dt))
                v = jnp.einsum("bsd,dhk->bshk", y_enc, lp["xattn"]["wv"].astype(dt))
                h, a = self._tf_layer_fwd(lp, h, positions, aux=a,
                                          cross_kv=(k, v))
                return (h, a), None
            (x, aux), _ = jax.lax.scan(
                self._maybe_remat(body), (x, aux), params["layers"]
            )

        if cfg.family == "vlm" and extra_embed is not None:
            x = x[:, extra_embed.shape[1]:]
        if return_hidden:
            return L.rms_norm(x, params["ln_f"], cfg.norm_eps), aux
        return self._unembed(params, x), aux

    # ------------------------------------------------------------------
    # loss (chunked over tokens so [tokens, vocab] logits never fully
    # materialize — vocab reaches 256k)
    # ------------------------------------------------------------------
    def loss(self, params, batch) -> jax.Array:
        cfg = self.cfg
        labels = batch["labels"]
        x, aux = self.forward(
            params, batch["tokens"], extra_embed=batch.get("extra_embed"),
            return_hidden=True,
        )
        b, s, d = x.shape
        xf = x.reshape(b * s, d)
        lf = labels.reshape(b * s)
        chunk = min(8192, b * s)
        n_chunks = max(1, (b * s) // chunk)

        w = (params["embed"].T if cfg.tie_embeddings else params["head"])

        def chunk_loss(carry, inp):
            xc, lc = inp
            logits = (xc @ w.astype(xc.dtype)).astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(
                logits, jnp.maximum(lc, 0)[:, None], axis=-1)[:, 0]
            mask = (lc >= 0).astype(jnp.float32)
            return carry + jnp.sum((lse - gold) * mask), None

        xcs = xf[: n_chunks * chunk].reshape(n_chunks, chunk, d)
        lcs = lf[: n_chunks * chunk].reshape(n_chunks, chunk)
        total, _ = jax.lax.scan(
            jax.checkpoint(chunk_loss), jnp.zeros((), jnp.float32), (xcs, lcs)
        )
        denom = jnp.maximum((lf >= 0).sum(), 1).astype(jnp.float32)
        return total / denom + aux

    # ------------------------------------------------------------------
    # KV / state caches + single-token decode
    # ------------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int, dtype=None) -> Params:
        cfg = self.cfg
        dt = jnp.dtype(dtype or cfg.dtype)
        hd = cfg.resolved_head_dim

        def attn_cache(n_stack: int | None):
            if cfg.kv_lora_rank:
                shape_c = (batch, max_len, cfg.kv_lora_rank)
                shape_r = (batch, max_len, cfg.rope_head_dim)
                if n_stack:
                    shape_c = (n_stack,) + shape_c
                    shape_r = (n_stack,) + shape_r
                return {"c_kv": jnp.zeros(shape_c, dt),
                        "k_rope": jnp.zeros(shape_r, dt)}
            shape = (batch, max_len, cfg.n_kv_heads, hd)
            if n_stack:
                shape = (n_stack,) + shape
            return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}

        def ssm_cache(n_stack: int):
            di, n = cfg.d_inner, cfg.ssm_state
            h, p_ = cfg.n_ssm_heads, cfg.ssm_head_dim
            return {
                "state": jnp.zeros((n_stack, batch, h, n, p_), jnp.float32),
                "conv": jnp.zeros(
                    (n_stack, batch, cfg.ssm_conv - 1, di + 2 * n), dt),
            }

        if cfg.family in ("dense", "moe", "vlm"):
            return {ck: attn_cache(n) for _, ck, _, n in self._tf_stacks()}
        if cfg.family == "ssm":
            return {"ssm": ssm_cache(cfg.n_layers)}
        if cfg.family == "hybrid":
            g, per, trailing = self._hybrid_layout
            c: Params = {
                "ssm_groups": jax.tree.map(
                    lambda a: a.reshape((g, per) + a.shape[1:]),
                    ssm_cache(g * per),
                ),
                "shared_attn": attn_cache(g),
            }
            if trailing:
                c["ssm_tail"] = ssm_cache(trailing)
            return c
        if cfg.family == "audio":
            return {
                "attn": attn_cache(cfg.n_layers),
                "cross_kv": {
                    "k": jnp.zeros(
                        (cfg.n_layers, batch, cfg.enc_ctx, cfg.n_kv_heads, hd),
                        dt),
                    "v": jnp.zeros(
                        (cfg.n_layers, batch, cfg.enc_ctx, cfg.n_kv_heads, hd),
                        dt),
                },
            }
        raise ValueError(cfg.family)

    def cache_axes(self) -> Params:
        """Logical axes mirroring :meth:`init_cache`'s structure."""
        cfg = self.cfg

        def attn_axes(stacked: bool):
            pre = (None,) if stacked else ()
            if cfg.kv_lora_rank:
                return {"c_kv": pre + ("batch", "cache_len", None),
                        "k_rope": pre + ("batch", "cache_len", None)}
            kv = pre + ("batch", "cache_len", "kv_heads", None)
            return {"k": kv, "v": kv}

        def ssm_axes_(extra: int = 1):
            pre = (None,) * extra
            return {
                "state": pre + ("batch", "ssm_heads", None, None),
                "conv": pre + ("batch", None, "ssm_inner"),
            }

        if cfg.family in ("dense", "moe", "vlm"):
            return {ck: attn_axes(True) for _, ck, _, _ in self._tf_stacks()}
        if cfg.family == "ssm":
            return {"ssm": ssm_axes_()}
        if cfg.family == "hybrid":
            _, _, trailing = self._hybrid_layout
            c: Params = {
                "ssm_groups": ssm_axes_(extra=2),
                "shared_attn": attn_axes(True),
            }
            if trailing:
                c["ssm_tail"] = ssm_axes_()
            return c
        if cfg.family == "audio":
            return {
                "attn": attn_axes(True),
                "cross_kv": {
                    "k": (None, "batch", None, "kv_heads", None),
                    "v": (None, "batch", None, "kv_heads", None),
                },
            }
        raise ValueError(cfg.family)

    # ------------------------------------------------------------------
    # prefill: run the full prompt once, writing KV/state caches at
    # offset 0, and return logits for the last position.
    # ------------------------------------------------------------------
    def prefill(self, params, tokens, cache, *, extra_embed=None,
                prompt_len=None):
        """tokens: [B, S] -> (last_logits [B, 1, V], cache, next_pos [B]).

        ``prompt_len``: [B] valid prompt lengths when right-padded to a
        bucket; the causal mask keeps padded keys out of valid queries'
        attention, SSM state updates are masked, and last-token logits are
        gathered per example.
        """
        cfg = self.cfg

        def write(buf, new):
            return jax.lax.dynamic_update_slice(
                buf, new.astype(buf.dtype), (0,) * buf.ndim)

        x = self._embed(params, tokens)
        aux = jnp.zeros((), jnp.float32)
        if cfg.family == "vlm" and extra_embed is not None:
            x = jnp.concatenate([extra_embed.astype(x.dtype), x], axis=1)
        if cfg.family == "audio":
            y_enc = self._encoder(params, extra_embed.astype(x.dtype))
        positions = jnp.arange(x.shape[1])[None, :]
        bsz = x.shape[0]
        if prompt_len is None:
            next_pos = jnp.full((bsz,), x.shape[1], jnp.int32)
        else:
            offset = x.shape[1] - tokens.shape[1]  # vlm prefix tokens
            next_pos = prompt_len.astype(jnp.int32) + offset

        if cfg.family in ("dense", "moe", "vlm"):
            new_cache = {}
            for pk, ck, dense, _ in self._tf_stacks():
                def body(carry, lp, dense=dense):
                    h, a = carry
                    h, a, kv = self._tf_layer_fwd(lp, h, positions, aux=a,
                                                  return_kv=True, dense=dense)
                    return (h, a), kv
                (x, aux), kvs = jax.lax.scan(body, (x, aux), params[pk])
                new_cache[ck] = jax.tree.map(write, cache[ck], kvs)
        elif cfg.family == "ssm":
            def body(h, lp):
                hh = L.rms_norm(h, lp["ln"], cfg.norm_eps)
                y, st = S.ssm_fwd(lp["ssm"], cfg, hh, return_state=True,
                                  prompt_len=prompt_len)
                return h + y, st
            x, sts = jax.lax.scan(body, x, params["layers"])
            new_cache = {"ssm": jax.tree.map(write, cache["ssm"], sts)}
        elif cfg.family == "hybrid":
            shared = params["shared_attn"]

            def group(h, gp):
                def inner(hh, lp):
                    zz = L.rms_norm(hh, lp["ln"], cfg.norm_eps)
                    y, st = S.ssm_fwd(lp["ssm"], cfg, zz, return_state=True,
                                      prompt_len=prompt_len)
                    return hh + y, st
                h, sts = jax.lax.scan(inner, h, gp)
                h, _, kv = self._tf_layer_fwd(shared, h, positions,
                                              return_kv=True)
                return h, (sts, kv)
            x, (gsts, gkvs) = jax.lax.scan(group, x, params["ssm_groups"])
            new_cache = {
                "ssm_groups": jax.tree.map(write, cache["ssm_groups"], gsts),
                "shared_attn": jax.tree.map(write, cache["shared_attn"], gkvs),
            }
            if "ssm_tail" in params:
                def tail(h, lp):
                    zz = L.rms_norm(h, lp["ln"], cfg.norm_eps)
                    y, st = S.ssm_fwd(lp["ssm"], cfg, zz, return_state=True,
                                      prompt_len=prompt_len)
                    return h + y, st
                x, tsts = jax.lax.scan(tail, x, params["ssm_tail"])
                new_cache["ssm_tail"] = jax.tree.map(
                    write, cache["ssm_tail"], tsts)
        elif cfg.family == "audio":
            def body(carry, lp):
                h, a = carry
                dt = h.dtype
                k = jnp.einsum("bsd,dhk->bshk", y_enc,
                               lp["xattn"]["wk"].astype(dt))
                v = jnp.einsum("bsd,dhk->bshk", y_enc,
                               lp["xattn"]["wv"].astype(dt))
                h, a, kv = self._tf_layer_fwd(lp, h, positions, aux=a,
                                              cross_kv=(k, v), return_kv=True)
                return (h, a), (kv, {"k": k, "v": v})
            (x, aux), (kvs, xkvs) = jax.lax.scan(body, (x, aux),
                                                 params["layers"])
            new_cache = {
                "attn": jax.tree.map(write, cache["attn"], kvs),
                "cross_kv": jax.tree.map(write, cache["cross_kv"], xkvs),
            }
        else:
            raise ValueError(cfg.family)

        if prompt_len is None:
            x_last = x[:, -1:, :]
        else:
            x_last = jax.vmap(
                lambda row, i: jax.lax.dynamic_slice(
                    row, (i, 0), (1, row.shape[1]))
            )(x, jnp.maximum(next_pos - 1, 0))
        logits = self._unembed(params, x_last)
        return logits, new_cache, next_pos

    def _decode_tf_layer(self, p, cfg, x, cache, pos, cross_kv=None,
                         dense=False):
        h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
        if cfg.kv_lora_rank:
            y, new_cache = L.mla_decode(p["attn"], cfg, h, cache, pos)
        else:
            y, new_cache = L.attention_decode(p["attn"], cfg, h, cache, pos)
        x = x + y
        if cross_kv is not None:
            h = L.rms_norm(x, p["ln_x"], cfg.norm_eps)
            x = x + L.attention_fwd(p["xattn"], cfg, h, pos[:, None],
                                    causal=False, kv_override=cross_kv)
        h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
        if cfg.is_moe and not dense:
            y, _ = M.moe_fwd(p["mixer"], cfg, h)
            x = x + y
        else:
            x = x + L.mlp_fwd(p["mixer"], cfg, h)
        return x, new_cache

    def decode_step(self, params, tokens, cache, pos):
        """tokens: [B, 1]; pos: [B] write positions. Returns (logits, cache)."""
        cfg = self.cfg
        x = self._embed(params, tokens)

        if cfg.family in ("dense", "moe", "vlm"):
            new_cache = {}
            for pk, ck, dense, _ in self._tf_stacks():
                def body(carry, inp, dense=dense):
                    h = carry
                    lp, lc = inp
                    h, new_c = self._decode_tf_layer(lp, cfg, h, lc, pos,
                                                     dense=dense)
                    return h, new_c
                x, new_cache[ck] = jax.lax.scan(
                    body, x, (params[pk], cache[ck])
                )
            cache = new_cache
        elif cfg.family == "ssm":
            def body(h, inp):
                lp, lc = inp
                hh = L.rms_norm(h, lp["ln"], cfg.norm_eps)
                y, new_c = S.ssm_decode(lp["ssm"], cfg, hh, lc)
                return h + y, new_c
            x, new_cache = jax.lax.scan(body, x, (params["layers"], cache["ssm"]))
            cache = {"ssm": new_cache}
        elif cfg.family == "hybrid":
            shared = params["shared_attn"]

            def group(h, inp):
                gp, gc, ac = inp

                def inner(hh, i2):
                    lp, lc = i2
                    zz = L.rms_norm(hh, lp["ln"], cfg.norm_eps)
                    y, nc = S.ssm_decode(lp["ssm"], cfg, zz, lc)
                    return hh + y, nc
                h, new_gc = jax.lax.scan(inner, h, (gp, gc))
                h, new_ac = self._decode_tf_layer(shared, cfg, h, ac, pos)
                return h, (new_gc, new_ac)
            x, (new_gc, new_ac) = jax.lax.scan(
                group, x,
                (params["ssm_groups"], cache["ssm_groups"], cache["shared_attn"]),
            )
            new_cache: Params = {"ssm_groups": new_gc, "shared_attn": new_ac}
            if "ssm_tail" in params:
                def tail(h, inp):
                    lp, lc = inp
                    zz = L.rms_norm(h, lp["ln"], cfg.norm_eps)
                    y, nc = S.ssm_decode(lp["ssm"], cfg, zz, lc)
                    return h + y, nc
                x, new_tail = jax.lax.scan(
                    tail, x, (params["ssm_tail"], cache["ssm_tail"])
                )
                new_cache["ssm_tail"] = new_tail
            cache = new_cache
        elif cfg.family == "audio":
            def body(carry, inp):
                h = carry
                lp, lc, xkv = inp
                h, new_c = self._decode_tf_layer(
                    lp, cfg, h, lc, pos, cross_kv=(xkv["k"], xkv["v"])
                )
                return h, new_c
            x, new_attn = jax.lax.scan(
                body, x, (params["layers"], cache["attn"], cache["cross_kv"])
            )
            cache = {"attn": new_attn, "cross_kv": cache["cross_kv"]}
        return self._unembed(params, x), cache
