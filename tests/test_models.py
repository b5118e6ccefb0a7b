"""Per-architecture smoke tests + model-level correctness invariants.

Every assigned architecture instantiates its REDUCED config and runs one
forward + one train step on CPU, asserting output shapes and finiteness.
Decode consistency (prefill + step-by-step decode == full forward) is
checked for one representative of each family.

Every test here jit-compiles at least one full model, so the whole module
carries the ``slow`` marker: ``pytest -m "not slow"`` is the fast local
loop, CI runs ``-m "not timing"`` and keeps this coverage.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import LM
from repro.models import ssm as S
from repro.train import AdamWConfig, build_train_step, init_train_state

pytestmark = pytest.mark.slow  # full-model jit smokes

KEY = jax.random.PRNGKey(0)


def _batch_for(cfg, b=2, s=32):
    batch = {
        "tokens": jax.random.randint(KEY, (b, s), 0, cfg.vocab),
        "labels": jax.random.randint(jax.random.PRNGKey(1), (b, s), 0,
                                     cfg.vocab),
    }
    if cfg.family == "vlm":
        batch["extra_embed"] = 0.1 * jax.random.normal(
            KEY, (b, cfg.n_img_tokens, cfg.d_model))
    if cfg.family == "audio":
        batch["extra_embed"] = 0.1 * jax.random.normal(
            KEY, (b, cfg.enc_ctx, cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", configs.ARCHS)
def test_smoke_forward_and_train_step(arch):
    cfg = configs.get_smoke(arch)
    lm = LM(cfg)
    params = lm.init(KEY)
    batch = _batch_for(cfg)
    logits, aux = jax.jit(
        lambda p, b: lm.forward(p, b["tokens"],
                                extra_embed=b.get("extra_embed"))
    )(params, batch)
    assert logits.shape == (2, 32, cfg.vocab)
    assert bool(jnp.isfinite(logits).all()), arch
    assert bool(jnp.isfinite(aux)), arch

    opt_cfg = AdamWConfig(lr=1e-3, total_steps=10)
    step = jax.jit(build_train_step(lm, opt_cfg))
    state = init_train_state(lm, params, opt_cfg)
    new_params, new_state, metrics = step(params, state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert bool(jnp.isfinite(metrics["grad_norm"]))
    assert float(metrics["grad_norm"]) > 0
    # params actually changed
    delta = sum(float(jnp.abs(a - b).max()) for a, b in zip(
        jax.tree.leaves(new_params), jax.tree.leaves(params)))
    assert delta > 0


@pytest.mark.parametrize("arch", configs.ARCHS)
def test_smoke_decode_step(arch):
    cfg = configs.get_smoke(arch)
    lm = LM(cfg)
    params = lm.init(KEY)
    cache = lm.init_cache(2, 16)
    tokens = jax.random.randint(KEY, (2, 1), 0, cfg.vocab)
    logits, new_cache = jax.jit(lm.decode_step)(
        params, tokens, cache, jnp.zeros((2,), jnp.int32))
    assert logits.shape == (2, 1, cfg.vocab)
    assert bool(jnp.isfinite(logits).all())
    # cache structure preserved
    assert jax.tree.structure(new_cache) == jax.tree.structure(cache)


@pytest.mark.parametrize("arch", [
    "qwen2.5-14b",            # dense GQA + bias
    "deepseek-v2-lite-16b",   # MLA + MoE
    "mamba2-780m",            # SSM
    "zamba2-7b",              # hybrid
    "whisper-large-v3",       # enc-dec
    "paligemma-3b",           # vlm
])
def test_prefill_decode_matches_forward(arch):
    cfg = configs.get_smoke(arch).replace(
        attn_impl="naive", remat=False, dtype="float32",
        moe_capacity_factor=64.0)  # dropless so decode == forward exactly
    lm = LM(cfg)
    params = lm.init(KEY)
    b, s, t0 = 2, 12, 8
    tokens = jax.random.randint(jax.random.PRNGKey(7), (b, s), 1, cfg.vocab)
    extra = None
    if cfg.family == "vlm":
        extra = 0.1 * jax.random.normal(KEY, (b, cfg.n_img_tokens, cfg.d_model))
    if cfg.family == "audio":
        extra = 0.1 * jax.random.normal(KEY, (b, cfg.enc_ctx, cfg.d_model))
    full, _ = lm.forward(params, tokens, extra_embed=extra)

    cache = lm.init_cache(b, 32, dtype="float32")
    lg, cache, pos = lm.prefill(params, tokens[:, :t0], cache,
                                extra_embed=extra)
    errs = [float(jnp.abs(lg[:, 0] - full[:, t0 - 1]).max())]
    for t in range(t0, s):
        lg, cache = lm.decode_step(params, tokens[:, t: t + 1], cache, pos)
        pos = pos + 1
        errs.append(float(jnp.abs(lg[:, 0] - full[:, t]).max()))
    assert max(errs) < 2e-3, (arch, errs)


def test_prefill_right_padding_equivalent():
    """Variable-length prefill: right-padded prompt + prompt_len == exact."""
    cfg = configs.get_smoke("mamba2-780m").replace(dtype="float32")
    lm = LM(cfg)
    params = lm.init(KEY)
    tokens = jax.random.randint(KEY, (1, 10), 1, cfg.vocab)
    c1 = lm.init_cache(1, 32, dtype="float32")
    lg_exact, c_exact, _ = lm.prefill(params, tokens, c1)
    padded = jnp.pad(tokens, ((0, 0), (0, 6)))
    c2 = lm.init_cache(1, 32, dtype="float32")
    lg_pad, c_pad, pos = lm.prefill(params, padded, c2,
                                    prompt_len=jnp.array([10]))
    assert int(pos[0]) == 10
    np.testing.assert_allclose(lg_pad, lg_exact, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(c_pad["ssm"]["state"], c_exact["ssm"]["state"],
                               atol=1e-4, rtol=1e-3)


def test_chunked_attention_equals_naive():
    cfg = configs.get_smoke("qwen2.5-14b").replace(dtype="float32",
                                                   remat=False)
    lm_naive = LM(cfg.replace(attn_impl="naive"))
    lm_chunk = LM(cfg.replace(attn_impl="chunked", attn_chunk=16))
    params = lm_naive.init(KEY)
    tokens = jax.random.randint(KEY, (2, 48), 0, cfg.vocab)
    a, _ = lm_naive.forward(params, tokens)
    b, _ = lm_chunk.forward(params, tokens)
    np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def test_ssd_chunked_matches_ref():
    ks = jax.random.split(KEY, 5)
    B, Sq, H, P, N = 2, 40, 3, 8, 5
    x = jax.random.normal(ks[0], (B, Sq, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, Sq, H)))
    a = -jnp.exp(0.3 * jax.random.normal(ks[2], (H,)))
    b = jax.random.normal(ks[3], (B, Sq, N))
    c = jax.random.normal(ks[4], (B, Sq, N))
    d = jnp.ones((H,))
    y_ref = S.ssd_ref(x, dt, a, b, c, d)
    for chunk in (8, 16, 64):  # includes padding case (40 % 16 != 0)
        y = S.ssd_chunked(x, dt, a, b, c, d, chunk=chunk)
        np.testing.assert_allclose(y_ref, y, atol=5e-4, rtol=5e-3)


def test_moe_balance_loss_signal():
    """Uniform router -> aux ~ coef; collapsed router -> aux >> coef."""
    from repro.models import moe as M
    cfg = configs.get_smoke("deepseek-moe-16b")
    p = M.moe_init(KEY, cfg)
    # positive activations + one dominant router column => all tokens
    # route to expert 0 (and a fixed runner-up), collapsing the balance.
    x = jnp.abs(0.1 * jax.random.normal(KEY, (4, 16, cfg.d_model))
                ).astype(jnp.bfloat16)
    _, aux_uniform = M.moe_fwd(p, cfg, x)
    bad_router = jnp.full_like(p["router"], -0.1).at[:, 0].set(0.5)
    p_bad = dict(p, router=bad_router)
    _, aux_collapsed = M.moe_fwd(p_bad, cfg, x)
    assert float(aux_collapsed) > 2.0 * float(aux_uniform)


def test_param_count_analytic_matches_actual():
    # every smoke config, and deepseek-v2's with a chip's share of experts
    held = configs.get_smoke("deepseek-v2").replace(
        n_routed_experts=4, n_router_experts=16)
    for arch, cfg in [(a, configs.get_smoke(a)) for a in configs.ARCHS] + [
            ("deepseek-v2-held", held)]:
        lm = LM(cfg)
        shapes = jax.eval_shape(lm.init, KEY)
        actual = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
        est = cfg.param_count()
        assert abs(actual - est) / actual < 0.15, (arch, actual, est)
