#!/usr/bin/env python3
"""Bring-up smoke test: the system's main path on one TPU chip.

Run from the checkout root, on a machine with a TPU:

    python chip_smoke.py

It takes no options and has no CPU mode.  Four phases run in order, in
this one process (the chip belongs to one process at a time); any failure
ends the run with a non-zero exit code and no result line:

1. device — the platform must be ``tpu`` and its ``device_kind`` must be
   in the roofline peak table; the compile-cache directory is placed;
2. kernels — every captured suite geometry (``CAPTURED_KERNELS``) runs
   compiled (``interpret=False``) on seeded inputs, is checked against
   its ``ref.py`` oracle, and its compiled program must hold the Pallas
   kernel (``tpu_custom_call``);
3. simulator — the default roster and the mamba2-780m models roster run
   once with ``backend="jax"`` (the window scan on the chip) and once
   with ``"vectorized"`` (NumPy); rows must be identical, and the
   ``scan.jax`` counter proves the device scan ran;
4. model — mamba2-780m at its published width (48 layers, d_model 1536,
   vocab 50 280; random weights from a seed) serves six requests through
   ``repro.serve.Engine`` in its own dtype (bf16); the same requests are
   served again in f32, and every greedy token of that run is checked
   against the argmax of a full forward pass.

The last line of standard output is one JSON object naming the device:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Phases 2-4 are plain functions so that a script can rehearse them at
small sizes on the CPU with ``interpret=True``.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import compile_cache, configs, obs  # noqa: E402
from repro.capture import CAPTURED_KERNELS  # noqa: E402
from repro.core import cachesim_vec, hlo_analysis  # noqa: E402

SEED = 0
ROSTER_REFS = 20_000          # python -m repro.suite --fast
MODEL = "mamba2-780m"


def require(ok: bool, what: str) -> None:
    """Fail the run (exit code 1, no result line) unless ``ok``; unlike
    ``assert`` this holds under ``python -O`` too."""
    if not ok:
        raise SystemExit(f"chip_smoke: {what}")


# --------------------------------------------------------------------------
# Phase 1: device.
# --------------------------------------------------------------------------
def check_device() -> dict:
    """The device jax runs on; fails unless it is a TPU in the peak table."""
    devices = jax.devices()
    dev = devices[0]
    require(dev.platform == "tpu",
            f"no TPU found (jax platform {dev.platform!r})")
    hlo_analysis.device_spec(dev.device_kind)   # unknown kind: raises
    cache = compile_cache.enable()
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} compile_cache={cache}", flush=True)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


# --------------------------------------------------------------------------
# Phase 2: every captured kernel geometry, compiled, against its oracle.
# --------------------------------------------------------------------------
# Max abs error allowed, relative to max |ref|: data movement is exact,
# the MXU families are held to 1%.
_TOL = {"stream": 1e-6, "gather": 0.0, "flashattn": 1e-2, "pagedkv": 1e-2,
        "moe": 1e-2, "ssm": 1e-2}


def _kernel_case(kernel: str, geo: dict, rng: np.random.Generator,
                 interpret: bool):
    """(kernel call, oracle, seeded args) for one captured geometry."""
    from repro.kernels import (flash_attention as fa, moe_dispatch as md,
                               paged_kv_decode as pk, ssm_scan as ss,
                               stream as st, token_gather as tg)

    keys = iter(jax.random.split(jax.random.PRNGKey(int(rng.integers(2**31))),
                                 4))

    def normal(*shape, scale=1.0):
        return scale * jax.random.normal(next(keys), shape, jnp.float32)

    if kernel == "stream":
        n, op = geo["n_elems"], geo["op"]
        a, b, q = normal(n), normal(n), jnp.float32(1.5)
        args = {"copy": (a,), "scale": (a, q), "add": (a, b),
                "triad": (a, b, q)}[op]
        fn = getattr(st, f"stream_{op}")
        return (lambda *xs: fn(*xs, interpret=interpret),
                getattr(st.ref, f"{op}_ref"), args)
    if kernel == "gather":
        table = normal(geo["n_rows"], geo["d"])
        idx = jnp.asarray(rng.integers(0, geo["n_rows"], geo["m"]),
                          jnp.int32)
        return (lambda t, i: tg.gather_rows(t, i, interpret=interpret),
                tg.gather_rows_ref, (table, idx))
    if kernel == "flashattn":
        q = normal(1, geo["sq"], 1, geo["d"])
        k, v = (normal(1, geo["sk"], 1, geo["d"]) for _ in range(2))
        return (lambda q, k, v: fa.flash_attention(
                    q, k, v, causal=False, interpret=interpret),
                lambda q, k, v: fa.attention_ref(q, k, v, causal=False),
                (q, k, v))
    if kernel == "pagedkv":
        shape = (geo["n_pages"], geo["page"], geo["d"])
        pt = rng.choice(geo["n_pages"], geo["n_active"], replace=False)
        return (lambda *xs: pk.paged_decode_attention(
                    *xs, interpret=interpret),
                pk.paged_decode_ref,
                (normal(geo["h"], geo["d"]), normal(*shape), normal(*shape),
                 jnp.asarray(pt, jnp.int32)))
    if kernel == "moe":
        d, t = geo["d"], geo["n_tokens"]
        eids = jnp.asarray(rng.integers(0, geo["n_experts"], t), jnp.int32)
        return (lambda x, w, e: md.moe_dispatch(x, w, e, interpret=interpret),
                md.moe_dispatch_ref,
                (normal(t, d), normal(geo["n_experts"], d, geo["f"],
                                      scale=d ** -0.5), eids))
    if kernel == "ssm":
        t, d, n, chunk = geo["seq_len"], geo["d"], geo["n"], geo["chunk"]
        x = normal(t, d)
        # dt in (0.95, 0.999): the closed form's documented precision regime
        dt = jax.random.uniform(next(keys), (t, d), jnp.float32, 0.95, 0.999)
        if geo["op"] == "ema":
            return (lambda x, dt, g: ss.ssm_ema_scan(
                        x, dt, g, chunk=chunk, interpret=interpret),
                    ss.ssm_ema_ref, (x, dt, normal(t, d)))
        return (lambda x, dt, b, c: ss.ssm_chunked_scan(
                    x, dt, b, c, chunk=chunk, interpret=interpret),
                ss.ssm_chunked_ref,
                (x, dt, normal(t, n, scale=n ** -0.5), normal(t, n)))
    raise ValueError(f"unknown kernel family {kernel!r}")


def run_kernels(cases, *, interpret: bool = False) -> None:
    """Run each ``(name, kernel family, geometry)`` case compiled and check
    it against its oracle (computed at full f32 matmul precision)."""
    rng = np.random.default_rng(SEED)
    for name, kernel, geo in cases:
        call, ref, args = _kernel_case(kernel, geo, rng, interpret)
        compiled = jax.jit(call).lower(*args).compile()
        if not interpret:
            require("tpu_custom_call" in compiled.as_text(),
                    f"{name}: compiled program holds no Pallas kernel")
        got = compiled(*args)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(ref)(*args)
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                    - want.astype(jnp.float32))))
        scale = float(jnp.max(jnp.abs(want.astype(jnp.float32))))
        print(f"kernel {name}: max_abs_err={err!r} max_abs_ref={scale!r}",
              flush=True)
        require(err <= _TOL[kernel] * scale,
                f"{name}: max abs error {err} exceeds {_TOL[kernel]} x {scale}")


# --------------------------------------------------------------------------
# Phase 3: the simulator's window scan on the device vs on the host.
# --------------------------------------------------------------------------
def run_rosters(rosters) -> None:
    """Each ``(label, build_registry, sections, checked source)`` roster runs
    once on ``backend="jax"`` and once on ``"vectorized"``, in-process and
    with no result store; the rows must be identical and every entry of
    the checked source must land in its expected class."""
    from repro.suite import SuiteRunner

    for label, build, sections, source in rosters:
        rows, counts = {}, {}
        for backend in ("jax", "vectorized"):
            cachesim_vec.clear_memo()   # no profile crosses backends
            obs.reset_counters()
            t0 = time.perf_counter()
            runner = SuiteRunner(build(), seed=SEED, backend=backend,
                                 store=None, sections=sections)
            rows[backend] = list(runner.roster().rows)
            counts[backend] = obs.counters()
            wall = time.perf_counter() - t0
            require(not runner.divergent(source=source),
                    f"roster {label} ({backend}): {source} class divergence")
            print(f"roster {label} backend={backend}: rows="
                  f"{len(rows[backend])} wall_s={wall!r}", flush=True)
        require(rows["jax"] == rows["vectorized"],
                f"roster {label}: jax rows differ from vectorized rows")
        scans = counts["jax"].get("scan.jax", 0)
        require(scans > 0, f"roster {label}: the device scan never ran")
        print(f"roster {label}: rows={len(rows['jax'])} identical=True "
              f"scan.jax={scans} scan_programs_compiled="
              f"{counts['jax'].get('scan.jax.programs', 0)}", flush=True)


# --------------------------------------------------------------------------
# Phase 4: one model at its published width, served.
# --------------------------------------------------------------------------
def serve_model(cfg, *, n_requests: int = 6, prompt_len=(3, 100),
                max_new: int = 16, slots: int = 4, max_len: int = 256,
                prompt_buckets=(32, 128, 512)) -> None:
    """Serve seeded requests through the engine in the config's own dtype
    (the deployment path), then serve them again in f32 at full matmul
    precision and check every greedy token of that run against the argmax
    of one full forward over prompt + output.

    The exact check runs in f32 because in bf16 the prefill, the decode
    recurrence and the full forward round differently; over many
    random-weight layers that flips argmaxes whose top two logits are
    near-tied, which says nothing about the engine.
    """
    from repro.models import LM
    from repro.serve import Engine, Request

    lm = LM(cfg)
    params = jax.jit(lm.init)(jax.random.PRNGKey(SEED))

    def serve(model):
        engine = Engine(model, params, max_batch=slots, max_len=max_len,
                        prompt_buckets=prompt_buckets)
        rng = np.random.default_rng(SEED)
        reqs = [Request(rid=i, max_new_tokens=max_new,
                        prompt=rng.integers(1, cfg.vocab, int(rng.integers(
                            prompt_len[0], prompt_len[1] + 1))
                        ).astype(np.int32))
                for i in range(n_requests)]
        t0 = time.perf_counter()
        out = engine.run(reqs)
        wall = time.perf_counter() - t0
        require(all(len(v) == max_new for v in out.values()),
                f"a request did not get {max_new} tokens: {out}")
        return reqs, wall

    served, wall = serve(lm)
    print(f"model {cfg.name}: layers={cfg.n_layers} d_model={cfg.d_model} "
          f"vocab={cfg.vocab} dtype={cfg.dtype} requests={len(served)} "
          f"tokens_served={n_requests * max_new} wall_s={wall!r} "
          f"(compiles included)", flush=True)

    with jax.default_matmul_precision("highest"):
        exact_lm = LM(cfg.replace(dtype="float32"))
        checked, _ = serve(exact_lm)
        # One forward over every request, right-padded to one length: the
        # model is causal, so the padding never reaches a position read.
        seqs = [np.concatenate([r.prompt, r.out_tokens[:-1]]) for r in checked]
        batch = np.zeros((len(seqs), max(map(len, seqs))), np.int32)
        for row, seq in zip(batch, seqs):
            row[:len(seq)] = seq
        logits, _ = jax.jit(exact_lm.forward)(params, jnp.asarray(batch))
    logits = np.asarray(logits, np.float32)
    steps = np.arange(max_new)
    gaps, ranges, exact = [], [], 0
    for row, req in zip(logits, checked):
        lg = row[len(req.prompt) - 1:][:max_new]
        toks = np.asarray(req.out_tokens)
        exact += int((lg.argmax(axis=-1) == toks).sum())
        gaps.append(lg.max(axis=-1) - lg[steps, toks])
        ranges.append(lg.max(axis=-1) - lg.min(axis=-1))
    gap, spread = np.concatenate(gaps), np.concatenate(ranges)
    same = sum(a == b for r, c in zip(served, checked)
               for a, b in zip(r.out_tokens, c.out_tokens))
    print(f"model {cfg.name}: f32 greedy check: {exact}/{gap.size} served "
          f"tokens are the forward's argmax, max logit gap "
          f"{float(gap.max())!r}; {cfg.dtype}-served tokens equal to the "
          f"f32-served ones: {same}/{gap.size}", flush=True)
    # A served token may differ from the forward's argmax only on an f32
    # near-tie: its forward logit within 1e-4 of the logit range of the max.
    require(bool((gap <= 1e-4 * spread).all()),
            f"served tokens are not the forward's greedy tokens: gaps {gap}")


def main() -> int:
    device = check_device()

    run_kernels([(s.name, s.kernel, dict(s.geometry))
                 for s in CAPTURED_KERNELS])

    from repro.suite import default_registry, registry_for
    run_rosters([
        ("default", lambda: default_registry(refs=ROSTER_REFS), (),
         "captured"),
        (f"models[{MODEL}]", lambda: registry_for(
            refs=ROSTER_REFS, sections=("models",), only=(MODEL,)),
         ("models",), "model"),
    ])

    serve_model(configs.get(MODEL))

    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
