"""Whole-model capture (repro.capture.model/flops/zoo) + the two gates.

Two differential gates the tentpole owes the rest of the repo:

1. **Single-kernel byte identity** — a jitted step containing exactly one
   Pallas kernel must produce, through the whole-model walker, the same
   word-address stream as the standalone kernel capture
   (``walk(cap, bases=...)`` external placement + the allocator's shared
   line-aligned sizing rule).
2. **Counter vs formula** — :func:`repro.capture.flops.eqn_flops` on each
   captured kernel's traced ``pallas_call`` must reproduce the hooks'
   hand-written FLOP formulas: exactly for STREAM / token-gather /
   MoE-dispatch (whose traced paths now pass ``flops=None`` and rely on
   the counter), and within a small tolerance for flash-attention /
   paged-KV / SSM, whose formulas round softmax and chunk-mask epilogues
   to flat per-score constants.  The SSM kernels also run their prefix
   sums as lower-triangular MXU matmuls, which the recurrence's formula
   leaves out; the gate adds them back.

Plus unit coverage of the model walker's region algebra (scan slicing,
carry ping-pong, transparent aliasing, dense-dot lowering, windowed
walks) and a smoke classification of zoo entries.
"""

import numpy as np
import pytest

from repro.capture import CAPTURED_KERNELS
from repro.capture.grid import walk

jax = pytest.importorskip("jax")
jnp = pytest.importorskip("jax.numpy")

from repro.capture import flops as F              # noqa: E402
from repro.capture import jaxpr as J              # noqa: E402
from repro.capture.jaxpr import from_jaxpr        # noqa: E402
from repro.capture.model import (                 # noqa: E402
    ModelCapture, capture_model)


# --------------------------------------------------------------------------
# Gate 1: single-kernel whole-model capture is byte-identical.
# --------------------------------------------------------------------------
def test_single_kernel_gate_byte_identical():
    from repro.kernels.stream import kernel as K

    n = 512 * 128 * 4
    a = jax.ShapeDtypeStruct((n,), jnp.float32)
    q = jnp.float32(1.5)
    fn = lambda x, y, s: K.stream_triad(x, y, s, block_rows=512)  # noqa: E731

    solo = walk(from_jaxpr(fn, (a, a, q), flops=None))
    mc = capture_model(fn, (a, a, q), name="gate")
    assert len(mc.ops) == 1 and mc.ops[0].kind == "pallas"
    model = mc.walk()
    assert np.array_equal(solo.addresses, model.addresses)
    assert (solo.loads, solo.stores) == (model.loads, model.stores)
    assert model.flops == solo.flops == mc.flops


def test_single_kernel_gate_scalar_prefetch():
    """Same gate through a kernel with data-dependent (scalar-prefetch)
    index maps: placeholder indices make the model trace self-consistent
    (all-zeros routing), so identity is against the zero-table capture."""
    from repro.kernels.token_gather import kernel as K

    n_rows, d, m = 1024, 128, 256
    table = jax.ShapeDtypeStruct((n_rows, d), jnp.float32)
    idx = jax.ShapeDtypeStruct((m,), jnp.int32)
    fn = K.gather_rows

    zeros = np.zeros(m, dtype=np.int32)
    solo = walk(from_jaxpr(fn, (table, idx), scalar_values=(zeros,),
                           flops=None))
    mc = capture_model(fn, (table, idx), name="gate-prefetch")
    assert len(mc.ops) == 1 and mc.ops[0].kind == "pallas"
    model = mc.walk()
    assert np.array_equal(solo.addresses, model.addresses)


# --------------------------------------------------------------------------
# Gate 2: the arithmetic counter vs every hook's hand formula.
# --------------------------------------------------------------------------
# family -> max |counted - formula| / formula.  Zero for the families whose
# traced hooks now *use* the counter; the rest round their softmax/mask
# epilogues into flat constants (see the hooks' comments).
_TOL = {
    "stream": 0.0,
    "gather": 0.0,
    "moe": 0.0,
    "ssm": 0.01,       # ema exact; expand folds mask ops into 5*C*d
    # (both after adding the prefix-sum matmuls, _ssm_prefix_flops)
    "flashattn": 0.005,
    "pagedkv": 0.05,
}


def _ssm_prefix_flops(geo: dict) -> float:
    """Operations of the SSM kernels' ``tril(1) @ v`` prefix sums over one
    1-core launch: a [C, C] x [C, D] matmul per chunk for each running sum
    (ema: the decay product and the state sum; expand: the decay
    product)."""
    sums = 2 if geo["op"] == "ema" else 1
    return sums * 2.0 * geo["chunk"] * geo["seq_len"] * geo["d"]


@pytest.mark.parametrize(
    "spec", CAPTURED_KERNELS, ids=[s.name for s in CAPTURED_KERNELS])
def test_counter_matches_hook_formula(spec, monkeypatch):
    counted = {}
    real = J.capture_pallas_eqn

    def spy(eqn, **kw):
        counted["flops"] = F.eqn_flops(eqn)
        return real(eqn, **kw)

    monkeypatch.setattr(J, "capture_pallas_eqn", spy)
    monkeypatch.setenv("REPRO_CAPTURE_PATH", "jaxpr")
    J.clear_memo()
    try:
        traced = spec.builder(1, np.random.default_rng(0))
        monkeypatch.setenv("REPRO_CAPTURE_PATH", "mirror")
        formula = spec.builder(1, np.random.default_rng(0)).flops
    finally:
        J.clear_memo()   # drop spy-built captures from the shared memo
    assert counted, f"{spec.name}: traced path never captured an eqn"
    if spec.kernel == "ssm":
        formula += _ssm_prefix_flops(dict(spec.geometry))
    tol = _TOL[spec.kernel]
    if tol == 0.0:
        assert counted["flops"] == formula == traced.flops, spec.name
    else:
        rel = abs(counted["flops"] - formula) / formula
        assert rel <= tol, (spec.name, counted["flops"], formula, rel)


# --------------------------------------------------------------------------
# The FLOP counter's rules.
# --------------------------------------------------------------------------
def test_count_flops_dot_and_elementwise():
    a = jax.ShapeDtypeStruct((64, 32), jnp.float32)
    b = jax.ShapeDtypeStruct((32, 16), jnp.float32)
    jx = jax.make_jaxpr(lambda x, y: jnp.tanh(x @ y))(a, b)
    # 2*M*N*K + one tanh per output element
    assert F.count_flops(jx) == 2 * 64 * 16 * 32 + 64 * 16


def test_count_flops_integer_ops_cost_zero():
    a = jax.ShapeDtypeStruct((128,), jnp.int32)
    jx = jax.make_jaxpr(lambda x: x + x * 2)(a)
    assert F.count_flops(jx) == 0.0


def test_count_flops_reduction_counts_input_elems():
    a = jax.ShapeDtypeStruct((64, 32), jnp.float32)
    jx = jax.make_jaxpr(lambda x: jnp.sum(x))(a)
    assert F.count_flops(jx) == 64 * 32


def test_count_flops_scan_multiplies_by_length():
    a = jax.ShapeDtypeStruct((8, 128), jnp.float32)

    def fn(xs):
        return jax.lax.scan(lambda c, x: (c + x, None),
                            jnp.zeros((128,)), xs)[0]

    assert F.count_flops(jax.make_jaxpr(fn)(a)) == 8 * 128


# --------------------------------------------------------------------------
# Model-walker region algebra.
# --------------------------------------------------------------------------
def _dense_ops(mc: ModelCapture):
    return [op for op in mc.ops if op.kind == "dense"]


def test_dot_lowering_geometry_and_flops():
    a = jax.ShapeDtypeStruct((256, 512), jnp.float32)
    b = jax.ShapeDtypeStruct((512, 128), jnp.float32)
    mc = capture_model(lambda x, y: x @ y, (a, b), name="dot")
    (op,) = _dense_ops(mc)
    g, mi, ni, ki = op.capture.grid
    assert g == 1 and mi * ni * ki > 1          # MXU-tiled, k innermost
    assert mc.flops == 2.0 * 256 * 128 * 512
    r = mc.walk()
    assert r.refs == r.addresses.size > 0


def test_scan_shares_weights_and_slices_xs():
    L, d = 4, 128
    x0 = jax.ShapeDtypeStruct((d, d), jnp.float32)
    ws = jax.ShapeDtypeStruct((L, d, d), jnp.float32)

    def fn(x, stacked):
        def body(c, w):
            return jnp.dot(c, w), None
        return jax.lax.scan(body, x, stacked)[0]

    mc = capture_model(fn, (x0, ws), name="layers")
    ops = _dense_ops(mc)
    assert len(ops) == L                         # unrolled per iteration
    rhs = [op.bases["rhs"] for op in ops]
    # xs slices advance monotonically inside one stacked region
    assert rhs == sorted(rhs) and len(set(rhs)) == L
    stride = rhs[1] - rhs[0]
    assert all(b - a == stride for a, b in zip(rhs, rhs[1:]))
    # the carry ping-pongs in place: every iteration reads one region
    lhs = {op.bases["lhs"] for op in ops[1:]}
    assert len(lhs) == 1


def test_transparent_alias_threads_producer_to_consumer():
    d = 64
    a = jax.ShapeDtypeStruct((d, d), jnp.float32)

    def fn(x, y, z):
        t = jnp.tanh(x @ y)      # small elementwise: aliases the dot out
        return t @ z

    mc = capture_model(fn, (a, a, a), name="chain")
    d1, d2 = _dense_ops(mc)
    assert d2.bases["lhs"] == d1.bases["out"]


def test_stream_lowering_threshold():
    big = jax.ShapeDtypeStruct((256, 256), jnp.float32)    # 64k elems
    small = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    mc_big = capture_model(lambda x, y: x + y, (big, big), name="big")
    mc_small = capture_model(lambda x, y: x + y, (small, small),
                             name="small")
    assert [op.kind for op in mc_big.ops] == ["stream"]
    assert mc_small.ops == ()
    r = mc_big.walk()
    # two whole arrays read + one written, in words (2 fp32/word)
    assert r.loads == 2 * 256 * 256 // 2
    assert r.stores == 256 * 256 // 2
    assert mc_big.flops == 256 * 256


def test_walk_window_is_contiguous_slice():
    big = jax.ShapeDtypeStruct((256, 256), jnp.float32)

    def fn(x, y):
        return jnp.tanh(x @ y) @ y

    mc = capture_model(fn, (big, big), name="win")
    full = mc.walk()
    target = full.refs // 3
    win = mc.walk_window(target)
    assert win.addresses.size == win.refs == target
    # the window is a verbatim contiguous slice of the full stream
    start = int((full.refs - target) * 0.5)
    assert np.array_equal(win.addresses,
                          full.addresses[start:start + target])
    # shorter-than-target traces come back whole
    assert mc.walk_window(full.refs * 2).refs == full.refs


def test_footprint_grows_with_distinct_regions():
    d = 128
    a = jax.ShapeDtypeStruct((d, d), jnp.float32)
    one = capture_model(lambda x, y: x @ y, (a, a), name="one")
    two = capture_model(lambda x, y, z: (x @ y) @ z, (a, a, a), name="two")
    assert two.footprint_words > one.footprint_words > 0


def test_walk_stream_blocks_concat_to_walk():
    """walk_stream's yielded blocks concatenate to exactly the
    materialized walk()/walk_window() streams — the generator path is
    identical by construction, never approximately."""
    big = jax.ShapeDtypeStruct((256, 256), jnp.float32)

    def fn(x, y):
        return jnp.tanh(x @ y) @ y

    mc = capture_model(fn, (big, big), name="stream-id")
    full = mc.walk()
    blocks = list(mc.walk_stream())
    assert len(blocks) > 1                       # genuinely block-wise
    assert np.array_equal(np.concatenate(blocks), full.addresses)

    target = full.refs // 3
    win = mc.walk_window(target)
    wblocks = list(mc.walk_stream(target))
    assert np.array_equal(np.concatenate(wblocks), win.addresses)
    # over-long targets fall back to the whole stream
    over = np.concatenate(list(mc.walk_stream(full.refs * 2)))
    assert np.array_equal(over, full.addresses)
    with pytest.raises(ValueError):
        next(mc.walk_stream(0))


def test_walk_stream_counters_vs_concat_counters():
    from repro import obs

    big = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    mc = capture_model(lambda x, y: x @ y, (big, big), name="stream-obs")
    obs.reset_counters()
    list(mc.walk_stream())
    c = obs.counters()
    assert c["capture.model.stream_blocks"] > 0
    assert "capture.model.concat" not in c
    obs.reset_counters()
    mc.walk()
    mc.walk_window(100)
    assert obs.counters()["capture.model.concat"] == 2


def _two_dots():
    """A step of three ops: a vectorized dot (grid (1, 3, 3, 3)), a tanh
    stream op and a second dot."""
    big = jax.ShapeDtypeStruct((384, 384), jnp.float32)
    return capture_model(lambda x, y: jnp.tanh(x @ y) @ y, (big, big),
                         name="two-dots")


@pytest.mark.parametrize("center", [0.0, 0.2, 0.5, 0.8, 1.0])
def test_windowed_stream_is_slice_of_whole_walk(center):
    """The streamed window and ``walk_window`` are the whole walk's
    centred slice, for windows that start and end inside ops; the ops
    the window cuts emit only their slice."""
    from repro import obs

    mc = _two_dots()
    full = mc.walk()
    sizes = np.array([op.walk(count_only=True).refs for op in mc.ops])
    assert len(sizes) == 3 and min(sizes) > 0
    target = sizes[1] + sizes[0] // 2          # cuts at least one op
    start = int((full.refs - target) * center)
    want = full.addresses[start:start + target]

    obs.reset_counters()
    blocks = list(mc.walk_stream(target, center=center))
    c = obs.counters()
    assert np.concatenate(blocks).tobytes() == want.tobytes()
    assert c["capture.model.stream_blocks"] == len(blocks)
    assert "capture.model.concat" not in c
    ends = np.cumsum(sizes)
    kept = np.clip(np.minimum(ends, start + target)
                   - np.maximum(ends - sizes, start), 0, None)
    cut = (kept > 0) & (kept < sizes)
    assert c["capture.walk.window_calls"] == np.count_nonzero(cut) >= 1
    assert c["capture.walk.skipped_refs"] == int((sizes - kept)[cut].sum())
    assert c["capture.walk.refs"] == target
    assert mc.walk_window(target, center=center).addresses.tobytes() \
        == want.tobytes()


@pytest.mark.parametrize("center", [0.0, 0.5, 1.0])
def test_window_placement_is_its_own_span_and_counts_its_ops(center,
                                                             tmp_path):
    """A windowed pass's placement (every op's count-only walk and the
    window's bounds) is one ``capture.model.window`` span, closed before
    any block is emitted, and ``capture.model.window_ops`` counts the ops
    the window overlaps."""
    from _obs_spans import bounds, span_events

    from repro import obs

    mc = _two_dots()
    sizes = np.array([op.walk(count_only=True).refs for op in mc.ops])
    target = int(sizes[1] + sizes[0] // 2)
    start = int((sizes.sum() - target) * center)
    ends = np.cumsum(sizes)
    overlapped = int(np.count_nonzero((ends > start)
                                      & (ends - sizes < start + target)))
    obs.reset_counters()
    obs.enable(tmp_path / "obs.jsonl")
    try:
        blocks = list(mc.walk_stream(target, center=center))
        mc.walk_window(target, center=center)
    finally:
        obs.disable()
    assert obs.counters()["capture.model.window_ops"] == 2 * overlapped
    events = span_events(tmp_path / "obs.jsonl")
    windows = [e for e in events if e["name"] == "capture.model.window"]
    assert len(windows) == 2
    first_emit = min(bounds(e)[0] for e in events
                     if e["name"] == "capture.walk.emit")
    assert bounds(windows[0])[1] <= first_emit
    assert sum(b.size for b in blocks) == target


def test_whole_walks_count_no_window():
    from repro import obs

    mc = _two_dots()
    full = mc.walk(count_only=True)            # sizes each op once
    obs.reset_counters()
    mc.walk()
    list(mc.walk_stream())
    list(mc.walk_stream(full.refs))            # the window is the step
    c = obs.counters()
    assert "capture.walk.window_calls" not in c
    assert c["capture.walk.refs"] == 3 * full.refs


# --------------------------------------------------------------------------
# Zoo entries flow through the standard pipeline and match their pins.
# --------------------------------------------------------------------------
def test_zoo_entry_classifies_as_pinned():
    from repro.capture.zoo import model_workloads
    from repro.core import classify

    ws = model_workloads(only=("qwen2.5-14b.decode.bs8",))
    assert len(ws) == 4      # the substring also picks the deep-cache axis
    w = next(w for w in ws if w.name == "model.qwen2.5-14b.decode.bs8")
    m = classify.measure(w, seed=0)
    assert classify.classify(m) == w.expected_class == "1b"
    assert w.ai_ops_per_access > 0


def test_zoo_deep_cache_entry_recomputes_to_1a():
    """One live recompute on the DRAM-bound side of the boundary: the
    qwen cache4096 cell must land in 1a, not just be pinned there."""
    from repro.capture.zoo import model_workloads
    from repro.core import classify

    (w,) = model_workloads(only=("qwen2.5-14b.decode.bs8.c4096",))
    m = classify.measure(w, seed=0)
    assert classify.classify(m) == w.expected_class == "1a"
    assert m.mpki >= 11.0


@pytest.mark.parametrize("mode", ["prefill", "eval"])
def test_zoo_new_modes_capture_and_census(mode):
    """prefill/eval are first-class capture modes: one jitted-step jaxpr
    each, with populated op-census columns."""
    from repro.capture.zoo import census_for, get_capture

    mc = get_capture("qwen2.5-14b", mode, 1)
    assert mc.walk(count_only=True).refs > 0
    model_ops, dense_ops, stream_ops, pallas_ops, mib = \
        census_for(f"model.qwen2.5-14b.{mode}.bs1")
    assert model_ops >= dense_ops > 0
    assert mib > 0


def test_zoo_roster_spans_swept_axes():
    """Pure declaration algebra — no jax, no captures."""
    from repro.capture import zoo

    assert len(zoo.MODEL_ZOO) >= 150
    assert {s.mode for s in zoo.MODEL_ZOO} == \
        {"decode", "prefill", "eval", "train"}
    decode_batches = {s.batch for s in zoo.MODEL_ZOO if s.mode == "decode"}
    assert decode_batches >= {1, 4, 8, 16, 32, 64}
    cache_depths = {s.geometry for s in zoo.MODEL_ZOO if s.mode == "decode"}
    assert {256, 1024, 4096, 16384} <= cache_depths
    seq_lens = {s.geometry for s in zoo.MODEL_ZOO if s.mode != "decode"}
    assert {128, 512} <= seq_lens
    assert len({s.config for s in zoo.MODEL_ZOO}) == 10
    # every entry pins (AI, class): registry builds never trace a model
    assert all(s.ai is not None and s.ai > 0 for s in zoo.MODEL_ZOO)


def test_zoo_batch_axes_never_flap():
    """Monotone-plausible label sequences along every batch axis: a
    label may change at most once (measured: it never does — the class
    boundary lives on the cache-depth axis)."""
    from repro.capture.zoo import batch_transitions, class_frontier

    for key, seq in class_frontier().items():
        changes = sum(c0 != c1 for (_, c0), (_, c1) in zip(seq, seq[1:]))
        assert changes <= 1, (key, seq)
    assert all(t == () for t in batch_transitions().values())


@pytest.mark.slow
def test_zoo_full_roster_matches_pins():
    from repro.capture.zoo import MODEL_ZOO, model_workloads
    from repro.core import classify

    ws = model_workloads()
    assert len(ws) == len(MODEL_ZOO) >= 150
    for w in ws:
        m = classify.measure(w, seed=0)
        assert classify.classify(m) == w.expected_class, w.name


# --------------------------------------------------------------------------
# Pinned class-transition boundaries: the sweep's headline finding.
# Each named test pins one config's boundary so a regression in capture
# or FLOP counting moves a named test, not just a CSV.  (Declaration
# algebra over _PINS — no jax.)
# --------------------------------------------------------------------------
def _cache_axis(config: str, batch: int = 8) -> dict[int, str]:
    from repro.capture.zoo import geometry_frontier

    return dict(geometry_frontier()[(config, "decode", batch)])


def test_boundary_crossers_rank_by_kv_read_ai():
    """Six configs cross 1b -> 1a on the cache-depth axis; the pinned
    crossing depth orders their KV-read arithmetic intensity."""
    crossing_depth = {
        "whisper-large-v3": 1024, "zamba2-7b": 1024,
        "deepseek-moe-16b": 1024, "phi4-mini-3.8b": 1024,
        "qwen2.5-14b": 4096, "nemotron-4-340b": 16384,
    }
    for config, depth in crossing_depth.items():
        axis = _cache_axis(config)
        below = [g for g in axis if g < depth]
        assert axis[depth] == "1a", (config, axis)
        assert all(axis[g] == "1b" for g in below), (config, axis)


def test_boundary_qwen_crosses_at_cache4096():
    axis = _cache_axis("qwen2.5-14b")
    assert (axis[256], axis[1024], axis[4096], axis[16384]) == \
        ("1b", "1b", "1a", "1a")


def test_boundary_nemotron_crosses_at_cache16384():
    axis = _cache_axis("nemotron-4-340b")
    assert (axis[256], axis[1024], axis[4096], axis[16384]) == \
        ("1b", "1b", "1b", "1a")


def test_boundary_zamba2_hybrid_flaps_at_cache4096():
    """The pinned caveat: zamba2's centered window covers ~9% of the
    c4096 step, so the SSM/attention phase mix under the window — not
    the physics — picks that label.  Pinned so a windowing change that
    fixes (or worsens) the bias moves this test."""
    axis = _cache_axis("zamba2-7b")
    assert (axis[256], axis[1024], axis[4096], axis[16384]) == \
        ("1b", "1a", "1b", "1a")


def test_boundary_asymptote_configs_never_cross():
    """granite/paligemma saturate a hair under MPKI 11 (terminal c65536
    point pinned 1b); deepseek-v2-lite's latent-compressed cache and
    mamba2's fixed SSM state never approach the line."""
    for config in ("granite-20b", "paligemma-3b",
                   "deepseek-v2-lite-16b", "mamba2-780m"):
        axis = _cache_axis(config)
        assert 65536 in axis, config
        assert set(axis.values()) == {"1b"}, (config, axis)


def test_boundary_mamba2_is_cache_depth_invariant():
    """The SSM contrast: pinned AI is byte-identical at every cache
    depth — decode state does not scale with context."""
    from repro.capture.zoo import ZOO_BY_NAME

    ais = {ZOO_BY_NAME[f"model.mamba2-780m.decode.bs8{sfx}"].ai
           for sfx in ("", ".c1024", ".c4096", ".c16384", ".c65536")}
    assert len(ais) == 1


def test_geometry_transitions_match_named_boundaries():
    from repro.capture.zoo import geometry_transitions

    gt = {k: v for k, v in geometry_transitions().items() if v}
    assert set(gt) == {(c, "decode", 8) for c in (
        "qwen2.5-14b", "phi4-mini-3.8b", "nemotron-4-340b",
        "deepseek-moe-16b", "zamba2-7b", "whisper-large-v3")}
    assert gt[("qwen2.5-14b", "decode", 8)] == \
        ((1024, "1b", 4096, "1a"),)
    assert gt[("zamba2-7b", "decode", 8)] == \
        ((256, "1b", 1024, "1a"), (1024, "1a", 4096, "1b"),
         (4096, "1b", 16384, "1a"))


@pytest.mark.slow
def test_models_registry_filter_preserves_fingerprints():
    from repro.suite.registry import models_registry

    full = models_registry(refs=20_000)
    sub = models_registry(refs=20_000, only=("qwen2.5", "mamba2"))
    assert 0 < len(sub) < len(full)
    kw = dict(seed=0, cores=(1, 4), backend="vectorized",
              sections=("models",))
    by_name = {e.name: e for e in full}
    for e in sub:
        assert e.fingerprint(**kw) == by_name[e.name].fingerprint(**kw)
