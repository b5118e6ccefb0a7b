"""Arithmetic-op counting over jaxprs (ROADMAP item 5's enabler).

The capture hooks historically carried a hand-written FLOP formula per
kernel geometry (``scan_flops``, ``decode_flops``, ...).  That is one more
mirror to keep honest — and it does not scale to whole-model capture,
where the traced jaxpr contains hundreds of equations nobody wants to
model by hand.  :func:`count_flops` replaces the formulas with a
principled counter: walk the jaxpr, charge each *floating-point* equation
its arithmetic cost, and recurse through every higher-order primitive
(``scan`` multiplies by its trip count, ``cond`` takes the worst branch,
``pallas_call`` multiplies its kernel body by the grid-step count).

Counting rules (DAMOV counts arithmetic operations, not instructions):

- equations whose first output is not floating/complex cost **zero** —
  index arithmetic, comparisons, and bool masks are bookkeeping, which is
  exactly how the hand formulas treated them (``token_gather`` counts 0);
- data-movement primitives (reshape / broadcast / slice / gather /
  convert / select / ref get-swap ...) cost zero regardless of dtype;
- elementwise arithmetic costs one op per output element
  (``integer_pow`` charges ``|y| - 1`` multiplies);
- ``dot_general`` costs ``2 * G * M * N * K`` (multiply + accumulate),
  ``conv_general_dilated`` the im2col equivalent;
- reductions (and cumulative ops) cost one op per *input* element.

The counter is exact against the hand formulas for the stream / gather /
MoE capture hooks (14 of the 24 captured roster entries) and agrees
within ~5% for flash-attention, paged-KV decode and the SSM scans, whose
formulas round the softmax / chunk-mask epilogues to flat per-score
constants.  The SSM kernels' prefix sums run as lower-triangular MXU
matmuls, which their formulas (the recurrence's own arithmetic) leave
out; ``tests/test_capture_model.py`` adds those back and pins all 24
entries.
"""

from __future__ import annotations

__all__ = ["count_flops", "eqn_flops"]

# Pure data movement / layout / bookkeeping: zero arithmetic regardless of
# dtype.  (Comparisons, int index math and bool masks are already zeroed
# by the float-output gate; this set catches float-valued movement.)
_ZERO = frozenset({
    "broadcast_in_dim", "reshape", "transpose", "convert_element_type",
    "slice", "dynamic_slice", "dynamic_update_slice", "concatenate",
    "gather", "scatter", "scatter-add", "scatter_add", "select_n", "iota",
    "copy", "squeeze", "expand_dims", "rev", "pad", "split",
    "reduce_precision", "stop_gradient", "device_put",
    "bitcast_convert_type", "real", "imag", "get", "swap", "masked_load",
    "masked_store", "broadcast", "sort", "top_k", "argmax", "argmin",
    "rng_bit_generator", "random_seed", "random_bits", "random_wrap",
    "random_unwrap", "clz", "population_count", "sharding_constraint",
    "optimization_barrier", "print", "debug_print",
})

# Reductions: one op per *input* element (n-element tree sum = n-1 adds).
_REDUCE = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
    "cumsum", "cumprod", "cummax", "cummin", "cumlogsumexp",
})

# clamp = max then min.
_COST_PER_ELEM = {"clamp": 2}


def _elems(aval) -> int:
    n = 1
    for d in getattr(aval, "shape", ()):
        n *= int(d)
    return n


def _is_float(aval) -> bool:
    import numpy as np

    dt = getattr(aval, "dtype", None)
    if dt is None:
        return False
    kind = np.dtype(dt).kind
    return kind in ("f", "c") or "float" in str(dt)  # bf16 et al. are kind f


def _dot_flops(eqn) -> float:
    (lc, rc), (lb, _rb) = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval.shape
    g = 1
    for d in lb:
        g *= int(lhs[d])
    k = 1
    for d in lc:
        k *= int(lhs[d])
    m = 1
    for i, d in enumerate(lhs):
        if i not in lb and i not in lc:
            m *= int(d)
    out = _elems(eqn.outvars[0].aval)
    n = out // max(1, g * m)
    return 2.0 * g * m * n * k


def _conv_flops(eqn) -> float:
    # im2col equivalence: 2 * out_elems * (in_ch / groups) * kernel_spatial
    rhs = eqn.invars[1].aval.shape  # [..., in_ch/groups, out_ch] layout-dep
    out = _elems(eqn.outvars[0].aval)
    dn = eqn.params["dimension_numbers"]
    k_spatial = 1
    for d in dn.rhs_spec[2:]:
        k_spatial *= int(rhs[d])
    in_ch = int(rhs[dn.rhs_spec[1]])
    return 2.0 * out * in_ch * k_spatial


def _sub_jaxprs(v):
    """Yield every jaxpr-like object inside one eqn param value."""
    # ClosedJaxpr forwards .eqns, so test for it (via .jaxpr) first.
    if hasattr(v, "jaxpr"):          # ClosedJaxpr
        yield v.jaxpr
    elif hasattr(v, "eqns"):         # raw Jaxpr
        yield v
    elif isinstance(v, (tuple, list)):
        for item in v:
            yield from _sub_jaxprs(item)


def eqn_flops(eqn) -> float:
    """Arithmetic-op cost of one equation (recursing into sub-jaxprs)."""
    name = eqn.primitive.name
    if name == "pallas_call":
        steps = 1
        for g in eqn.params["grid_mapping"].grid:
            steps *= int(g)
        return steps * count_flops(eqn.params["jaxpr"])
    if name == "scan":
        return int(eqn.params["length"]) * count_flops(eqn.params["jaxpr"])
    if name == "cond":
        return max(count_flops(b) for b in eqn.params["branches"])
    if name == "while":
        # trip count is data-dependent; charge one body pass (documented —
        # the model zoo's steps use scan, never while)
        return (count_flops(eqn.params["body_jaxpr"])
                + count_flops(eqn.params["cond_jaxpr"]))
    inner = [j for v in eqn.params.values() for j in _sub_jaxprs(v)]
    if inner:                        # pjit / remat / custom_* / closed_call
        return sum(count_flops(j) for j in inner)
    if name in _ZERO or not eqn.outvars:
        return 0.0
    if not _is_float(eqn.outvars[0].aval):
        return 0.0
    if name == "dot_general":
        return _dot_flops(eqn)
    if name == "conv_general_dilated":
        return _conv_flops(eqn)
    if name in _REDUCE:
        return float(_elems(eqn.invars[0].aval))
    if name == "integer_pow":
        return max(1, abs(int(eqn.params["y"])) - 1) * float(
            _elems(eqn.outvars[0].aval))
    per = _COST_PER_ELEM.get(name, 1)
    return per * float(_elems(eqn.outvars[0].aval))


def count_flops(jaxpr) -> float:
    """Total arithmetic-op count of a (closed) jaxpr."""
    j = getattr(jaxpr, "jaxpr", jaxpr)
    return sum(eqn_flops(eqn) for eqn in j.eqns)
