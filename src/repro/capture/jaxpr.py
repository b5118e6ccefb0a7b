"""Zero-mirroring capture: trace a ``pallas_call`` and walk its jaxpr.

The original capture path asked every kernel package to *mirror* its
``pallas_call`` geometry — grid, block shapes, index maps — as plain data
in a ``capture.py`` hook, and a consistency test to keep the mirror honest.
That works, but it makes adding a captured kernel a two-artifact job and
leaves a window where kernel and mirror drift.

:func:`from_jaxpr` removes the mirroring step: it traces the kernel with
``jax.make_jaxpr`` (abstract tracing only — no TPU, no compilation), finds
the single ``pallas_call`` equation, and reads the launch geometry straight
out of the equation's ``GridMapping`` params:

- the grid;
- one ``BlockMapping`` per block-mapped operand (inputs then outputs),
  giving the block shape and the index-map jaxpr;
- scalar-prefetch operands (``num_index_operands``), which have no block
  mapping — the Pallas pipeline copies them to SMEM once before the grid
  runs, so they become whole-array operands with a constant index map,
  exactly how the mirrored hooks modeled them.

Index-map jaxprs may read scalar-prefetch refs (``idx_ref[i]``); those ref
ops are discharged (:func:`jax._src.state.discharge.discharge_state`) and
the resulting pure jaxpr is evaluated for **every grid step in one vmap**,
yielding an index table.  The returned :class:`~repro.capture.grid
.GridCapture` therefore needs jax only at *capture* time; the walk itself
(:func:`repro.capture.grid.walk`) stays pure NumPy, and the emitted DMA
word stream is byte-identical to the mirrored hooks' streams
(``tests/test_capture_jaxpr.py`` proves this differentially for every
legacy captured entry).

Path selection: the per-kernel hooks accept ``path="auto"|"jaxpr"|
"mirror"``; ``auto`` (overridable via ``$REPRO_CAPTURE_PATH``) resolves to
``jaxpr`` whenever jax is importable and falls back to the retained
mirrored geometry otherwise, so a jax-free interpreter can still build the
full suite registry.  Captures are memoized per launch geometry
(:func:`memoized`) because suite builds and core sweeps re-request the
same geometry many times.
"""

from __future__ import annotations

import os
from collections import OrderedDict
from typing import Callable, Sequence

import numpy as np

from .grid import GridCapture, OperandSpec

__all__ = ["from_jaxpr", "capture_pallas_eqn", "find_pallas_eqns",
           "capture_path", "memoized", "elems_per_word", "PATHS"]

PATHS = ("auto", "jaxpr", "mirror")


def _jax_importable() -> bool:
    try:
        import jax  # noqa: F401
    except Exception:
        return False
    return True


def capture_path(path: str = "auto") -> str:
    """Resolve a capture-path request to ``"jaxpr"`` or ``"mirror"``.

    ``auto`` honours ``$REPRO_CAPTURE_PATH`` (if set to a non-``auto``
    value) and otherwise picks ``jaxpr`` exactly when jax is importable.
    An explicit ``jaxpr``/``mirror`` argument always wins — the
    differential tests rely on forcing each side.
    """
    if path not in PATHS:
        raise ValueError(f"capture path must be one of {PATHS}, got {path!r}")
    if path == "auto":
        env = os.environ.get("REPRO_CAPTURE_PATH", "auto")
        if env not in PATHS:
            raise ValueError(
                f"$REPRO_CAPTURE_PATH must be one of {PATHS}, got {env!r}")
        path = env
    if path != "auto":
        return path
    return "jaxpr" if _jax_importable() else "mirror"


# --------------------------------------------------------------------------
# Capture memo.  Suite builds walk every captured entry once per (geometry,
# cores) and the engine's core sweep re-requests geometries; tracing a
# kernel costs ~50 ms, so hooks memoize on their full geometry key (which
# includes scalar-prefetch value bytes where indices steer the DMA).
# --------------------------------------------------------------------------
_MEMO: OrderedDict[tuple, GridCapture] = OrderedDict()
_MEMO_CAP = 256


def memoized(key: tuple, build: Callable[[], GridCapture]) -> GridCapture:
    """LRU-memoize one capture per geometry key."""
    got = _MEMO.get(key)
    if got is not None:
        _MEMO.move_to_end(key)
        return got
    cap = build()
    _MEMO[key] = cap
    while len(_MEMO) > _MEMO_CAP:
        _MEMO.popitem(last=False)
    return cap


def clear_memo() -> None:
    _MEMO.clear()


# --------------------------------------------------------------------------
# The jaxpr walker.
# --------------------------------------------------------------------------
def _param_jaxprs(v):
    """Yield every jaxpr-like object inside one eqn param value.

    Covers raw ``Jaxpr`` attrs (pjit, closed_call, custom_* wrappers) *and*
    containers of them — ``cond`` keeps its branches in a tuple, which the
    original attr-only walk silently missed.
    """
    # ClosedJaxpr first: it forwards .eqns to its inner jaxpr, so the
    # raw-Jaxpr check would match it too — but callers need .invars.
    if hasattr(v, "jaxpr"):          # ClosedJaxpr
        yield v.jaxpr
    elif hasattr(v, "eqns"):         # raw Jaxpr
        yield v
    elif isinstance(v, (tuple, list)):
        for item in v:
            yield from _param_jaxprs(item)


def find_pallas_eqns(jaxpr, out: list | None = None) -> list:
    """Collect ``pallas_call`` eqns, recursing into nested jaxprs (pjit,
    scan, cond branches, closed_call, custom_* wrappers)."""
    if out is None:
        out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn)
            continue  # a kernel body cannot contain another pallas_call
        for v in eqn.params.values():
            for inner in _param_jaxprs(v):
                find_pallas_eqns(inner, out)
    return out


# Back-compat alias (pre-model-capture private name).
_find_pallas_eqns = find_pallas_eqns


def elems_per_word(dtype, *dims: int) -> int:
    """Elements per 8-byte DAMOV trace word for one operand.

    Word collapse requires every row start to be word-aligned, so the
    packing factor is reduced (via gcd) to divide the operand's last-dim
    extents — e.g. a ``(1,)`` fp32 broadcast scalar packs 1 elem/word, not
    2, exactly as the mirrored hooks model it (same single word address
    either way).
    """
    epw = max(1, 8 // np.dtype(dtype).itemsize)
    import math
    for d in dims:
        epw = math.gcd(epw, int(d)) if d else epw
    return max(1, epw)


class _NpUnsupported(Exception):
    """Index-map jaxpr uses a primitive the NumPy evaluator doesn't cover."""


def _np_trunc_div(a, b):
    # lax.div on integers rounds toward zero (C semantics); numpy //
    # floors, so route through the magnitude quotient.
    return np.sign(a) * np.sign(b) * (np.abs(a) // np.abs(b))


# Vectorized implementations of the elementwise primitives index maps use
# (affine arithmetic + comparisons).  Anything absent raises
# _NpUnsupported and the caller falls back to the jax evaluation.
_NP_ELEMENTWISE = {
    "add": np.add, "sub": np.subtract, "mul": np.multiply,
    "max": np.maximum, "min": np.minimum, "neg": np.negative,
    "sign": np.sign, "abs": np.abs,
    "and": np.bitwise_and, "or": np.bitwise_or, "xor": np.bitwise_xor,
    "not": np.invert,
    "div": _np_trunc_div,
    "rem": np.fmod,  # lax.rem is the C-style truncated remainder
    "lt": np.less, "le": np.less_equal, "gt": np.greater,
    "ge": np.greater_equal, "eq": np.equal, "ne": np.not_equal,
}


def _np_dynamic_slice(ins, sizes, n_steps):
    """Batched ``lax.dynamic_slice``: per-step scalar starts (clamped, as
    lax does) into an unbatched operand array."""
    (op, op_batched), *starts = ins
    if op_batched:
        raise _NpUnsupported("batched dynamic_slice operand")
    sizes = tuple(int(s) for s in sizes)
    nd = op.ndim
    batched = any(b for _, b in starts)
    idx = []
    for d, ((s, sb), size) in enumerate(zip(starts, sizes)):
        if s.ndim != (1 if sb else 0):
            raise _NpUnsupported("non-scalar dynamic_slice start")
        s = np.clip(s.astype(np.int64), 0, op.shape[d] - size)
        offs = np.arange(size, dtype=np.int64).reshape(
            (1,) * (d + 1) + (size,) + (1,) * (nd - d - 1))
        sarr = s.reshape(((n_steps,) if sb else (1,)) + (1,) * nd)
        idx.append(sarr + offs)
    out = op[tuple(np.broadcast_arrays(*idx))]
    if not batched:
        out = out[0]
    return (out, batched)


def _np_index_table(jaxpr, consts, grid: tuple[int, ...], scalars,
                    n_block_dims: int) -> np.ndarray:
    """Pure-NumPy evaluation of a discharged index-map jaxpr, all grid
    steps at once.

    A tiny vmap: every value is ``(array, batched)`` where batched arrays
    carry a leading ``n_steps`` axis.  Covers the affine + scalar-table
    index maps every repo kernel uses (add/mul/compare/select_n/
    dynamic_slice/squeeze + nested pjit); raises :class:`_NpUnsupported`
    on anything else, and the caller falls back to the jax path.  Worth
    the interpreter: the jax evaluation XLA-compiles one vmapped
    program per (operand, grid) shape, which dominates cold suite builds.
    """
    from jax.extend.core import Literal

    n_steps = 1
    for g in grid:
        n_steps *= int(g)
    axes = np.indices(grid).reshape(len(grid), -1).astype(np.int64)
    env: dict = {}

    def read(v):
        if isinstance(v, Literal):
            return (np.asarray(v.val), False)
        return env[v]

    def aligned(vals):
        """Add/align the batch axis so plain numpy broadcasting matches
        per-example (vmap) broadcasting."""
        rank = max(a.ndim - (1 if b else 0) for a, b in vals)
        out = []
        for a, b in vals:
            ex = a.ndim - (1 if b else 0)
            if b:
                a = a.reshape(a.shape[:1] + (1,) * (rank - ex)
                              + a.shape[1:])
            else:
                a = a.reshape((1,) + (1,) * (rank - ex) + a.shape)
            out.append(a)
        return out

    def run(jaxpr, consts, args):
        for var, c in zip(jaxpr.constvars, consts):
            env[var] = (np.asarray(c), False)
        for var, a in zip(jaxpr.invars, args):
            env[var] = a
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            ins = [read(v) for v in eqn.invars]
            batched = any(b for _, b in ins)
            if name == "pjit":
                closed = eqn.params["jaxpr"]
                outs = run(closed.jaxpr, closed.consts, ins)
            elif name in _NP_ELEMENTWISE:
                arrs = aligned(ins)
                outs = [(_NP_ELEMENTWISE[name](*arrs), batched)]
            elif name == "select_n":
                if len(ins) != 3:
                    raise _NpUnsupported("select_n with >2 cases")
                pred, lo, hi = aligned(ins)
                outs = [(np.where(pred, hi, lo), batched)]
            elif name == "convert_element_type":
                (a, b), = ins
                outs = [(a.astype(np.dtype(eqn.params["new_dtype"])), b)]
            elif name == "squeeze":
                (a, b), = ins
                dims = tuple(int(d) + (1 if b else 0)
                             for d in eqn.params["dimensions"])
                outs = [(np.squeeze(a, axis=dims), b)]
            elif name == "dynamic_slice":
                outs = [_np_dynamic_slice(ins, eqn.params["slice_sizes"],
                                          n_steps)]
            else:
                raise _NpUnsupported(name)
            for var, out in zip(eqn.outvars, outs):
                env[var] = out
        return [read(v) for v in jaxpr.outvars]

    args = ([(axes[i], True) for i in range(len(grid))]
            + [(np.asarray(s), False) for s in scalars])
    outs = run(jaxpr, consts, args)[:n_block_dims]
    cols = []
    for a, b in outs:
        if not b:
            a = np.broadcast_to(a.reshape((1,) + a.shape),
                                (n_steps,) + a.shape)
        if a.ndim != 1:
            a = a.reshape(n_steps, -1)
            if a.shape[1] != 1:
                raise _NpUnsupported("non-scalar block index output")
            a = a[:, 0]
        cols.append(a.astype(np.int64))
    if not cols:
        return np.zeros((n_steps, 0), dtype=np.int64)
    return np.stack(cols, axis=1)


def _tabulate_index_map(index_map_jaxpr, grid: tuple[int, ...],
                        scalar_values: tuple) -> np.ndarray:
    """Evaluate one block's index map for every grid step.

    Returns an int64 table of shape ``(n_steps, block_rank)`` in row-major
    grid-step order (last grid axis fastest — the Pallas iteration order
    the walker replays).  Ref reads of scalar-prefetch operands are
    discharged to pure ops first; the discharged jaxpr appends the ref
    values as extra outputs, which are dropped.  The common all-affine /
    scalar-table maps are evaluated by the vectorized NumPy interpreter
    (:func:`_np_index_table`); exotic maps fall back to a vmapped jax
    evaluation.
    """
    import jax
    import jax.numpy as jnp
    from jax import core
    from jax._src.state.discharge import discharge_state

    dj, dconsts = discharge_state(index_map_jaxpr.jaxpr,
                                  index_map_jaxpr.consts)
    scalars = tuple(jnp.asarray(v) for v in scalar_values)
    n_steps = 1
    for g in grid:
        n_steps *= int(g)
    # discharge appends the (unchanged) ref values as extra outputs; the
    # block indices are the leading outputs
    n_block_dims = len(dj.outvars) - len(scalars)

    def point(*gidx):
        outs = core.eval_jaxpr(dj, dconsts, *gidx, *scalars)
        return tuple(jnp.asarray(o) for o in outs[:n_block_dims])

    if n_steps == 0:
        return np.zeros((0, n_block_dims), dtype=np.int64)
    if not grid:
        # gridless pallas_call: one implicit step, index maps take no args
        row = point()
        return np.asarray([[int(x) for x in row]], dtype=np.int64) \
            if n_block_dims else np.zeros((1, 0), dtype=np.int64)
    try:
        return _np_index_table(
            dj, dconsts, grid, [np.asarray(v) for v in scalar_values],
            n_block_dims)
    except _NpUnsupported:
        pass
    steps = np.stack(
        [a.ravel() for a in np.indices(grid)], axis=0
    ).astype(np.int32)
    try:
        cols = jax.vmap(point)(*[jnp.asarray(steps[a])
                                 for a in range(len(grid))])
    except Exception:
        # vmap can reject exotic index maps; fall back to the plain loop.
        rows = [point(*(jnp.int32(x) for x in steps[:, s]))
                for s in range(n_steps)]
        cols = [jnp.stack([r[d] for r in rows])
                for d in range(n_block_dims)]
    return np.stack(
        [np.asarray(c, dtype=np.int64) for c in cols], axis=1
    )


def _table_index_map(table: np.ndarray,
                     grid: tuple[int, ...]) -> Callable[..., tuple]:
    """Turn a per-step index table into the walker's index_map callable."""
    strides = [1] * len(grid)
    for i in range(len(grid) - 2, -1, -1):
        strides[i] = strides[i + 1] * grid[i + 1]

    def index_map(*step: int) -> tuple[int, ...]:
        lin = 0
        for s, st in zip(step, strides):
            lin += int(s) * st
        return tuple(int(x) for x in table[lin])

    # The walker reads the whole table at once when present, skipping the
    # per-step closure calls (grid.py `_op_table`).
    index_map.table = table
    return index_map


def _prefetch_spec(name: str, sds) -> OperandSpec:
    """Scalar-prefetch operand: copied to SMEM once before the grid runs —
    a whole-array input with a constant index map (the walker emits its
    words a single time, at grid start)."""
    shape = tuple(int(d) for d in sds.shape)
    rank = len(shape)
    return OperandSpec(
        name=name, role="in", shape=shape, block_shape=shape,
        index_map=lambda *step, _r=rank: (0,) * _r,
        elems_per_word=elems_per_word(sds.dtype, shape[-1]),
    )


def from_jaxpr(fn, args: Sequence, *, scalar_values: Sequence = (),
               flops: float | None = 0.0,
               name: str | None = None) -> GridCapture:
    """Capture one kernel launch's geometry by tracing its jaxpr.

    ``fn`` is traced with ``jax.make_jaxpr`` over ``args`` (concrete arrays
    or ``jax.ShapeDtypeStruct`` placeholders — only shapes/dtypes matter to
    the trace) and must contain exactly one ``pallas_call``.
    ``scalar_values`` supplies the **concrete** values of the call's
    scalar-prefetch operands in order (``num_index_operands`` of them);
    they are needed to evaluate data-dependent index maps (gather /
    paged-KV / MoE dispatch) and must equal the values the real launch
    would receive.  ``flops`` is the arithmetic-op count of the whole
    launch; ``None`` derives it by counting the kernel jaxpr's arithmetic
    eqns (:mod:`repro.capture.flops`) — hooks that keep a hand formula
    pass it explicitly so AI stays identical to the mirrored path.
    """
    import jax

    closed = jax.make_jaxpr(fn)(*args)
    eqns = find_pallas_eqns(closed.jaxpr)
    if len(eqns) != 1:
        raise ValueError(
            f"expected exactly one pallas_call in the traced jaxpr, "
            f"found {len(eqns)}")
    return capture_pallas_eqn(eqns[0], scalar_values=scalar_values,
                              flops=flops, name=name)


def block_dim_size(dim) -> int:
    """Elements one block spans along one dim of a ``BlockMapping``.

    ``Blocked(n)`` spans ``n``; ``Squeezed`` (what a ``None`` block dim
    becomes) spans one element.  ``Element`` and ``BoundedSlice`` index
    maps return element offsets rather than block indices, which the
    walker does not model, so they are refused.
    """
    from jax.experimental import pallas as pl

    if dim is None or isinstance(dim, pl.Squeezed):
        return 1
    if isinstance(dim, pl.Blocked):
        return int(dim.block_size)
    raise NotImplementedError(
        f"block dim {dim!r}: only Blocked and Squeezed dims are captured")


def capture_pallas_eqn(eqn, *, scalar_values: Sequence = (),
                       flops: float | None = None,
                       name: str | None = None) -> GridCapture:
    """Capture one already-traced ``pallas_call`` equation's geometry.

    The eqn-level entry point :func:`from_jaxpr` bottoms out in — and the
    one :mod:`repro.capture.model` calls directly for every ``pallas_call``
    it discovers inside a whole-step jaxpr.  ``flops=None`` (the default
    here, unlike :func:`from_jaxpr`'s legacy ``0.0``) counts the kernel
    body's arithmetic eqns times the grid-step count.
    """
    gm = eqn.params["grid_mapping"]
    grid = tuple(int(g) for g in gm.grid)
    in_shapes = list(gm.in_shapes)
    out_shapes = list(gm.out_shapes)
    n_prefetch = int(gm.num_index_operands)
    if len(scalar_values) != n_prefetch:
        raise ValueError(
            f"kernel has {n_prefetch} scalar-prefetch operand(s); got "
            f"{len(scalar_values)} scalar_values")

    operands: list[OperandSpec] = []
    for i, sds in enumerate(in_shapes[:n_prefetch]):
        operands.append(_prefetch_spec(f"in{i}", sds))

    block_mapped = (
        [(f"in{i + n_prefetch}", "in", sds)
         for i, sds in enumerate(in_shapes[n_prefetch:])]
        + [(f"out{i}", "out", sds) for i, sds in enumerate(out_shapes)]
    )
    mappings = list(gm.block_mappings)
    if len(mappings) != len(block_mapped):
        raise ValueError(
            f"block-mapping count {len(mappings)} != block-mapped operand "
            f"count {len(block_mapped)}")
    scalars = tuple(np.asarray(v) for v in scalar_values)
    for (op_name, role, sds), bm in zip(block_mapped, mappings):
        block_shape = tuple(block_dim_size(b) for b in bm.block_shape)
        table = _tabulate_index_map(bm.index_map_jaxpr, grid, scalars)
        if table.shape[1] != len(block_shape):
            raise ValueError(
                f"{op_name}: index map returns {table.shape[1]} block "
                f"indices for a rank-{len(block_shape)} block")
        operands.append(OperandSpec(
            name=op_name, role=role,
            shape=tuple(int(d) for d in sds.shape),
            block_shape=block_shape,
            index_map=_table_index_map(table, grid),
            elems_per_word=elems_per_word(
                sds.dtype, block_shape[-1],
                sds.shape[-1] if len(sds.shape) > 1 else 0),
        ))

    if name is None:
        info = eqn.params.get("name_and_src_info")
        name = getattr(info, "name", None) or "pallas_call"
    if flops is None:
        from .flops import eqn_flops
        flops = eqn_flops(eqn)
    return GridCapture(
        name=name, grid=grid, operands=tuple(operands), flops=flops)
