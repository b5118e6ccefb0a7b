"""Pallas BlockSpec/grid DMA walker: kernel launch geometry -> HBM trace.

A Pallas TPU kernel's HBM traffic is fully determined by its launch
geometry: the grid, and one ``BlockSpec`` (block shape + index map) per
operand.  The pipeline fetches an *input* block when its index map output
changes between consecutive grid steps (an unchanged block is kept resident
in VMEM — the "revisiting" optimization), and writes an *output* block on
the last consecutive grid step that maps to it.  ``pl.when`` guards inside
the kernel body do **not** suppress these automatic copies; they gate
compute only.

:func:`walk` replays that schedule in pure NumPy and emits the resulting
HBM **word**-address stream (8-byte words, matching the DAMOV trace
convention; fp32 elements pack two per word) — loads and stores per operand
tile, in issue order.  The walker is deterministic, needs neither a TPU nor
jax, and produces the same word-address traces
:mod:`repro.core.cachesim` consumes for the synthetic suite, so captured
kernels and synthetic workloads are characterized by one methodology.

Two capture paths feed the walker, and they are **stream-identical by
contract**:

- :func:`from_jaxpr` (the default whenever jax is importable) traces the
  kernel's ``pallas_call`` and reads the geometry straight out of the
  jaxpr — zero mirroring; see :mod:`repro.capture.jaxpr`;
- the per-kernel ``capture.py`` hooks keep a mirrored-geometry fallback so
  a jax-free interpreter can still build the full suite registry.

Counter-identity invariant: for every captured entry, the two paths emit
**byte-identical** word-address streams and equal load/store/flop counters
(``tests/test_capture_jaxpr.py`` diffs them over the whole legacy roster),
so suite-store fingerprints, AI columns and class verdicts never depend on
which path produced a trace.  The walker itself upholds the counter
contract ``refs == loads + stores == addresses.size`` on full and windowed
walks, and a ``count_only`` walk returns the same counters with an empty
address array.
"""

from __future__ import annotations

import math
import weakref
from dataclasses import dataclass
from typing import Callable

import numpy as np

from repro import obs

__all__ = [
    "OperandSpec",
    "GridCapture",
    "CaptureResult",
    "walk",
    "from_jaxpr",
    "WORDS_PER_FP32_PAIR",
]

# DAMOV traces address 8-byte words; the repo's kernels run fp32 (4 B), so
# two elements share one word address.
WORDS_PER_FP32_PAIR = 2

_LINE_WORDS = 8  # 64 B cache line, for base-address alignment only


@dataclass(frozen=True)
class OperandSpec:
    """One ``pl.BlockSpec`` of a kernel launch, as data.

    ``index_map`` receives the grid indices (same signature as the Pallas
    index map, minus scalar-prefetch refs, which hooks close over) and
    returns the block index tuple.
    """

    name: str
    role: str                       # "in" | "out"
    shape: tuple[int, ...]          # logical array shape, elements
    block_shape: tuple[int, ...]    # BlockSpec block shape, elements
    index_map: Callable[..., tuple[int, ...]]
    elems_per_word: int = WORDS_PER_FP32_PAIR

    def __post_init__(self) -> None:
        if self.role not in ("in", "out"):
            raise ValueError(f"{self.name}: role must be 'in'|'out'")
        if len(self.shape) != len(self.block_shape):
            raise ValueError(
                f"{self.name}: rank mismatch {self.shape} vs {self.block_shape}"
            )
        # Word collapse (`words[::elems_per_word]`) requires every row
        # start to be word-aligned; row strides are multiples of the array
        # last dim, so it must divide evenly (rank-1 operands are a single
        # span and only need the block-level check in _tile_words).
        if len(self.shape) > 1 and self.shape[-1] % self.elems_per_word:
            raise ValueError(
                f"{self.name}: array last dim {self.shape[-1]} not a "
                f"multiple of {self.elems_per_word} elems/word")

    @property
    def words(self) -> int:
        """Array footprint in 8-byte words."""
        n = 1
        for d in self.shape:
            n *= d
        return -(-n // self.elems_per_word)


@dataclass(frozen=True)
class GridCapture:
    """Per-thread launch geometry of one kernel invocation."""

    name: str
    grid: tuple[int, ...]
    operands: tuple[OperandSpec, ...]
    flops: float = 0.0              # arithmetic ops of the whole launch


@dataclass
class CaptureResult:
    """The captured HBM word-address stream + accounting."""

    name: str
    addresses: np.ndarray           # word addresses, issue order
    loads: int
    stores: int
    footprint_words: int            # sum of operand array footprints
    grid_steps: int
    flops: float

    @property
    def refs(self) -> int:
        # == addresses.size for a full or windowed walk; also correct for
        # a count-only walk, whose address array is empty.
        return self.loads + self.stores

    @property
    def flops_per_ref(self) -> float:
        return self.flops / self.refs if self.refs else 0.0


def _tile_words(op: OperandSpec, block_idx: tuple[int, ...],
                base_word: int) -> np.ndarray:
    """Word addresses of one block, row-major element order (DMA order)."""
    shape, blk = op.shape, op.block_shape
    # Row-major strides in elements.
    strides = [1] * len(shape)
    for i in range(len(shape) - 2, -1, -1):
        strides[i] = strides[i + 1] * shape[i + 1]
    # Element offsets of every row of the block along the last axis.
    lead = [
        np.arange(b) * s + i * b * s
        for i, b, s, in zip(block_idx[:-1], blk[:-1], strides[:-1])
    ]
    starts = np.zeros(1, dtype=np.int64)
    for axis in lead:
        starts = (starts[:, None] + axis[None, :]).ravel()
    last_b = blk[-1]
    last_start = block_idx[-1] * last_b
    if last_b % op.elems_per_word or last_start % op.elems_per_word:
        raise ValueError(
            f"{op.name}: block rows must be word-aligned "
            f"(last dim {last_b} at offset {last_start}, "
            f"{op.elems_per_word} elems/word)")
    # Each row is a contiguous span of `last_b` elements; emit its words.
    row = np.arange(last_start, last_start + last_b, dtype=np.int64)
    elems = (starts[:, None] + row[None, :]).ravel()
    words = elems // op.elems_per_word
    # Collapse element-pairs sharing one word (fp32: stride-2 duplicates).
    if op.elems_per_word > 1:
        words = words[:: op.elems_per_word]
    return base_word + words


def _tile_rows(op: OperandSpec, idxs: np.ndarray,
               base_word: int) -> tuple[np.ndarray, int]:
    """Word runs of many blocks of one operand at once.

    ``idxs`` is ``(k, rank)``.  Returns ``(starts, w)``: ``starts[i, r]``
    is the first word address of row ``r`` (row-major over the block's
    leading axes, ``R = prod(block_shape[:-1])`` rows) of block ``i``, and
    each row is the ``w = block_shape[-1] // elems_per_word`` contiguous
    words from its start, so ``(starts[i, :, None] + arange(w)).ravel()``
    equals ``_tile_words(op, tuple(idxs[i]), base_word)``.  Every row
    starts word-aligned (the array's last dim and the block's are whole
    words), so the floor division runs once a row, not once an element.
    """
    shape, blk = op.shape, op.block_shape
    strides = [1] * len(shape)
    for i in range(len(shape) - 2, -1, -1):
        strides[i] = strides[i + 1] * shape[i + 1]
    k = idxs.shape[0]
    last_b = blk[-1]
    if last_b % op.elems_per_word:
        # With last_b word-aligned every block offset idx*last_b is too,
        # so this single check covers _tile_words' per-block guard.
        raise ValueError(
            f"{op.name}: block rows must be word-aligned "
            f"(last dim {last_b}, {op.elems_per_word} elems/word)")
    starts = (idxs[:, -1] * last_b)[:, None]
    for a in range(len(blk) - 1):
        ax = np.arange(blk[a], dtype=np.int64) * strides[a]
        offs = idxs[:, a, None] * (blk[a] * strides[a]) + ax[None, :]
        starts = (starts[:, :, None] + offs[:, None, :]).reshape(k, -1)
    w = last_b // op.elems_per_word
    return base_word + starts // op.elems_per_word, w


def from_jaxpr(fn, args, *, scalar_values=(), flops: float = 0.0,
               name: str | None = None) -> GridCapture:
    """Capture a kernel's launch geometry by tracing its ``pallas_call``.

    Thin entry point for :func:`repro.capture.jaxpr.from_jaxpr` (imported
    lazily so this module stays importable without jax); see that module
    for the walk-the-eqn-params contract.
    """
    from .jaxpr import from_jaxpr as _from_jaxpr

    return _from_jaxpr(fn, args, scalar_values=scalar_values, flops=flops,
                       name=name)


def walk(cap: GridCapture, *, count_only: bool = False,
         bases: dict[str, int] | None = None,
         span: tuple[int, int] | None = None) -> CaptureResult:
    """Replay the pipeline schedule and emit the word-address stream.

    Arrays are laid out back-to-back in HBM, line-aligned, in operand
    order.  Per grid step (row-major order, last axis fastest — the Pallas
    sequential iteration order): fetch every input block whose index map
    output changed, then write back every output block whose residency ends
    at this step.

    ``count_only`` skips address materialization and returns only the
    load/store/flop accounting (used to derive per-ref AI without paying
    for megaword traces, e.g. by ``python -m repro.suite --list``).

    ``bases`` overrides the per-operand base word addresses (operand name
    -> absolute base).  :mod:`repro.capture.model` places every op of a
    whole-model capture in one shared address space this way — its
    allocator applies the *same* line-aligned sizing rule as the default
    layout here, so a single-op model capture is byte-identical to the
    standalone walk (the differential gate in
    ``tests/test_capture_model.py``).

    ``span=(lo, hi)`` emits only references ``[lo, hi)`` of the stream
    (clipped to its length): ``addresses`` equals ``walk(cap).addresses
    [lo:hi]`` and ``loads``/``stores`` count that slice by role.  A
    vectorized walk tiles only the blocks that overlap the span, so a
    window's slice of a large op costs the slice, not the op.
    """
    if span is not None:
        if count_only:
            raise ValueError("span needs addresses; count_only emits none")
        if not 0 <= span[0] <= span[1]:
            raise ValueError(f"span {span}: need 0 <= lo <= hi")
    with obs.span("capture.walk", kernel=cap.name, count_only=count_only):
        res = _walk(cap, count_only=count_only, bases=bases, span=span)
    obs.count("capture.walk.calls")
    obs.count("capture.walk.refs", res.refs)
    return res


def _clip_span(span: tuple[int, int] | None, total: int) -> tuple[int, int]:
    """``span`` clipped to a stream of ``total`` refs (all of it when
    ``span`` is None); counts a span short of the whole stream."""
    if span is None:
        return 0, total
    lo, hi = min(span[0], total), min(span[1], total)
    if hi - lo < total:
        obs.count("capture.walk.window_calls")
        obs.count("capture.walk.skipped_refs", total - (hi - lo))
    return lo, hi


def _block_words(op: OperandSpec) -> int:
    n = 1
    for d in op.block_shape:
        n *= d
    return -(-n // op.elems_per_word)


_OP_TABLES: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _op_table(op: OperandSpec, steps: list[tuple[int, ...]]) -> np.ndarray:
    """Per-step block-index table, ``(n_steps, block_rank)`` int64.

    jaxpr-captured index maps carry their precomputed table (set by
    ``_table_index_map``); mirrored Python index maps are evaluated once
    per step and memoized per map object (keyed weakly, revalidated
    against the step list) — a capture walked once per core count pays
    the per-step Python only on its first walk.
    """
    tbl = getattr(op.index_map, "table", None)
    if tbl is not None and len(tbl) == len(steps):
        return np.asarray(tbl, dtype=np.int64).reshape(len(steps), -1)
    cached = _OP_TABLES.get(op.index_map)
    if cached is not None and cached[0] == steps:
        return cached[1]
    rows = np.empty((len(steps), len(op.block_shape)), dtype=np.int64)
    for si, step in enumerate(steps):
        rows[si] = [int(x) for x in op.index_map(*step)]
    try:
        _OP_TABLES[op.index_map] = (list(steps), rows)
    except TypeError:
        pass                      # unhashable / non-weakref map: skip memo
    return rows


def _walk(cap: GridCapture, *, count_only: bool,
          bases: dict[str, int] | None,
          span: tuple[int, int] | None = None) -> CaptureResult:
    """Vectorized pipeline replay.

    Emission decisions are mask arithmetic over per-operand index tables;
    only the steps that actually move a block run any per-step Python.
    Counter- and byte-identical to the scalar reference walker
    (:func:`_walk_loop`, kept for the differential gate in
    ``tests/test_capture.py``):

    - an *input* fetches when its block index differs from the previous
      value recorded under its operand name — names are shared state, so
      the comparison runs over the step-major, operand-order merged
      sequence of every same-named operand;
    - an *output* writes back when its own next-step index differs (or at
      the final step).

    With a ``span``, only the events whose blocks overlap it are tiled.
    """
    if bases is None:
        base: dict[str, int] = {}
        cursor = 0
        for op in cap.operands:
            if op.name not in base:
                base[op.name] = cursor
                cursor += (-(-op.words // _LINE_WORDS) * _LINE_WORDS
                           + _LINE_WORDS)
    else:
        base = {op.name: bases[op.name] for op in cap.operands}

    n_steps = math.prod(cap.grid)
    if n_steps == 0:
        footprint = sum({op.name: op.words for op in cap.operands}.values())
        return CaptureResult(
            name=cap.name, addresses=np.empty(0, dtype=np.int64),
            loads=0, stores=0, footprint_words=footprint, grid_steps=0,
            flops=cap.flops)
    if count_only and n_steps == 1:
        # Single-step launch (gridless ops dominate whole-model traces):
        # every input fetches once, every output writes back once.
        loads = stores = 0
        for op in cap.operands:
            if op.role == "in":
                loads += _block_words(op)
            else:
                stores += _block_words(op)
        footprint = sum({op.name: op.words for op in cap.operands}.values())
        return CaptureResult(
            name=cap.name, addresses=np.empty(0, dtype=np.int64),
            loads=loads, stores=stores, footprint_words=footprint,
            grid_steps=1, flops=cap.flops)
    if n_steps * len(cap.operands) <= 64:
        # Tiny launches (whole-model traces are thousands of small ops):
        # mask setup costs more than just walking the steps.
        return _walk_loop(cap, count_only=count_only, bases=bases,
                          span=span)
    with obs.span("capture.walk.schedule"):
        steps = list(np.ndindex(*cap.grid))
        tables = [_op_table(op, steps) for op in cap.operands]

        # Merged change masks per operand name (inputs consult the last
        # index written by ANY same-named operand, outputs included).
        by_name: dict[str, list[int]] = {}
        for oi, op in enumerate(cap.operands):
            by_name.setdefault(op.name, []).append(oi)
        emit = np.zeros((len(cap.operands), n_steps), dtype=bool)
        for name, ois in by_name.items():
            k = len(ois)
            merged = np.stack([tables[oi] for oi in ois], axis=1)  # (n,k,r)
            flat = merged.reshape(n_steps * k, -1)
            changed = np.empty(n_steps * k, dtype=bool)
            changed[0] = True
            np.any(flat[1:] != flat[:-1], axis=1, out=changed[1:])
            changed = changed.reshape(n_steps, k)
            for j, oi in enumerate(ois):
                if cap.operands[oi].role == "in":
                    emit[oi] = changed[:, j]
        for oi, op in enumerate(cap.operands):
            if op.role != "in":
                t = tables[oi]
                emit[oi, -1] = True
                np.any(t[1:] != t[:-1], axis=1, out=emit[oi, :-1])

    loads = stores = 0
    if count_only:
        for oi, op in enumerate(cap.operands):
            words = int(emit[oi].sum()) * _block_words(op)
            if op.role == "in":
                loads += words
            else:
                stores += words
        addr = np.empty(0, dtype=np.int64)
    else:
        # nonzero on the transposed mask yields events in (step, operand)
        # lexicographic order — the scalar walker's emission order.  All
        # of one operand's blocks tile in a single batched call as word
        # runs, one per block row, and are placed at row granularity.
        with obs.span("capture.walk.emit"):
            si_arr, oi_arr = np.nonzero(emit.T)
            bw = np.array([_block_words(op) for op in cap.operands],
                          dtype=np.int64)
            sizes = bw[oi_arr]
            ends = np.cumsum(sizes)
            lo, hi = _clip_span(span, int(ends[-1]) if ends.size else 0)
            # The stream is the events' blocks back to back, so the events
            # overlapping [lo, hi) are one run [e0, e1): tile only those.
            e0 = int(np.searchsorted(ends, lo, side="right"))
            e1 = int(np.searchsorted(ends - sizes, hi, side="left"))
            si_arr, oi_arr = si_arr[e0:e1], oi_arr[e0:e1]
            sizes, ends = sizes[e0:e1], ends[e0:e1]
            first = int(ends[0] - sizes[0]) if ends.size else lo
            last = int(ends[-1]) if ends.size else lo
            runs = []
            for oi, op in enumerate(cap.operands):
                sel = np.flatnonzero(oi_arr == oi)
                if sel.size:
                    runs.append((op, sel, *_tile_rows(
                        op, tables[oi][si_arr[sel]], base[op.name])))
            # A block is R rows of w words, so every event starts a
            # multiple of g, the row widths' gcd, after `first`: place the
            # start of each g-word chunk, then expand all chunks at once.
            g = math.gcd(*(w for *_, w in runs)) if runs else 1
            chunks = np.empty((last - first) // g, dtype=np.int64)
            rows = 0
            for op, sel, starts, w in runs:
                # a block's chunks are its rows' chunks, back to back
                block = (starts[:, :, None]
                         + np.arange(0, w, g)).reshape(sel.size, -1)
                at = (ends[sel] - sizes[sel] - first) // g
                chunks[at[:, None] + np.arange(block.shape[1])] = block
                rows += starts.size
                if op.role == "in":
                    loads += starts.size * w
                else:
                    stores += starts.size * w
            addr = np.empty(last - first, dtype=np.int64)
            np.add(chunks[:, None], np.arange(g, dtype=np.int64),
                   out=addr.reshape(-1, g))
            obs.count("capture.walk.emit_runs", rows)
            if (first, last) != (lo, hi):
                # the run's first and last blocks may stick out of the span
                for e, out in ((0, lo - first), (-1, last - hi)):
                    if cap.operands[oi_arr[e]].role == "in":
                        loads -= out
                    else:
                        stores -= out
                addr = addr[lo - first:hi - first]

    footprint = sum({op.name: op.words for op in cap.operands}.values())
    return CaptureResult(
        name=cap.name,
        addresses=addr.astype(np.int64, copy=False),
        loads=loads,
        stores=stores,
        footprint_words=footprint,
        grid_steps=n_steps,
        flops=cap.flops,
    )


def _walk_loop(cap: GridCapture, *, count_only: bool,
               bases: dict[str, int] | None,
               span: tuple[int, int] | None = None) -> CaptureResult:
    """Scalar reference walker — the schedule spelled out one step at a
    time.  Serves tiny launches (where mask setup would dominate) and the
    differential gate that diffs it against the vectorized :func:`_walk`
    over the captured-kernel roster.  A ``span`` slices the whole walk.
    """
    if bases is None:
        base: dict[str, int] = {}
        cursor = 0
        for op in cap.operands:
            if op.name not in base:
                base[op.name] = cursor
                cursor += (-(-op.words // _LINE_WORDS) * _LINE_WORDS
                           + _LINE_WORDS)
    else:
        base = {op.name: bases[op.name] for op in cap.operands}

    steps = list(np.ndindex(*cap.grid))
    chunks: list[np.ndarray] = []
    roles: list[str] = []
    loads = stores = 0
    prev_idx: dict[str, tuple[int, ...] | None] = {
        op.name: None for op in cap.operands
    }
    for si, step in enumerate(steps):
        nxt = steps[si + 1] if si + 1 < len(steps) else None
        for op in cap.operands:
            bidx = tuple(int(x) for x in op.index_map(*step))
            if op.role == "in":
                if bidx != prev_idx[op.name]:
                    if count_only:
                        loads += _block_words(op)
                    else:
                        w = _tile_words(op, bidx, base[op.name])
                        chunks.append(w)
                        roles.append(op.role)
                        loads += w.size
            else:
                nidx = (
                    tuple(int(x) for x in op.index_map(*nxt))
                    if nxt is not None else None
                )
                if nidx != bidx:  # residency ends here -> write back
                    if count_only:
                        stores += _block_words(op)
                    else:
                        w = _tile_words(op, bidx, base[op.name])
                        chunks.append(w)
                        roles.append(op.role)
                        stores += w.size
            prev_idx[op.name] = bidx

    addr = (
        np.concatenate(chunks)
        if chunks else np.empty(0, dtype=np.int64)
    )
    if span is not None:
        lo, hi = _clip_span(span, addr.size)
        addr = addr[lo:hi]
        loads = stores = pos = 0
        for w, role in zip(chunks, roles):
            n = max(0, min(pos + w.size, hi) - max(pos, lo))
            pos += w.size
            if role == "in":
                loads += n
            else:
                stores += n
    footprint = sum({op.name: op.words for op in cap.operands}.values())
    return CaptureResult(
        name=cap.name,
        addresses=addr.astype(np.int64, copy=False),
        loads=loads,
        stores=stores,
        footprint_words=footprint,
        grid_steps=len(steps),
        flops=cap.flops,
    )
