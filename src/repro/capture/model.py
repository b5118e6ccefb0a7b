"""Whole-model capture: one jitted step's jaxpr -> one concatenated trace.

:mod:`repro.capture.jaxpr` captures a *single* ``pallas_call``.  This
module walks the jaxpr of a whole jitted step — a config's forward /
decode / train-step function traced with ``jax.make_jaxpr`` — and turns
**every** data-moving equation into a captured op in one shared HBM
address space, concatenating the per-op DMA walks in real program order:

- ``pallas_call`` eqns (discovered recursively through ``pjit`` / ``scan``
  / ``cond`` / remat / custom_* sub-jaxprs) are captured with the existing
  :func:`~repro.capture.jaxpr.capture_pallas_eqn` ->
  :class:`~repro.capture.grid.GridCapture` -> :func:`~repro.capture.grid
  .walk` pipeline, byte-identically to their standalone capture (the
  single-kernel differential gate in ``tests/test_capture_model.py``);
- non-Pallas ``dot_general`` eqns lower to a canonical (G, M, N, K)
  MXU-tiled GridCapture — grid ``(G, M/bm, N/bn, K/bk)``, k-innermost, the
  classic accumulate schedule — so dense layers' weight/activation traffic
  is not invisible;
- ``conv_general_dilated`` and large arithmetic eqns (norms, softmaxes,
  optimizer updates — anything with >= ``stream_min_elems`` elements
  moved) lower to single-step whole-array *synthetic stream* ops: inputs
  read once, outputs written once;
- everything else moves no words (index math, reshapes, small fused
  elementwise ops — the TPU keeps those in registers/VMEM).

Inter-op data flow is modeled by a **Var-keyed region allocator**: every
jaxpr variable that any captured op touches gets a line-aligned region
(the *same* sizing rule :func:`~repro.capture.grid.walk` applies
internally, which is what makes the single-op gate byte-identical), and

- an op consuming another op's output var reads the producer's region
  (real producer->consumer reuse);
- ``scan`` is unrolled: per-iteration xs/ys slices address
  ``stacked_base + i * slice_words`` inside the stacked operand's region,
  const operands (weights shared across iterations) keep one region, and
  the carry ping-pongs in place — so a layer stack's residual stream is
  one hot buffer, exactly the reuse a cache simulation must see;
- small same-size elementwise ops are *transparent*: their output
  aliases their input's region (fused chains move no extra words but
  preserve producer->consumer locality through them).

Approximations (all documented here, none load-bearing for the six-class
verdict): ``while`` bodies are walked once (the model zoo's steps use
``scan``); ``cond`` takes its worst (max-FLOP) branch; scalar-prefetch
operands of nested Pallas kernels get placeholder (zero) values when the
surrounding trace is abstract; gather/scatter index traffic is dropped
(single-token cache updates are negligible next to the weight streams).

FLOPs come from :func:`repro.capture.flops.count_flops` over the *whole*
jaxpr — including the elementwise eqns that emit no trace — so a
whole-model workload's AI reflects everything the step computes, not just
the ops that moved words.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from .flops import count_flops, eqn_flops
from .grid import _LINE_WORDS, CaptureResult, GridCapture, OperandSpec, walk
from .jaxpr import capture_pallas_eqn, elems_per_word

__all__ = ["ModelOp", "ModelCapture", "capture_model"]

# Arithmetic eqns below this many moved elements (inputs + outputs) stay
# in VMEM/registers in our traffic model; at or above it they lower to a
# single-step whole-array stream op.  32768 fp32 elements = 128 KiB.
STREAM_MIN_ELEMS = 32768

# Runaway-unroll backstop: a smoke-config step flattens to hundreds of
# ops, not tens of thousands.
_MAX_OPS = 20_000

# Dense-dot grid-step ceiling; tiles grow past the 128-lane MXU tile
# before a dot degenerates to a whole-array stream (walk cost is
# per-step Python, so unbounded grids would make capture, not the
# simulated workload, the bottleneck).
_MAX_DOT_STEPS = 8192

# Same-size elementwise prims whose output aliases an input region when
# they are too small to emit a stream op (fused chains).
_TRANSPARENT = frozenset({
    "convert_element_type", "reshape", "transpose", "squeeze",
    "expand_dims", "add", "sub", "mul", "div", "max", "min", "neg", "exp",
    "log", "tanh", "logistic", "sqrt", "rsqrt", "integer_pow",
    "stop_gradient", "select_n", "copy",
})


@dataclass(frozen=True)
class ModelOp:
    """One captured op of a whole-model trace.

    ``bases`` maps the capture's operand names to absolute base word
    addresses in the model's shared address space; ``kind`` is
    ``"pallas"`` | ``"dense"`` | ``"stream"``.
    """

    name: str
    kind: str
    capture: GridCapture
    bases: dict[str, int]

    def walk(self, *, count_only: bool = False,
             span: tuple[int, int] | None = None) -> CaptureResult:
        """The op's walk; ``span`` emits only its refs ``[lo, hi)``
        (:func:`~repro.capture.grid.walk`)."""
        if count_only and span is None:
            # Count-only walks are pure and repeated (walk_window sizes
            # every op, then whole-step accounting counts them again), so
            # cache on the instance (frozen dataclass → object.__setattr__).
            got = getattr(self, "_counts", None)
            if got is None:
                got = walk(self.capture, count_only=True, bases=self.bases)
                object.__setattr__(self, "_counts", got)
            return got
        return walk(self.capture, count_only=count_only, bases=self.bases,
                    span=span)


@dataclass
class ModelCapture:
    """A whole step's ops in program order + whole-jaxpr accounting."""

    name: str
    ops: tuple[ModelOp, ...]
    flops: float                # counted over the WHOLE jaxpr
    footprint_words: int        # allocator high-water mark

    @property
    def op_kinds(self) -> dict[str, int]:
        counts: dict[str, int] = {}
        for op in self.ops:
            counts[op.kind] = counts.get(op.kind, 0) + 1
        return counts

    def walk(self, *, count_only: bool = False) -> CaptureResult:
        """Concatenate every op's DMA walk in program order."""
        chunks: list[np.ndarray] = []
        loads = stores = steps = 0
        for op in self.ops:
            r = op.walk(count_only=count_only)
            loads += r.loads
            stores += r.stores
            steps += r.grid_steps
            if not count_only:
                chunks.append(r.addresses)
        if not count_only:
            from repro import obs

            # Counted so the streamed data path (walk_stream ->
            # simulate_chunked) can be *gated* on never materializing a
            # concatenated whole-step trace (benchmarks.perf_gate
            # --obs-require 'capture.model.concat==0').
            obs.count("capture.model.concat")
        addr = (np.concatenate(chunks) if chunks
                else np.empty(0, dtype=np.int64))
        return CaptureResult(
            name=self.name, addresses=addr, loads=loads, stores=stores,
            footprint_words=self.footprint_words, grid_steps=steps,
            flops=self.flops)

    def walk_stream(self, target_refs: int | None = None, *,
                    center: float = 0.5):
        """Yield per-op address blocks in program order, never concatenated.

        The generator form of :meth:`walk` / :meth:`walk_window`: feeding
        it to :func:`repro.core.cachesim_stream.simulate_chunked` (which
        accepts any iterable of address blocks) simulates the whole step
        under a fixed memory ceiling — peak trace memory is the largest
        single op's walk (with a target, the largest slice of an op in the
        window), regardless of how many megarefs the step emits.
        Counter identity is structural: with ``target_refs=None`` the
        yielded blocks concatenate to exactly ``walk().addresses``; with a
        target they concatenate to ``walk_window(target_refs, center=
        center).addresses`` (same count-only sizing pass, same boundary
        slices).  Like ``walk_window``, a shorter-than-target step streams
        whole (callers cycle it, the ``np.resize`` convention).
        """
        from repro import obs

        if target_refs is None:
            for op in self.ops:
                addr = op.walk().addresses
                if addr.size:
                    obs.count("capture.model.stream_blocks")
                    yield addr
            return
        with obs.span("capture.model.window", model=self.name):
            counts, window = self._window(target_refs, center)
        if window is None:
            yield from self.walk_stream()
            return
        for blk in self._window_blocks(counts, *window):
            obs.count("capture.model.stream_blocks")
            yield blk

    def _window(self, target_refs: int, center: float):
        """Every op's count-only walk and the centred window ``(start,
        end)`` of ``target_refs`` refs, or None where the step is no
        longer than the target."""
        if target_refs <= 0:
            raise ValueError("target_refs must be positive")
        counts = [op.walk(count_only=True) for op in self.ops]
        total = sum(r.refs for r in counts)
        if total <= target_refs:
            return counts, None
        start = int((total - target_refs) * min(max(center, 0.0), 1.0))
        return counts, (start, start + target_refs)

    def _window_blocks(self, counts, start: int, end: int):
        """Each op's slice of refs ``[start, end)`` of the step, in program
        order; an op the window cuts emits only its slice.  Counts the ops
        the window overlaps (``capture.model.window_ops``)."""
        from repro import obs

        pos = 0
        for op, r in zip(self.ops, counts):
            nxt = pos + r.refs
            if nxt > start and pos < end:
                obs.count("capture.model.window_ops")
                blk = op.walk(span=(max(0, start - pos),
                                    min(r.refs, end - pos))).addresses
                if blk.size:
                    yield blk
            pos = nxt
            if pos >= end:
                break

    def walk_window(self, target_refs: int, *,
                    center: float = 0.5) -> CaptureResult:
        """A representative contiguous window of the whole-step trace.

        Train steps emit tens of megarefs; simulating all of them buys
        nothing over a steady-state slice (the weight streams repeat layer
        after layer), so the zoo samples one contiguous ``target_refs``
        window (SimPoint-style, ``center`` picks where).  Each op emits
        only its slice of the window, so peak memory is the window — the
        full multi-hundred-MB trace is never materialized.
        Shorter-than-target traces come back whole (callers cycle them,
        the ``np.resize`` convention).  Load/store counters are scaled pro
        rata; ``flops`` stays the whole-step count so AI must be taken
        against the whole-step ``refs``, not the window length.
        """
        from repro import obs

        with obs.span("capture.model.window", model=self.name):
            counts, window = self._window(target_refs, center)
        if window is None:
            return self.walk()

        obs.count("capture.model.concat")  # windowed traces materialize too
        addr = np.concatenate(list(self._window_blocks(counts, *window)))
        total = sum(r.refs for r in counts)
        loads = sum(r.loads for r in counts)
        w_loads = int(round(loads * target_refs / total))
        return CaptureResult(
            name=self.name, addresses=addr, loads=w_loads,
            stores=target_refs - w_loads,
            footprint_words=self.footprint_words,
            grid_steps=sum(r.grid_steps for r in counts),
            flops=self.flops)


# --------------------------------------------------------------------------
# Region allocator.  Refs are resolved lazily: ("region", key) allocates on
# first materialization (when a consuming op knows the operand's words),
# ("slice", parent, i, L) addresses iteration i of a scanned operand inside
# the parent's L-slice region.
# --------------------------------------------------------------------------
class _Alloc:
    def __init__(self) -> None:
        self.cursor = 0
        self.regions: dict[object, tuple[int, int]] = {}

    def region(self, key, words: int) -> int:
        got = self.regions.get(key)
        if got is not None and got[1] >= words:
            return got[0]
        # same line-aligned rule as walk()'s internal layout — the
        # single-op byte-identity contract depends on it
        base = self.cursor
        self.cursor += -(-words // _LINE_WORDS) * _LINE_WORDS + _LINE_WORDS
        self.regions[key] = (base, words)
        return base

    def base_for(self, ref, words: int) -> int:
        if ref[0] == "region":
            return self.region(ref[1], words)
        _, parent, i, length = ref
        return self.base_for(parent, words * length) + i * words


def _is_literal(v) -> bool:
    return hasattr(v, "val")


def _is_drop(v) -> bool:
    return type(v).__name__ == "DropVar"


def _elems(aval) -> int:
    n = 1
    for d in getattr(aval, "shape", ()):
        n *= int(d)
    return n


def _resolve(env: dict, v):
    """A var's region ref: its binding, defaulting to a fresh region keyed
    by the var itself (jaxpr vars are unique per trace scope)."""
    if _is_literal(v):
        return ("region", object())
    return env.get(v, ("region", v))


def _tile(n: int, cap: int = 128) -> int:
    t = max(1, min(n, cap))
    while n % t:
        t -= 1
    return t


def _whole_spec(name: str, role: str, aval) -> OperandSpec | None:
    """Whole-array single-step operand (conv / stream lowering)."""
    shape = tuple(int(d) for d in aval.shape)
    if not shape or 0 in shape:
        return None  # scalars and empties move no words
    rank = len(shape)
    return OperandSpec(
        name=name, role=role, shape=shape, block_shape=shape,
        index_map=lambda *s, _r=rank: (0,) * _r,
        elems_per_word=elems_per_word(aval.dtype, shape[-1]))


def _lower_dot(eqn) -> GridCapture | None:
    """Canonical MXU-tiled lowering of one ``dot_general``."""
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    out = eqn.outvars[0].aval
    g = k = 1
    for d in lb:
        g *= int(lhs.shape[d])
    for d in lc:
        k *= int(lhs.shape[d])
    m = max(1, _elems(lhs) // max(1, g * k))
    n = max(1, _elems(rhs) // max(1, g * k))
    if 0 in (g, m, n, k) or _elems(out) == 0:
        return None
    bm, bn, bk = _tile(m), _tile(n), _tile(k)
    steps = g * (m // bm) * (n // bn) * (k // bk)
    if steps > _MAX_DOT_STEPS:  # stream the whole K per tile first
        bk = k
        steps = g * (m // bm) * (n // bn)
    if steps > _MAX_DOT_STEPS:
        bm, bn = _tile(m, 1024), _tile(n, 1024)
        steps = g * (m // bm) * (n // bn)
    if steps > _MAX_DOT_STEPS:  # degenerate: one whole-array pass
        bm, bn, bk = m, n, k

    def spec(name, role, shape, block, imap, dtype):
        return OperandSpec(
            name=name, role=role, shape=shape, block_shape=block,
            index_map=imap,
            elems_per_word=elems_per_word(dtype, block[-1], shape[-1]))

    return GridCapture(
        name="dot_general",
        grid=(g, m // bm, n // bn, k // bk),
        operands=(
            spec("lhs", "in", (g, m, k), (1, bm, bk),
                 lambda gg, i, j, kk: (gg, i, kk), lhs.dtype),
            spec("rhs", "in", (g, k, n), (1, bk, bn),
                 lambda gg, i, j, kk: (gg, kk, j), rhs.dtype),
            spec("out", "out", (g, m, n), (1, bm, bn),
                 lambda gg, i, j, kk: (gg, i, j), out.dtype),
        ),
        flops=eqn_flops(eqn))


def _stream_capture(eqn) -> GridCapture | None:
    """Single-step whole-array lowering (conv + large arithmetic eqns)."""
    operands: list[OperandSpec] = []
    seen: list = []
    for i, v in enumerate(eqn.invars):
        if _is_literal(v) or v in seen:
            continue
        seen.append(v)
        spec = _whole_spec(f"in{i}", "in", v.aval)
        if spec is not None:
            operands.append(spec)
    n_in = len(operands)
    for i, v in enumerate(eqn.outvars):
        spec = _whole_spec(f"out{i}", "out", v.aval)
        if spec is not None:
            operands.append(spec)
    if not operands or len(operands) == n_in:
        return None
    return GridCapture(name=eqn.primitive.name, grid=(),
                       operands=tuple(operands), flops=eqn_flops(eqn))


def _pallas_placeholders(gm) -> tuple:
    """Zero-valued scalar-prefetch stand-ins for kernels whose routing
    indices are data-dependent (abstract at whole-model trace time)."""
    return tuple(
        np.zeros(tuple(int(d) for d in sds.shape),
                 dtype=np.dtype(sds.dtype))
        for sds in list(gm.in_shapes)[: int(gm.num_index_operands)])


class _Walker:
    def __init__(self, stream_min_elems: int) -> None:
        self.alloc = _Alloc()
        self.ops: list[ModelOp] = []
        self.stream_min_elems = stream_min_elems
        self._eqn_caps: dict[int, GridCapture | None] = {}
        self._seq = 0

    # -- op emission -------------------------------------------------------
    def _emit(self, kind: str, cap: GridCapture, operand_vars: list,
              env: dict) -> None:
        """Bind the capture's operands to regions, in operand order (the
        order walk() itself allocates, so a lone op reproduces the
        standalone layout bit for bit)."""
        if len(self.ops) >= _MAX_OPS:
            raise ValueError(
                f"whole-model capture exceeded {_MAX_OPS} ops — "
                f"unexpectedly deep unroll; raise stream_min_elems or "
                f"shrink the traced config")
        bases: dict[str, int] = {}
        for spec, v in zip(cap.operands, operand_vars):
            bases[spec.name] = self.alloc.base_for(
                _resolve(env, v), spec.words)
        self._seq += 1
        self.ops.append(ModelOp(
            name=f"{self._seq:04d}.{cap.name}", kind=kind, capture=cap,
            bases=bases))

    def _cached(self, eqn, build) -> GridCapture | None:
        got = self._eqn_caps.get(id(eqn), False)
        if got is False:
            got = build()
            self._eqn_caps[id(eqn)] = got
        return got

    # -- jaxpr walk --------------------------------------------------------
    def walk_jaxpr(self, jaxpr, env: dict) -> None:
        for eqn in jaxpr.eqns:
            self.eqn(eqn, env)

    def eqn(self, eqn, env: dict) -> None:
        name = eqn.primitive.name
        if name == "pallas_call":
            cap = self._cached(eqn, lambda: capture_pallas_eqn(
                eqn, scalar_values=_pallas_placeholders(
                    eqn.params["grid_mapping"]),
                flops=None))
            # capture operand order == invars (prefetch + block-mapped)
            # then outvars — exactly how capture_pallas_eqn names them
            self._emit("pallas", cap,
                       list(eqn.invars) + list(eqn.outvars), env)
            return
        if name == "dot_general":
            cap = self._cached(eqn, lambda: _lower_dot(eqn))
            if cap is not None:
                self._emit("dense", cap,
                           [eqn.invars[0], eqn.invars[1], eqn.outvars[0]],
                           env)
            return
        if name == "scan":
            self._scan(eqn, env)
            return
        if name == "cond":
            branches = eqn.params["branches"]
            branch = max(branches, key=count_flops)
            child = {
                bv: _resolve(env, ov)
                for bv, ov in zip(branch.jaxpr.invars, eqn.invars[1:])
                if not _is_drop(bv)
            }
            self.walk_jaxpr(branch.jaxpr, child)
            return
        if name == "while":
            body = eqn.params["body_jaxpr"]
            n_cc = int(eqn.params["cond_nconsts"])
            child = {
                bv: _resolve(env, ov)
                for bv, ov in zip(body.jaxpr.invars, eqn.invars[n_cc:])
                if not _is_drop(bv)
            }
            self.walk_jaxpr(body.jaxpr, child)  # one pass (documented)
            return
        inner = self._inner_jaxprs(eqn)
        if inner:
            self._generic_call(eqn, inner, env)
            return
        if name == "conv_general_dilated" or self._wants_stream(eqn):
            cap = self._cached(eqn, lambda: _stream_capture(eqn))
            if cap is not None:
                seen: list = []
                vs = []
                for v in eqn.invars:
                    if not _is_literal(v) and v not in seen \
                            and _elems(v.aval):
                        seen.append(v)
                        vs.append(v)
                vs += [v for v in eqn.outvars if _elems(v.aval)]
                self._emit("stream", cap, vs, env)
                return
        self._maybe_alias(eqn, env)

    @staticmethod
    def _inner_jaxprs(eqn) -> list:
        from .jaxpr import _param_jaxprs

        return [j for v in eqn.params.values() for j in _param_jaxprs(v)]

    def _generic_call(self, eqn, inner: list, env: dict) -> None:
        """pjit / remat / custom_* / closed_call: one sub-jaxpr whose
        invars line up 1:1 with the eqn's — thread regions through, and
        alias the eqn outputs to the callee's outputs."""
        if len(inner) == 1 and len(inner[0].invars) == len(eqn.invars):
            child = {
                bv: _resolve(env, ov)
                for bv, ov in zip(inner[0].invars, eqn.invars)
                if not _is_drop(bv)
            }
            self.walk_jaxpr(inner[0], child)
            for ov, iv in zip(eqn.outvars, inner[0].outvars):
                if not _is_drop(ov) and not _is_literal(iv):
                    env[ov] = _resolve(child, iv)
            return
        for j in inner:  # unknown call shape: fresh regions inside
            self.walk_jaxpr(j, {})

    def _scan(self, eqn, env: dict) -> None:
        p = eqn.params
        body = p["jaxpr"].jaxpr
        n_c, n_k = int(p["num_consts"]), int(p["num_carry"])
        length = int(p["length"])
        const_refs = [_resolve(env, v) for v in eqn.invars[:n_c]]
        carry_refs = [_resolve(env, v) for v in eqn.invars[n_c:n_c + n_k]]
        xs_refs = [_resolve(env, v) for v in eqn.invars[n_c + n_k:]]
        ys_outs = eqn.outvars[n_k:]
        ys_refs = [_resolve(env, v) if not _is_drop(v) else None
                   for v in ys_outs]
        order = range(length - 1, -1, -1) if p.get("reverse") \
            else range(length)
        for i in order:
            child: dict = {}
            for bv, ref in zip(body.invars[:n_c], const_refs):
                if not _is_drop(bv):
                    child[bv] = ref
            for bv, ref in zip(body.invars[n_c:n_c + n_k], carry_refs):
                if not _is_drop(bv):
                    child[bv] = ref
            for bv, ref in zip(body.invars[n_c + n_k:], xs_refs):
                if not _is_drop(bv):
                    child[bv] = ("slice", ref, i, length)
            # pre-seed outputs: the body's y writes land in slice i of the
            # stacked output region; the carry ping-pongs in place
            for bv, ref in zip(body.outvars[:n_k], carry_refs):
                if not _is_drop(bv) and not _is_literal(bv) \
                        and bv not in child:
                    child[bv] = ref
            for bv, ref in zip(body.outvars[n_k:], ys_refs):
                if ref is not None and not _is_drop(bv) \
                        and not _is_literal(bv) and bv not in child:
                    child[bv] = ("slice", ref, i, length)
            self.walk_jaxpr(body, child)
            carry_refs = [
                ref if _is_drop(bv) or _is_literal(bv)
                else _resolve(child, bv)
                for bv, ref in zip(body.outvars[:n_k], carry_refs)
            ]
        for ov, ref in zip(eqn.outvars[:n_k], carry_refs):
            if not _is_drop(ov):
                env[ov] = ref

    def _wants_stream(self, eqn) -> bool:
        if not eqn.outvars or _is_drop(eqn.outvars[0]):
            return False
        if eqn_flops(eqn) <= 0.0:
            return False
        moved = sum(_elems(v.aval) for v in eqn.invars
                    if not _is_literal(v))
        moved += sum(_elems(v.aval) for v in eqn.outvars)
        return moved >= self.stream_min_elems

    def _maybe_alias(self, eqn, env: dict) -> None:
        """Transparent elementwise: output aliases a same-size input."""
        if eqn.primitive.name not in _TRANSPARENT or not eqn.outvars:
            return
        ov = eqn.outvars[0]
        if _is_drop(ov):
            return
        n = _elems(ov.aval)
        for iv in eqn.invars:
            if not _is_literal(iv) and _elems(iv.aval) == n:
                env[ov] = _resolve(env, iv)
                return


def capture_model(fn, args, *, name: str = "model",
                  stream_min_elems: int = STREAM_MIN_ELEMS) -> ModelCapture:
    """Trace ``fn`` over ``args`` and capture its whole-step DMA schedule.

    ``args`` are concrete arrays or ``jax.ShapeDtypeStruct`` placeholders
    (abstract tracing only — no TPU, no compilation, no real weights).
    Keyword-style steps can be adapted with a lambda.  Returns the ops in
    program order plus whole-jaxpr counted FLOPs; ``ModelCapture.walk``
    yields the concatenated word-address stream.
    """
    import jax

    from repro import obs

    with obs.span("capture.model.trace", model=name):
        closed = jax.make_jaxpr(fn)(*args)
    with obs.span("capture.model.walk_jaxpr", model=name):
        walker = _Walker(stream_min_elems)
        walker.walk_jaxpr(closed.jaxpr, {})
    obs.count("capture.model.captures")
    obs.count("capture.model.ops", len(walker.ops))
    return ModelCapture(
        name=name, ops=tuple(walker.ops),
        flops=count_flops(closed.jaxpr),
        footprint_words=walker.alloc.cursor)
