"""Capture hook: STREAM kernel launch geometry as a :class:`GridCapture`.

The hook's only real job is the *per-thread modeling choice*: strong
scaling follows the kernel's natural parallelization (the row-tile grid is
partitioned across cores, so a thread's capture is the launch over its
``n_elems / cores`` slice, at least one tile).  The launch geometry itself
comes from the kernel: the default path traces ``kernel.py``'s
``pallas_call`` and walks its jaxpr (:func:`repro.capture.jaxpr.from_jaxpr`
— zero mirroring); ``path="mirror"`` keeps the original hand-mirrored
geometry as the jax-free fallback, differentially guaranteed
stream-identical by ``tests/test_capture_jaxpr.py``.
"""

from __future__ import annotations

from repro.capture.grid import GridCapture, OperandSpec
from repro.capture.jaxpr import capture_path, from_jaxpr, memoized

__all__ = ["capture", "bytes_moved", "STREAM_OPS", "LANES",
           "DEFAULT_BLOCK_ROWS"]

# Mirrors repro.kernels.stream.kernel (kept jax-free on purpose).
LANES = 128
DEFAULT_BLOCK_ROWS = 512

# op -> (input operand names, arithmetic ops per output element)
STREAM_OPS: dict[str, tuple[tuple[str, ...], float]] = {
    "copy": (("a",), 0.0),
    "scale": (("q", "a"), 1.0),
    "add": (("a", "b"), 1.0),
    "triad": (("q", "a", "b"), 2.0),
}


def bytes_moved(op: str, n_elems: int, itemsize: int) -> int:
    """HBM bytes per invocation (reads + writes), STREAM convention."""
    arrays = sum(1 for name in STREAM_OPS[op][0] if name != "q")
    return (arrays + 1) * n_elems * itemsize


def capture(op: str, n_elems: int, *, cores: int = 1,
            block_rows: int = DEFAULT_BLOCK_ROWS,
            path: str = "auto") -> GridCapture:
    """Per-thread launch geometry for one STREAM op over ``n_elems``."""
    if op not in STREAM_OPS:
        raise ValueError(f"unknown stream op {op!r}; expected {set(STREAM_OPS)}")
    _, ops_per_elem = STREAM_OPS[op]
    tile_elems = block_rows * LANES
    if n_elems % tile_elems:
        raise ValueError(f"n_elems {n_elems} not a multiple of {tile_elems}")
    n_thread = max(tile_elems, n_elems // max(1, cores) // tile_elems * tile_elems)
    flops = ops_per_elem * n_thread
    if capture_path(path) == "jaxpr":
        return memoized(
            ("stream", op, n_thread, block_rows),
            lambda: _traced(op, n_thread, block_rows))
    return _mirror(op, n_thread, block_rows, flops)


def _traced(op: str, n_thread: int, block_rows: int) -> GridCapture:
    """Trace the real kernel's ``pallas_call`` over the per-thread slice.

    ``flops=None``: counted off the kernel jaxpr's arithmetic eqns
    (:mod:`repro.capture.flops`) — exactly the per-element op mix the
    mirror's ``STREAM_OPS`` table hand-codes, so the two paths stay
    counter-identical without a duplicated formula here.
    """
    import jax
    import jax.numpy as jnp

    from . import kernel as K

    a = jax.ShapeDtypeStruct((n_thread,), jnp.float32)
    q = jnp.float32(1.5)
    fns = {
        "copy": (K.stream_copy, (a,)),
        "scale": (K.stream_scale, (a, q)),
        "add": (K.stream_add, (a, a)),
        "triad": (K.stream_triad, (a, a, q)),
    }
    fn, args = fns[op]
    return from_jaxpr(
        lambda *xs: fn(*xs, block_rows=block_rows), args,
        flops=None, name=f"stream_{op}")


def _mirror(op: str, n_thread: int, block_rows: int,
            flops: float) -> GridCapture:
    """Jax-free fallback: the ``pallas_call`` geometry as plain data."""
    inputs, _ = STREAM_OPS[op]
    rows = n_thread // LANES
    grid = (rows // block_rows,)

    def arr(name: str, role: str) -> OperandSpec:
        return OperandSpec(
            name=name, role=role, shape=(rows, LANES),
            block_shape=(block_rows, LANES), index_map=lambda i: (i, 0),
        )

    operands: list[OperandSpec] = []
    for name in inputs:
        if name == "q":  # broadcast scalar: fetched once (index map constant)
            operands.append(OperandSpec(
                name="q", role="in", shape=(1,), block_shape=(1,),
                index_map=lambda i: (0,), elems_per_word=1,
            ))
        else:
            operands.append(arr(name, "in"))
    operands.append(arr("o", "out"))

    return GridCapture(
        name=f"stream_{op}",
        grid=grid,
        operands=tuple(operands),
        flops=flops,
    )
