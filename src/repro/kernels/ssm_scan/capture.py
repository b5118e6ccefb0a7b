"""Capture hook: chunked-SSM-scan launch geometry as a :class:`GridCapture`.

Per-thread modeling: sequence-parallel SSM layers shard the time axis
across cores (chunk boundaries carry tiny [n, d] states, negligible next
to the streams), so a thread's capture is the chunk walk over its
``seq_len / cores`` slice, at least one chunk — the same strong-scaling
convention as STREAM.  The recurrent state lives in VMEM scratch and
never appears in the HBM trace; what the hierarchy sees is the pure
chunk-granular stream of x/dt (+ gate, or +B/C) blocks in and y blocks
out.

Geometry comes from the kernel: the default path traces ``kernel.py``'s
``pallas_call`` over the per-thread slice and walks its jaxpr;
``path="mirror"`` keeps the jax-free mirrored geometry (differentially
stream-identical).
"""

from __future__ import annotations

from repro.capture.grid import GridCapture, OperandSpec
from repro.capture.jaxpr import capture_path, from_jaxpr, memoized

__all__ = ["capture", "scan_flops", "SSM_OPS"]

SSM_OPS = ("ema", "expand")


def scan_flops(op: str, *, seq_len: int, d: int, n: int, chunk: int) -> float:
    """Arithmetic ops of one scan over ``seq_len`` steps."""
    n_chunks = seq_len // chunk
    if op == "ema":
        # decay product + div + running sum + state mul/add + gate
        return 6.0 * seq_len * d
    # chunk closed form: gram [C,C,N] + masked matmul [C,C,D] + two
    # state contractions [C,N,D] + the vector epilogue
    return n_chunks * (2.0 * chunk * chunk * (n + d)
                       + 4.0 * chunk * n * d + 5.0 * chunk * d)


def capture(op: str, *, seq_len: int, d: int, n: int = 128,
            chunk: int = 128, cores: int = 1,
            path: str = "auto") -> GridCapture:
    """Per-thread geometry for one SSM scan over ``seq_len / cores``."""
    if op not in SSM_OPS:
        raise ValueError(f"unknown ssm op {op!r}; expected {SSM_OPS}")
    if seq_len % chunk:
        raise ValueError(f"seq_len {seq_len} not a multiple of chunk {chunk}")
    if d % 128:
        raise ValueError(f"d {d} must be a multiple of 128 (lane dim)")
    t_thread = max(chunk, seq_len // max(1, cores) // chunk * chunk)
    # Kept on both capture paths (the mirror has no jaxpr to count).  The
    # formula is the recurrence's own arithmetic: the kernel's prefix
    # sums run as tril(1) @ v MXU matmuls (2*C*C*d each per chunk), which
    # it leaves out.  With those added, the jaxpr counter reproduces the
    # ema formula exactly and the expand closed form within ~0.5% (it
    # folds the chunk-boundary mask ops into 5*C*d) — pinned by
    # tests/test_capture_model.py.
    flops = scan_flops(op, seq_len=t_thread, d=d, n=n, chunk=chunk)
    if capture_path(path) == "jaxpr":
        return memoized(
            ("ssm_scan", op, t_thread, d, n, chunk),
            lambda: _traced(op, t_thread, d, n, chunk, flops))
    return _mirror(op, t_thread, d, n, chunk, flops)


def _traced(op: str, t: int, d: int, n: int, chunk: int,
            flops: float) -> GridCapture:
    import jax
    import jax.numpy as jnp

    from . import kernel as K

    xd = jax.ShapeDtypeStruct((t, d), jnp.float32)
    if op == "ema":
        fn = lambda x, dt, g: K.ssm_ema_scan(x, dt, g, chunk=chunk)
        args = (xd, xd, xd)
    else:
        bn = jax.ShapeDtypeStruct((t, n), jnp.float32)
        fn = lambda x, dt, b, c: K.ssm_chunked_scan(x, dt, b, c, chunk=chunk)
        args = (xd, xd, bn, bn)
    return from_jaxpr(fn, args, flops=flops, name=f"ssm_{op}")


def _mirror(op: str, t: int, d: int, n: int, chunk: int,
            flops: float) -> GridCapture:
    """Jax-free fallback: the launch geometry as plain data."""

    def stream(name: str, role: str, width: int) -> OperandSpec:
        return OperandSpec(
            name=name, role=role, shape=(t, width),
            block_shape=(chunk, width), index_map=lambda i: (i, 0),
        )

    if op == "ema":
        operands = (stream("x", "in", d), stream("dt", "in", d),
                    stream("g", "in", d), stream("y", "out", d))
    else:
        operands = (stream("x", "in", d), stream("dt", "in", d),
                    stream("b", "in", n), stream("c", "in", n),
                    stream("y", "out", d))
    return GridCapture(
        name=f"ssm_{op}",
        grid=(t // chunk,),
        operands=operands,
        flops=flops,
    )
