"""Capture hook: token-gather launch geometry as a :class:`GridCapture`.

Per-thread modeling: each core gathers its own slice of the global index
stream, so a thread's capture is simply ``m`` gathered rows with
thread-private random indices over the *shared* table (the synthetic
``irregular`` family makes the same modeling choice).  ``rng`` supplies the
indices, so the trace is deterministic per (workload, seed).

Geometry comes from the kernel: the default path traces ``kernel.py``'s
``PrefetchScalarGridSpec`` launch and walks its jaxpr, passing the concrete
index vector as the scalar-prefetch value so the data-dependent
``table[idx[i]]`` index map resolves to the same per-step block indices the
hardware DMA engine would follow.  ``path="mirror"`` keeps the jax-free
mirrored geometry (differentially stream-identical).
"""

from __future__ import annotations

import numpy as np

from repro.capture.grid import GridCapture, OperandSpec
from repro.capture.jaxpr import (capture_path, elems_per_word,
                                from_jaxpr, memoized)

__all__ = ["capture"]


def capture(n_rows: int, d: int, m: int, *,
            rng: np.random.Generator, path: str = "auto") -> GridCapture:
    """Per-thread geometry: gather ``m`` of ``n_rows`` rows of width ``d``."""
    if d % 128:
        raise ValueError(f"d {d} must be a multiple of 128 (lane dim)")
    idx = rng.integers(0, n_rows, size=m, dtype=np.int64)
    if capture_path(path) == "jaxpr":
        return memoized(
            ("gather", n_rows, d, m, idx.tobytes()),
            lambda: _traced(n_rows, d, m, idx))
    return _mirror(n_rows, d, m, idx)


def _traced(n_rows: int, d: int, m: int, idx: np.ndarray) -> GridCapture:
    import jax
    import jax.numpy as jnp

    from .kernel import gather_rows

    table = jax.ShapeDtypeStruct((n_rows, d), jnp.float32)
    idx_sds = jax.ShapeDtypeStruct((m,), jnp.int32)
    # flops=None: counted off the kernel jaxpr — a pure row copy has no
    # float arithmetic, so the counter lands on the mirror's literal 0.0.
    return from_jaxpr(
        gather_rows, (table, idx_sds),
        scalar_values=(idx.astype(np.int32),),
        flops=None, name="token_gather")


def _mirror(n_rows: int, d: int, m: int, idx: np.ndarray) -> GridCapture:
    """Jax-free fallback: the launch geometry as plain data — idx is
    scalar-prefetched once (constant index map), then each grid step ``i``
    DMAs row ``table[idx[i]]`` in and output row ``i`` out, both through
    the kernel's ``[rows, 1, d]`` views."""
    return GridCapture(
        name="token_gather",
        grid=(m,),
        operands=(
            # int32 indices, scalar-prefetched once before the grid runs
            # (same word-packing rule as the jaxpr path, so odd-length
            # index vectors stay byte-identical across paths).
            OperandSpec(
                name="idx", role="in", shape=(m,), block_shape=(m,),
                index_map=lambda i: (0,),
                elems_per_word=elems_per_word(np.int32, m),
            ),
            OperandSpec(
                name="table", role="in", shape=(n_rows, 1, d),
                block_shape=(1, 1, d),
                index_map=lambda i, _idx=idx: (int(_idx[i]), 0, 0),
            ),
            OperandSpec(
                name="out", role="out", shape=(m, 1, d),
                block_shape=(1, 1, d), index_map=lambda i: (i, 0, 0),
            ),
        ),
        flops=0.0,  # pure data movement
    )
