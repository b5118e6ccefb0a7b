"""Capture hook: MoE dispatch launch geometry as a :class:`GridCapture`.

Per-thread modeling: expert-parallel serving shards the *token batch*
across cores, so a thread's capture is its own ``n_tokens`` slice with
thread-private random top-1 expert assignments over the **shared** expert
weight table (the same shared-table choice as ``token_gather``).  The rng
draws the assignments, the hook sorts them (the kernel contract), and the
Pallas revisiting optimization turns each sorted expert run into exactly
one weight-tile fetch — so the captured DMA stream directly encodes the
tokens-per-expert ratio that decides whether dispatch is weight-traffic
bound (few tokens per expert: the expert table streams through the
hierarchy every batch) or activation bound (long runs amortize the tile).

Geometry comes from the kernel: the default path traces ``kernel.py``'s
``PrefetchScalarGridSpec`` launch and walks its jaxpr with the concrete
sorted (token, expert) vectors as scalar-prefetch values;
``path="mirror"`` keeps the jax-free mirrored geometry (differentially
stream-identical).
"""

from __future__ import annotations

import numpy as np

from repro.capture.grid import GridCapture, OperandSpec
from repro.capture.jaxpr import (capture_path, elems_per_word,
                                from_jaxpr, memoized)

__all__ = ["capture", "dispatch_flops"]


def dispatch_flops(*, n_tokens: int, d: int, f: int) -> float:
    """Arithmetic ops of one dispatch: a [1, d] x [d, f] GEMM per token."""
    return n_tokens * 2.0 * d * f


def capture(*, n_tokens: int, d: int, f: int, n_experts: int,
            rng: np.random.Generator, expert_ids: np.ndarray | None = None,
            path: str = "auto") -> GridCapture:
    """Per-thread geometry: dispatch ``n_tokens`` over ``n_experts``.

    ``expert_ids`` overrides the rng assignment draw with an explicit
    per-token expert list (the serving scenarios feed traffic-shaped
    routing through here); the hook still sorts it (the kernel contract)
    and still draws the token permutation from ``rng``.
    """
    if d % 128 or f % 128:
        raise ValueError(f"d {d} / f {f} must be multiples of 128 (lanes)")
    if expert_ids is not None:
        eid = np.asarray(expert_ids, dtype=np.int64)
        if eid.ndim != 1 or eid.size != n_tokens:
            raise ValueError(f"expert_ids must be [{n_tokens}] (n_tokens), "
                             f"got shape {eid.shape}")
        if eid.size and (eid.min() < 0 or eid.max() >= n_experts):
            raise ValueError(f"expert_ids entries must be in [0, {n_experts})")
        eid = np.sort(eid)
    else:
        eid = np.sort(rng.integers(0, n_experts, size=n_tokens, dtype=np.int64))
    # Token order: the sorted permutation of a thread-private batch.  The
    # permutation (not arange) matters: the x-gather and y-scatter rows
    # must be irregular the way a real routed batch is.
    tok = rng.permutation(n_tokens).astype(np.int64)
    flops = dispatch_flops(n_tokens=n_tokens, d=d, f=f)
    if capture_path(path) == "jaxpr":
        return memoized(
            ("moe_dispatch", n_tokens, d, f, n_experts,
             tok.tobytes(), eid.tobytes()),
            lambda: _traced(n_tokens, d, f, n_experts, tok, eid))
    return _mirror(n_tokens, d, f, n_experts, tok, eid, flops)


def _traced(n_tokens: int, d: int, f: int, n_experts: int,
            tok: np.ndarray, eid: np.ndarray) -> GridCapture:
    # flops=None: counted off the kernel jaxpr — the per-token [1,d]x[d,f]
    # GEMM dot_general counts to exactly dispatch_flops(), which the
    # jax-free mirror below keeps as its formula.
    import jax
    import jax.numpy as jnp

    from .kernel import moe_dispatch_sorted

    x = jax.ShapeDtypeStruct((n_tokens, d), jnp.float32)
    w = jax.ShapeDtypeStruct((n_experts, d, f), jnp.float32)
    ids = jax.ShapeDtypeStruct((n_tokens,), jnp.int32)
    return from_jaxpr(
        moe_dispatch_sorted, (x, w, ids, ids),
        scalar_values=(tok.astype(np.int32), eid.astype(np.int32)),
        flops=None, name="moe_dispatch")


def _mirror(n_tokens: int, d: int, f: int, n_experts: int,
            tok: np.ndarray, eid: np.ndarray, flops: float) -> GridCapture:
    """Jax-free fallback: the launch geometry as plain data (activation
    and output rows through the kernel's ``[tokens, 1, width]`` views)."""

    def prefetch(name: str) -> OperandSpec:
        return OperandSpec(
            name=name, role="in", shape=(n_tokens,),
            block_shape=(n_tokens,), index_map=lambda i: (0,),
            elems_per_word=elems_per_word(np.int32, n_tokens),
        )

    return GridCapture(
        name="moe_dispatch",
        grid=(n_tokens,),
        operands=(
            prefetch("tok"),
            prefetch("eid"),
            OperandSpec(
                name="x", role="in", shape=(n_tokens, 1, d),
                block_shape=(1, 1, d),
                index_map=lambda i, _t=tok: (int(_t[i]), 0, 0),
            ),
            OperandSpec(
                name="w", role="in", shape=(n_experts, d, f),
                block_shape=(1, d, f),
                index_map=lambda i, _e=eid: (int(_e[i]), 0, 0),
            ),
            OperandSpec(
                name="y", role="out", shape=(n_tokens, 1, f),
                block_shape=(1, 1, f),
                index_map=lambda i, _t=tok: (int(_t[i]), 0, 0),
            ),
        ),
        flops=flops,
    )
