"""MoE token dispatch + expert FFN as a Pallas TPU kernel (scalar prefetch).

The end-to-end expert-parallel dispatch of a mixture-of-experts layer,
fused into one grid: the host sorts the token ids by their expert
assignment, scalar-prefetches both the sorted token order and the sorted
expert ids into SMEM, and the grid walks the sorted token stream.  Per
step ``i`` the BlockSpec index maps steer three DMAs:

- ``x[tok[i]]``   — gather the token's activation row (irregular);
- ``w[eid[i]]``   — the expert's weight tile.  Because tokens are sorted,
  consecutive steps usually name the *same* expert, and the Pallas
  revisiting optimization keeps the tile VMEM-resident across the whole
  run — the weight is re-fetched once per expert, not once per token.
  That run-length reuse is the entire performance story of MoE dispatch,
  and the capture path reproduces it exactly;
- ``y[tok[i]]``   — scatter the FFN output row back to token order.

The kernel body is just the per-token expert GEMM ``y = x @ w``.  The
activation and output rows are viewed as ``[T, 1, D]`` / ``[T, 1, F]``
with ``(None, 1, ·)`` blocks, so each block's last two dims equal the
array's and meet the TPU's (8, 128) tiling rule; the views are free
reshapes and leave the DMA word stream unchanged.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["moe_dispatch_sorted", "moe_dispatch"]


def _kernel(tok_ref, eid_ref, x_ref, w_ref, y_ref):
    del tok_ref, eid_ref  # consumed by the index maps
    y_ref[...] = jnp.dot(x_ref[...], w_ref[0],
                         preferred_element_type=jnp.float32
                         ).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_dispatch_sorted(x, w, tok, eid, *, interpret: bool = False):
    """x: [T, D]; w: [E, D, F]; tok, eid: [T] int32 (expert-sorted).

    ``tok`` is a permutation of ``range(T)`` such that ``eid`` (the expert
    of ``x[tok[i]]``) is non-decreasing.  Returns y: [T, F] in original
    token order (``y[tok[i]] = x[tok[i]] @ w[eid[i]]``).
    """
    t, d = x.shape
    _, _, f = w.shape

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(t,),
        in_specs=[
            pl.BlockSpec((None, 1, d), lambda i, tok, eid: (tok[i], 0, 0)),
            pl.BlockSpec((1, d, f), lambda i, tok, eid: (eid[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, f),
                               lambda i, tok, eid: (tok[i], 0, 0)),
    )
    y = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, 1, f), x.dtype),
        interpret=interpret,
    )(tok.astype(jnp.int32), eid.astype(jnp.int32), x.reshape(t, 1, d), w)
    return y.reshape(t, f)


def moe_dispatch(x, w, expert_ids, *, interpret: bool = False):
    """Unsorted entry: sorts tokens by expert, then dispatches.

    ``expert_ids``: [T] int32 expert assignment per token (top-1 routing).
    """
    order = jnp.argsort(expert_ids, stable=True)
    return moe_dispatch_sorted(x, w, order, expert_ids[order],
                               interpret=interpret)
