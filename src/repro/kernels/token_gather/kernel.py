"""Irregular row gather as a Pallas TPU kernel (scalar-prefetch DMA).

The TPU-idiomatic answer to DAMOV's irregular-access classes (1a-irregular
/ 1b pointer-chase): there is no cache hierarchy to thrash and no
pointer-chasing latency to hide with a prefetcher — instead, the *indices*
are scalar-prefetched into SMEM ahead of the grid, and each grid step's
BlockSpec index_map redirects the automatic HBM->VMEM DMA to the gathered
row block.  The hardware overlaps the next block's DMA with the current
block's copy-out, so irregular reads run at streaming bandwidth as long as
rows are >= one VMEM tile — exactly the "extract MLP with regular engines"
adaptation DAMOV §3.3.1 calls for (MoE token dispatch and paged-KV reads
are this kernel).

Rows are gathered one at a time.  The table is viewed as ``[N, 1, D]``
with ``(None, 1, D)`` blocks, so each block's last two dims equal the
array's and meet the TPU's (8, 128) tiling rule; a ``(1, D)`` block of an
``[N, D]`` table does not.  The view is a free reshape and leaves the
DMA word stream unchanged.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["gather_rows"]


def _kernel(idx_ref, table_ref, o_ref):
    del idx_ref  # consumed by the index_map
    o_ref[...] = table_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_rows(table, idx, *, interpret: bool = False):
    """table: [N, D] (D a multiple of 128); idx: [M] int32 -> [M, D].

    Each output row i is the DMA copy table[idx[i]]; idx lives in SMEM via
    scalar prefetch and steers the BlockSpec index_map.
    """
    n, d = table.shape
    m = idx.shape[0]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m,),
        in_specs=[
            pl.BlockSpec((None, 1, d),
                         lambda i, idx_ref: (idx_ref[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, d), lambda i, idx_ref: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, 1, d), table.dtype),
        interpret=interpret,
    )(idx.astype(jnp.int32), table.reshape(n, 1, d))
    return out.reshape(m, d)
