"""Chunked SSM (selective-state-space) scan as Pallas TPU kernels.

Two kernels share one structure — the time axis is cut into VMEM-sized
chunks, the grid walks the chunks sequentially, and the recurrent state
lives in VMEM scratch across the whole grid (it never touches HBM):

- :func:`ssm_ema_scan` — gated diagonal recurrence
  ``h_t = dt_t * h_{t-1} + x_t``, ``y_t = g_t * h_t`` (a first-order
  selective gate; the memory behaviour of the scan is four pure streams);
- :func:`ssm_chunked_scan` — state-expanded selective scan (Mamba-2-style
  chunked algorithm): ``h_t = dt_t * h_{t-1} + B_t (outer) x_t``,
  ``y_t = C_t . h_t`` with ``h`` an [n, d] state.  Within a chunk the
  recurrence is evaluated in closed form: with the running decay product
  ``P_t = prod_{u<=t} dt_u``,

      y = P * (tril(C @ B^T) @ (x / P) + C @ h_in)
      h_out = P[-1] * (h_in + B^T @ (x / P))

  which turns the sequential scan into two chunk-local matmuls — the MXU
  formulation actually used on TPUs.  ``dt`` must stay in (0, 1]; the
  closed form divides by the decay product, so extremely small per-chunk
  products (dt << 0.9 with large chunks) lose precision — callers pick
  the chunk length accordingly.

Both kernels take the running sums along time the same MXU way: Mosaic
lowers neither ``cumsum`` nor ``cumprod``, so a prefix sum is a matmul
with the lower-triangular ones matrix, ``tril(1) @ v``, and the decay
product is ``P = exp(tril(1) @ log(dt))``.  These matmuls run at full f32
precision; they add ``2 * C * C * D`` operations per chunk each, which
the captured FLOP formula (the recurrence's own arithmetic) leaves out.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssm_ema_scan", "ssm_chunked_scan"]


def _prefix_sum(v):
    """Running sum of ``v`` [C, D] along axis 0, as one MXU matmul."""
    c = v.shape[0]
    tril = jnp.tril(jnp.ones((c, c), jnp.float32))
    return jax.lax.dot(tril, v, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)


def _decay_product(dt):
    """Running product of ``dt`` [C, D] in (0, 1] along axis 0."""
    return jnp.exp(_prefix_sum(jnp.log(dt.astype(jnp.float32))))


def _ema_kernel(x_ref, dt_ref, g_ref, y_ref, h_scr):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    p = _decay_product(dt_ref[...])                             # [C, D]
    z = _prefix_sum(x_ref[...].astype(jnp.float32) / p)
    h = p * (h_scr[...] + z)                                    # [C, D]
    y_ref[...] = (g_ref[...].astype(jnp.float32) * h).astype(y_ref.dtype)
    h_scr[...] = h[-1:]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssm_ema_scan(x, dt, g, *, chunk: int = 128, interpret: bool = False):
    """x, dt, g: [T, D] -> y: [T, D] with y_t = g_t * (dt_t h_{t-1} + x_t)."""
    t, d = x.shape
    assert t % chunk == 0, (t, chunk)
    grid = (t // chunk,)
    spec = pl.BlockSpec((chunk, d), lambda i: (i, 0))
    return pl.pallas_call(
        _ema_kernel,
        grid=grid,
        in_specs=[spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((t, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((1, d), jnp.float32)],
        interpret=interpret,
    )(x, dt, g)


def _chunked_kernel(x_ref, dt_ref, b_ref, c_ref, y_ref, h_scr):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    p = _decay_product(dt_ref[...])                             # [C, D]
    xb = x_ref[...].astype(jnp.float32) / p                     # [C, D]
    bc = b_ref[...].astype(jnp.float32)                         # [C, N]
    cc = c_ref[...].astype(jnp.float32)                         # [C, N]
    h0 = h_scr[...]                                             # [N, D]
    gram = jax.lax.dot_general(
        cc, bc, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                     # [C, C]
    mask = jnp.tril(jnp.ones_like(gram))
    y = p * (jax.lax.dot_general(
        gram * mask, xb, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
        + jax.lax.dot_general(
            cc, h0, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32))
    y_ref[...] = y.astype(y_ref.dtype)
    h_scr[...] = p[-1:] * (h0 + jax.lax.dot_general(
        bc, xb, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssm_chunked_scan(x, dt, b, c, *, chunk: int = 128,
                     interpret: bool = False):
    """x, dt: [T, D]; b, c: [T, N] -> y: [T, D].

    State-expanded recurrence ``h_t = dt_t h_{t-1} + b_t (outer) x_t``,
    ``y_t = c_t . h_t``, evaluated chunk-by-chunk in closed form.
    """
    t, d = x.shape
    _, n = b.shape
    assert t % chunk == 0, (t, chunk)
    grid = (t // chunk,)
    xd = pl.BlockSpec((chunk, d), lambda i: (i, 0))
    bn = pl.BlockSpec((chunk, n), lambda i: (i, 0))
    return pl.pallas_call(
        _chunked_kernel,
        grid=grid,
        in_specs=[xd, xd, bn, bn],
        out_specs=xd,
        out_shape=jax.ShapeDtypeStruct((t, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((n, d), jnp.float32)],
        interpret=interpret,
    )(x, dt, b, c)
