"""Compile every Pallas kernel family for a described TPU v5e chip.

Interpret mode (what the rest of the kernel tests run) cannot see what
the chip's compiler refuses: Mosaic's (8, 128) tiling rule, primitives it
does not lower (``cumsum``, ``cumprod``), fast-memory limits.  Each test
here lowers one kernel at one captured suite geometry for a chip that is
described, not attached, and checks that the compiled program holds the
kernel (``tpu_custom_call``).  Nothing runs, so nothing here says
anything about results or times.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU compiler's library,
and the test workers all import this file.
"""

import os

import pytest

from repro.capture import CAPTURED_KERNELS

jax = pytest.importorskip("jax")
jnp = pytest.importorskip("jax.numpy")


@pytest.fixture(scope="module")
def one_chip():
    """A single-device sharding on a described v5e chip; compiles run with
    the persistent cache off (a program compiled for a described chip is
    written to the cache but cannot be read back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _geometry(name: str) -> dict:
    return dict(next(s for s in CAPTURED_KERNELS if s.name == name).geometry)


def _stream(sds):
    from repro.kernels.stream import stream_triad

    n = _geometry("pal.stream.triad.2MiB")["n_elems"]
    a = sds((n,), jnp.float32)
    return stream_triad, (a, a, sds((), jnp.float32))


def _gather(sds):
    from repro.kernels.token_gather import gather_rows

    g = _geometry("pal.gather.64kx128")
    return gather_rows, (sds((g["n_rows"], g["d"]), jnp.float32),
                         sds((g["m"],), jnp.int32))


def _flash(sds):
    from repro.kernels.flash_attention import flash_attention

    g = _geometry("pal.flashattn.d128.kv2k")
    q = sds((1, g["sq"], 1, g["d"]), jnp.float32)
    kv = sds((1, g["sk"], 1, g["d"]), jnp.float32)
    return (lambda q, k, v: flash_attention(q, k, v, causal=False),
            (q, kv, kv))


def _paged(sds):
    from repro.kernels.paged_kv_decode import paged_decode_attention

    g = _geometry("pal.pagedkv.gqa8.p32")
    pages = sds((g["n_pages"], g["page"], g["d"]), jnp.float32)
    return paged_decode_attention, (sds((g["h"], g["d"]), jnp.float32),
                                    pages, pages,
                                    sds((g["n_active"],), jnp.int32))


def _moe(sds):
    from repro.kernels.moe_dispatch import moe_dispatch_sorted

    g = _geometry("pal.moe.warm.8e")
    t = g["n_tokens"]
    ids = sds((t,), jnp.int32)
    return moe_dispatch_sorted, (
        sds((t, g["d"]), jnp.float32),
        sds((g["n_experts"], g["d"], g["f"]), jnp.float32), ids, ids)


def _ssm_ema(sds):
    from repro.kernels.ssm_scan import ssm_ema_scan

    g = _geometry("pal.ssm.ema.1k.d128")
    x = sds((g["seq_len"], g["d"]), jnp.float32)
    return (lambda x, dt, gate: ssm_ema_scan(x, dt, gate, chunk=g["chunk"]),
            (x, x, x))


def _ssm_expand(sds):
    from repro.kernels.ssm_scan import ssm_chunked_scan

    g = _geometry("pal.ssm.expand.512.d128")
    x = sds((g["seq_len"], g["d"]), jnp.float32)
    bc = sds((g["seq_len"], g["n"]), jnp.float32)
    return (lambda x, dt, b, c: ssm_chunked_scan(x, dt, b, c,
                                                 chunk=g["chunk"]),
            (x, x, bc, bc))


KERNELS = {"stream": _stream, "token_gather": _gather,
           "flash_attention": _flash, "paged_kv_decode": _paged,
           "moe_dispatch": _moe, "ssm_ema": _ssm_ema,
           "ssm_expand": _ssm_expand}


@pytest.mark.parametrize("family", list(KERNELS))
def test_kernel_compiles_for_v5e(family, one_chip):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = KERNELS[family](sds)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
