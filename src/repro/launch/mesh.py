"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before any jax initialization).

Single pod: 16 x 16 = 256 chips, axes ("data", "model").
Multi-pod:  2 x 16 x 16 = 512 chips, axes ("pod", "data", "model") — the
"pod" axis carries data parallelism across pods (gradient all-reduce over
DCN/ICI) and joins "data" for FSDP weight sharding.
"""

from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_local_mesh", "make_abstract_mesh"]


def make_abstract_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Device-free mesh for resolving shardings (tests, planning)."""
    from jax.sharding import AbstractMesh

    return AbstractMesh(tuple(shape), tuple(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_local_mesh(model_axis: int = 1):
    """Small mesh over whatever devices exist (tests / CPU smoke)."""
    n = len(jax.devices())
    assert n % model_axis == 0
    return jax.make_mesh((n // model_axis, model_axis), ("data", "model"))
