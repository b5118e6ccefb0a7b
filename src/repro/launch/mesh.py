"""Device meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state.
"""

from __future__ import annotations

import jax

__all__ = ["make_local_mesh", "make_abstract_mesh"]


def make_abstract_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Device-free mesh for resolving shardings (tests, planning)."""
    from jax.sharding import AbstractMesh

    return AbstractMesh(tuple(shape), tuple(axes))


def make_local_mesh(model_axis: int = 1):
    """Small mesh over whatever devices exist (tests / CPU smoke)."""
    n = len(jax.devices())
    assert n % model_axis == 0
    return jax.make_mesh((n // model_axis, model_axis), ("data", "model"))
