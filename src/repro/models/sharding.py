"""Logical-axis sharding: MaxText-style logical -> physical resolution.

Every parameter and activation carries a tuple of *logical* axis names;
:func:`logical_to_spec` maps them to mesh axes through a rules table,
dropping any mapping whose dimension is not divisible by the mesh-axis size
(e.g. 40 attention heads cannot split across a 16-way model axis — the
resolver falls back to replication for that dimension instead of failing,
which is what lets one rules table serve all ten architectures).

Default rules implement: batch data-parallel over ("pod", "data"), tensor
parallel over "model" (heads / ffn / vocab / experts), FSDP weight sharding
over ("pod", "data") on the embed dimension.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "DEFAULT_RULES",
    "INFER_RULES",
    "logical_to_spec",
    "named_sharding",
    "tree_shardings",
    "activate",
    "constrain",
    "Axes",
]

Axes = tuple[str | None, ...]

# logical axis -> mesh axis (or tuple of mesh axes) or None (replicate)
DEFAULT_RULES: dict[str, Any] = {
    "batch": ("pod", "data"),
    "tokens": ("pod", "data"),     # flattened batch*seq (MoE routing)
    "seq": None,
    "embed": None,
    "fsdp": ("pod", "data"),       # weight sharding over the data axes
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",
    "qkv": "model",                # fused head*dim projection columns
    "ffn": "model",
    "experts": "model",
    "expert_ffn": None,
    "kv_lora": None,
    "q_lora": None,
    "ssm_inner": "model",
    "ssm_heads": "model",
    "ssm_state": None,
    "layers": None,
    "conv": None,
    # Sequence parallelism on the inter-layer residual stream (Megatron-SP):
    # the layer-scan's saved activations shard over the model axis on the
    # sequence dim; XLA inserts all-gather at q/k/v projections and
    # reduce-scatter after the output projections.  Cuts per-chip saved
    # activations by model_shards at equal collective bytes vs pure-TP.
    "seq_residual": "model",
}

# Inference: weights stay resident, sharded over the model axis only — no
# per-step FSDP all-gather (serving reuses weights across thousands of
# decode steps, so gathering per step would be absurd).  KV caches shard
# their *length* dimension over the model axis (flash-decode style: each
# chip attends over its cache shard, XLA all-reduces the softmax stats) —
# this is what lets 32k-context x large-batch caches fit HBM even when
# kv_heads < model shards.
INFER_RULES: dict[str, Any] = dict(DEFAULT_RULES, fsdp=None,
                                   cache_len="model")
# Training/prefill never shard cache length (written in one shot).
DEFAULT_RULES["cache_len"] = None


def _mesh_axes_size(mesh: Mesh, axes: Any) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    size = 1
    for a in axes:
        size *= mesh.shape.get(a, 1)
    return size


def logical_to_spec(
    mesh: Mesh,
    logical: Sequence[str | None],
    shape: Sequence[int] | None = None,
    rules: dict[str, Any] | None = None,
) -> P:
    """Resolve logical axes to a PartitionSpec, enforcing divisibility."""
    rules = rules or DEFAULT_RULES
    used: set[str] = set()
    out: list[Any] = []
    for i, name in enumerate(logical):
        target = rules.get(name) if name else None
        if target is None:
            out.append(None)
            continue
        targets = (target,) if isinstance(target, str) else tuple(target)
        # Drop mesh axes that are absent/trivial in this mesh or already used.
        targets = tuple(t for t in targets
                        if mesh.shape.get(t, 1) > 1 and t not in used)
        if not targets:
            out.append(None)
            continue
        size = _mesh_axes_size(mesh, targets)
        if shape is not None and shape[i] % size != 0:
            # Try a shrinking prefix of the target axes.
            while targets and shape[i] % _mesh_axes_size(mesh, targets) != 0:
                targets = targets[:-1]
            if not targets:
                out.append(None)
                continue
        used.update(targets)
        out.append(targets[0] if len(targets) == 1 else targets)
    # Trim trailing Nones for tidiness.
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def named_sharding(
    mesh: Mesh,
    logical: Sequence[str | None],
    shape: Sequence[int] | None = None,
    rules: dict[str, Any] | None = None,
) -> NamedSharding:
    return NamedSharding(mesh, logical_to_spec(mesh, logical, shape, rules))


# --------------------------------------------------------------------------
# Trace-time sharding constraints (hints for the SPMD partitioner — avoids
# "involuntary full rematerialization" on gathers/scatters in MoE/embedding
# paths).  Model code calls ``constrain(x, "tokens", None)``; it is a no-op
# unless a (mesh, rules) context is active during tracing.
# --------------------------------------------------------------------------
_TLS = threading.local()


@contextlib.contextmanager
def activate(mesh: Mesh, rules: dict[str, Any] | None = None):
    prev = getattr(_TLS, "ctx", None)
    _TLS.ctx = (mesh, rules or DEFAULT_RULES)
    try:
        yield
    finally:
        _TLS.ctx = prev


def constrain(x: jax.Array, *logical: str | None) -> jax.Array:
    ctx = getattr(_TLS, "ctx", None)
    if ctx is None:
        return x
    mesh, rules = ctx
    spec = logical_to_spec(mesh, logical, x.shape, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def tree_shardings(
    mesh: Mesh,
    tree_struct: Any,
    logical_tree: Any,
    rules: dict[str, Any] | None = None,
) -> Any:
    """Map a pytree of logical-axes tuples + a matching pytree of
    ShapeDtypeStructs (or arrays) to NamedShardings."""

    def resolve(logical: Axes, leaf: Any) -> NamedSharding:
        shape = getattr(leaf, "shape", None)
        return named_sharding(mesh, logical, shape, rules)

    return jax.tree.map(
        resolve, logical_tree, tree_struct,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x
        ),
    )
