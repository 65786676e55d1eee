"""Ambient-mesh context + graceful sharding constraints.

Model code calls ``constrain(x, 'batch', 'seq', None)`` with *logical* axis
names; the ambient :class:`ShardingRules` maps them to mesh axes. Constraints
degrade gracefully: with no ambient mesh (single-device smoke tests) they are
no-ops, and any logical dim not divisible by its mesh-axis size drops that
axis (e.g. hymba's 25 attention heads on a 16-way tensor axis).
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Logical-axis -> mesh-axis mapping for the (pod, [rep,] data, model)
    mesh. ``rep`` (replicated DSLSH cells, DESIGN.md §10) joins the batch
    axes — replicas split query/batch rows — but never the parameter axes:
    replicas hold identical state by construction."""

    batch: tuple = ("pod", "rep", "data")  # data parallel (+ replica split)
    fsdp: tuple = ("pod", "data")  # parameter/optimizer sharding (ZeRO)
    tensor: tuple = ("model",)  # tensor parallel (heads / ffn / vocab / experts)
    seq: tuple = ("model",)  # sequence parallel (activations between blocks)
    expert: tuple = ("model",)  # expert parallel

    def axes(self, logical: str | None) -> tuple:
        if logical is None:
            return (None,)
        return getattr(self, logical)


_STATE: dict[str, Any] = {"mesh": None, "rules": ShardingRules()}


@contextlib.contextmanager
def use_mesh(mesh: Mesh | None, rules: ShardingRules | None = None):
    old = dict(_STATE)
    _STATE["mesh"] = mesh
    if rules is not None:
        _STATE["rules"] = rules
    try:
        if mesh is not None:
            with mesh:
                yield
        else:
            yield
    finally:
        _STATE.update(old)


def get_mesh() -> Mesh | None:
    return _STATE["mesh"]


def get_rules() -> ShardingRules:
    return _STATE["rules"]


def axis_size(mesh: Mesh, axes: tuple) -> int:
    return math.prod(mesh.shape[a] for a in axes if a is not None and a in mesh.shape)


def logical_to_spec(mesh: Mesh, rules: ShardingRules, logical: tuple, shape: tuple) -> P:
    """Resolve logical axes to a PartitionSpec, dropping non-divisible dims."""
    spec = []
    used: set = set()
    for dim, name in enumerate(logical):
        axes = tuple(
            a
            for a in rules.axes(name)
            if a is not None and a in mesh.shape and a not in used
        )
        if not axes:
            spec.append(None)
            continue
        size = math.prod(mesh.shape[a] for a in axes)
        if shape[dim] % size != 0:
            # try progressively shorter prefixes of the axis tuple
            while axes and shape[dim] % math.prod(mesh.shape[a] for a in axes) != 0:
                axes = axes[:-1]
        if axes:
            used.update(axes)
            spec.append(axes if len(axes) > 1 else axes[0])
        else:
            spec.append(None)
    return P(*spec)


def constrain(x: jax.Array, *logical: str | None) -> jax.Array:
    """with_sharding_constraint by logical axis names; no-op without a mesh."""
    mesh = get_mesh()
    if mesh is None:
        return x
    assert len(logical) == x.ndim, (logical, x.shape)
    spec = logical_to_spec(mesh, get_rules(), tuple(logical), x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def spec_for(shape: tuple, *logical: str | None) -> P:
    """PartitionSpec for in/out_shardings of jit (dry-run uses this)."""
    mesh = get_mesh()
    if mesh is None:
        return P()
    return logical_to_spec(mesh, get_rules(), tuple(logical), shape)


def mesh_axis_size(*axes_names: str) -> int:
    mesh = get_mesh()
    if mesh is None:
        return 1
    return math.prod(mesh.shape.get(a, 1) for a in axes_names)
