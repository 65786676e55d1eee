"""Production mesh builders (functions, not constants — importing this module
never touches jax device state)."""
from __future__ import annotations

import jax


def _auto(n: int) -> tuple:
    return (jax.sharding.AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips with a leading pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_local_mesh(data: int = 1, model: int = 1):
    """Small host-device mesh for tests/examples (requires enough devices)."""
    return jax.make_mesh(
        (data, model), ("data", "model"), axis_types=_auto(2)
    )


def make_replicated_mesh(rep: int = 1, data: int = 1, model: int = 1):
    """Mesh with a leading replica axis for routed DSLSH queries.

    ``rep * data * model`` devices: each (data, model) cell exists ``rep``
    times, and ``distributed.mesh_query`` row-shards the query batch over
    the ``rep`` axis before its two-stage merge (DESIGN.md §10)."""
    return jax.make_mesh(
        (rep, data, model), ("rep", "data", "model"), axis_types=_auto(3)
    )
