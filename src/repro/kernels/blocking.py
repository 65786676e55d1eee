"""Shared padding / blocking / interpret policy for the Pallas kernel ops.

Every kernel wrapper (`l1_topk/ops.py`, `hash_pack/ops.py`,
`flash_attention/ops.py`) needs the same three things, previously
copy-pasted per wrapper:

* right-padding an axis to a tile multiple (`pad_axis`),
* clamping a configured block size down for small inputs so tiny calls
  (streaming inserts, few-query chunks) don't pad to a full block
  (`clamp_sublane` / `clamp_pow2`),
* deciding whether `pallas_call` runs in interpret mode
  (`resolve_interpret`).

The interpret policy (DESIGN.md §6): compiled Mosaic kernels only exist on
real TPUs, so interpret defaults to *on* everywhere else (CPU/GPU test and
CI environments) and *off* on TPU. ``SLSHConfig.interpret`` (threaded
through the pipeline's backend dispatch) or the wrappers' ``interpret=``
argument override the platform default in either direction.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

SUBLANE = 8  # f32 sublane minimum (second-to-last tile dim)
LANE = 128  # lane width (last tile dim)


def round_up(n: int, mult: int) -> int:
    """Smallest multiple of ``mult`` >= ``n``."""
    return -(-n // mult) * mult


def pad_axis(x: jax.Array, axis: int, mult: int, value=0) -> jax.Array:
    """Right-pad ``axis`` of ``x`` to a multiple of ``mult`` with ``value``."""
    rem = (-x.shape[axis]) % mult
    if rem == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, rem)
    return jnp.pad(x, widths, constant_values=value)


def clamp_sublane(n: int, blk: int) -> int:
    """Shrink a row-block to the next sublane multiple covering ``n``.

    Small batches (streaming inserts hash a handful of points at a time)
    then pad only up to the next multiple of 8 instead of a full block."""
    return min(blk, max(SUBLANE, round_up(n, SUBLANE)))


def clamp_pow2(n: int, blk: int, lo: int = SUBLANE) -> int:
    """Shrink a block to the next power of two covering ``n`` (>= ``lo``).

    For blocked dimensions that want power-of-two tiles (grid splits,
    bitonic-friendly widths): ``min(blk, 2^ceil(log2 n))``, floored at
    ``lo``. ``blk`` and ``lo`` must themselves be powers of two."""
    return min(blk, max(lo, 1 << max(0, n - 1).bit_length()))


def resolve_interpret(override: bool | None = None) -> bool:
    """Interpret-mode policy: auto-off on real TPU, on everywhere else."""
    if override is not None:
        return override
    return jax.default_backend() != "tpu"
