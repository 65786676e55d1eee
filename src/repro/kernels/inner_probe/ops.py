"""jit'd public wrapper for the inner_probe kernel (padding + interpret policy).

Serves the staged gather stage of the compiled pallas backend
(``BackendOps.inner_probe``, DESIGN.md §6): the slab probe of every
(outer table, query) pair's heavy bucket, in place of the element takes
of ``pipeline._gather_one_table`` (DESIGN.md §3).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import tables
from repro.kernels import blocking
from repro.kernels.inner_probe.inner_probe import _SIGN, inner_probe_pallas


@functools.partial(jax.jit, static_argnames=("c_in", "interpret"))
def inner_probe(
    inner_keys: jax.Array,  # (L, H, L_in, P) uint32, each row ascending
    inner_idx: jax.Array,  # (L, H, L_in, P) int32
    h: jax.Array,  # (L, Q) int32 heavy-registry slot per (table, query)
    keys: jax.Array,  # (Q, L_in) uint32 the queries' inner keys
    *,
    c_in: int,
    interpret: bool | None = None,
) -> jax.Array:
    """Inner-layer candidates ``(L, Q, L_in * c_in)`` int32.

    Row ``(l, q)`` holds, for each inner table ``i`` in order, up to
    ``c_in`` indices of ``inner_idx[l, h[l, q], i]`` whose key equals
    ``keys[q, i]``, -1 past the run: exactly ``tables.gather_bucket`` over
    ``tables.bucket_range`` (``ref.inner_probe_ref``). Compiled, the slab's
    lane axis pads to the 128-lane width; rows pad with ``PAD_KEY`` keys
    and -1 indices, which leaves every window as it was.
    """
    interpret = blocking.resolve_interpret(interpret)
    l_out, _, l_in, p = inner_keys.shape
    width = max(p, c_in)
    if not interpret:
        width = blocking.round_up(width, blocking.LANE)
    if width != p:
        inner_keys = blocking.pad_axis(inner_keys, 3, width, value=tables.PAD_KEY)
        inner_idx = blocking.pad_axis(inner_idx, 3, width, value=-1)
    keys_q = jax.lax.bitcast_convert_type(keys, jnp.int32) ^ _SIGN
    out = inner_probe_pallas(
        h.astype(jnp.int32).reshape(-1), keys_q[:, :, None], inner_keys,
        inner_idx, c_in=c_in, interpret=interpret,
    )
    return out.reshape(l_out, keys.shape[0], l_in * c_in)
