"""Pure-jnp oracle for the inner_probe kernel: the element takes the
staged gather runs without it (``pipeline._gather_one_table``)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import tables


def inner_probe_ref(
    inner_keys: jax.Array,  # (L, H, L_in, P) uint32
    inner_idx: jax.Array,  # (L, H, L_in, P) int32
    h: jax.Array,  # (L, Q) int32
    keys: jax.Array,  # (Q, L_in) uint32
    *,
    c_in: int,
) -> jax.Array:
    """-> ``(L, Q, L_in * c_in)``: per (table, query, inner table) the
    binary-searched run of the query's key, ``c_in`` indices, -1 padded."""
    l_in = inner_keys.shape[2]

    def one(t, hq, kq):
        def row(li):
            lo, hi = tables.bucket_range(inner_keys[t, hq, li], kq[li])
            return tables.gather_bucket(inner_idx[t, hq, li], lo, hi, c_in)

        return jax.vmap(row)(jnp.arange(l_in)).reshape(-1)

    per_table = jax.vmap(one, in_axes=(None, 0, 0))  # over queries
    return jax.vmap(per_table, in_axes=(0, 0, None))(
        jnp.arange(inner_keys.shape[0]), h, keys
    )
