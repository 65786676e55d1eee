"""Pallas TPU kernel: the inner-layer probe over whole heavy-bucket slabs.

For every (outer table ``l``, query ``q``) pair the stratified layer probes
the ``L_in`` inner tables of one heavy bucket ``h[l, q]``: find the query's
inner key in each sorted row and keep up to ``c_in`` indices of its run.
XLA lowers that probe, vmapped, to gathers of single scalars. Here each
grid step instead DMAs the bucket's whole ``(L_in, P)`` key slab and index
slab into VMEM (the block index comes from the scalar-prefetched ``h``) and
works on them with vector ops (DESIGN.md §3):

* ``lo = count(keys < k)`` and ``n = count(keys == k)`` per row, lane
  reductions in place of the two binary searches (equal on sorted rows);
* the window ``idx[lo : lo + c_in]`` is a per-row left rotation by ``lo``,
  built from one static lane rotation per bit of ``lo`` (a barrel shifter),
  then masked to -1 past ``n``.

Keys arrive as uint32 and are compared as int32 with the sign bit flipped,
which keeps their unsigned order. Grid: (L, Q), queries fastest, so
consecutive steps that map to the same slab (pairs of one table whose
buckets share a registry slot) skip its re-fetch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SIGN = -(1 << 31)  # int32 sign bit: flipping it maps uint32 order onto int32


def _probe_kernel(
    h_ref,  # (L * Q,) int32 in SMEM — heavy slot per (l, q), scalar-prefetched
    k_ref,  # (1, L_in, 1) int32 — the query's inner keys, sign-flipped
    keys_ref,  # (1, 1, L_in, P) uint32 — the slab's sorted inner keys
    idx_ref,  # (1, 1, L_in, P) int32 — dataset indices aligned with the keys
    out_ref,  # (1, 1, L_in, c_in) int32
    *,
    c_in: int,
):
    del h_ref  # consumed by the index maps
    keys = jax.lax.bitcast_convert_type(keys_ref[0, 0], jnp.int32) ^ _SIGN
    k = k_ref[0]  # (L_in, 1)
    lo = jnp.sum(jnp.where(keys < k, 1.0, 0.0), axis=1, keepdims=True)
    n = jnp.sum(jnp.where(keys == k, 1.0, 0.0), axis=1, keepdims=True)
    lo, n = lo.astype(jnp.int32), n.astype(jnp.int32)  # exact: P < 2**24
    x = idx_ref[0, 0]
    p = x.shape[1]
    # rows with lo == P have n == 0 and are masked whole, so the bits of
    # P - 1 cover every shift that reaches the output
    for b in range((p - 1).bit_length()):
        step = 1 << b
        x = jnp.where((lo & step) != 0, pltpu.roll(x, p - step, 1), x)
    j = jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], c_in), 1)
    out_ref[0, 0] = jnp.where(j < n, x[:, :c_in], -1)


@functools.partial(jax.jit, static_argnames=("c_in", "interpret"))
def inner_probe_pallas(
    h: jax.Array,  # (L * Q,) int32, row-major over (l, q)
    keys_q: jax.Array,  # (Q, L_in, 1) int32, sign-flipped
    inner_keys: jax.Array,  # (L, H, L_in, P) uint32
    inner_idx: jax.Array,  # (L, H, L_in, P) int32
    *,
    c_in: int,
    interpret: bool = True,
) -> jax.Array:
    """-> ``(L, Q, L_in, c_in)`` int32 windows, -1 past each run's end."""
    l_out, _, l_in, p = inner_keys.shape
    q_n = keys_q.shape[0]
    slab = pl.BlockSpec(
        (1, 1, l_in, p), lambda t, q, h_ref: (t, h_ref[t * q_n + q], 0, 0)
    )
    return pl.pallas_call(
        functools.partial(_probe_kernel, c_in=c_in),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(l_out, q_n),
            in_specs=[
                pl.BlockSpec((1, l_in, 1), lambda t, q, h_ref: (q, 0, 0)),
                slab,
                slab,
            ],
            out_specs=pl.BlockSpec(
                (1, 1, l_in, c_in), lambda t, q, h_ref: (t, q, 0, 0)
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((l_out, q_n, l_in, c_in), jnp.int32),
        interpret=interpret,
    )(h, keys_q, inner_keys, inner_idx)
