"""Static-shape LSH hash tables.

The paper's buckets are linked lists of pointers into shared memory; the
TPU-native equivalent is a CSR-style layout: per table we keep the point
indices sorted by bucket key. A bucket is then a contiguous [lo, hi) slice
found by two binary searches (vectorized searchsorted). See DESIGN.md §8.2.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

PAD_KEY = jnp.uint32(0xFFFFFFFF)


class TableSet(NamedTuple):
    sorted_keys: jax.Array  # (L, n) uint32, each row ascending
    sorted_idx: jax.Array  # (L, n) int32, dataset indices aligned with keys


class HeavyBuckets(NamedTuple):
    """Top-H_max buckets per table with population > alpha*n (paper §2)."""

    keys: jax.Array  # (L, H) uint32 bucket key (PAD_KEY where invalid)
    start: jax.Array  # (L, H) int32 offset into the table's sorted arrays
    size: jax.Array  # (L, H) int32 true population
    valid: jax.Array  # (L, H) bool
    overflowed: jax.Array  # (L,) int32 count of heavy buckets beyond H budget


def build_tables(keys: jax.Array) -> TableSet:
    """keys: (L, n) uint32 -> sorted tables."""
    n = keys.shape[1]
    idx = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), keys.shape)
    sorted_keys, sorted_idx = jax.vmap(
        lambda k, i: jax.lax.sort((k, i), num_keys=1)
    )(keys, idx)
    return TableSet(sorted_keys, sorted_idx)


def _heavy_one_table(
    sorted_keys: jax.Array, alpha_n: jax.Array, h_max: int
) -> tuple[jax.Array, ...]:
    n = sorted_keys.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_keys[1:] != sorted_keys[:-1]]
    )
    seg_id = jnp.cumsum(is_start.astype(jnp.int32)) - 1  # (n,)
    sizes = jax.ops.segment_sum(jnp.ones(n, jnp.int32), seg_id, num_segments=n)
    starts = jax.ops.segment_min(
        jnp.where(is_start, pos, n).astype(jnp.int32), seg_id, num_segments=n
    )
    # Rows may carry PAD_KEY tail entries (capacity-padded streaming tables,
    # DESIGN.md §9) — the pad segment must never be classified heavy.
    seg_key = sorted_keys[jnp.clip(starts, 0, n - 1)]
    heavy_sizes = jnp.where((sizes > alpha_n) & (seg_key != PAD_KEY), sizes, 0)
    if n < h_max:  # fewer segments than registry slots: pad with empties
        heavy_sizes = jnp.pad(heavy_sizes, (0, h_max - n))
    top_sizes, top_segs = jax.lax.top_k(heavy_sizes, h_max)
    valid = top_sizes > 0
    top_start = jnp.where(valid, starts[top_segs], 0)
    top_key = jnp.where(valid, sorted_keys[top_start], PAD_KEY)
    overflow = jnp.sum((heavy_sizes > 0).astype(jnp.int32)) - jnp.sum(
        valid.astype(jnp.int32)
    )
    return top_key, top_start.astype(jnp.int32), top_sizes, valid, overflow


def find_heavy(tables: TableSet, alpha_n: jax.Array, h_max: int) -> HeavyBuckets:
    """Top-``h_max`` buckets per table with population > ``alpha_n``.

    The registry the stratified (inner) layer indexes — and the heat signal
    replication-aware routing places replicas by (DESIGN.md §10). The
    streaming PAD segment is never classified heavy (DESIGN.md §9.1).
    """
    key, start, size, valid, overflow = jax.vmap(
        lambda sk: _heavy_one_table(sk, alpha_n, h_max)
    )(tables.sorted_keys)
    return HeavyBuckets(key, start, size, valid, overflow)


def find_heavy_streamed(
    tables: TableSet, alpha_n: jax.Array, h_max: int
) -> HeavyBuckets:
    """:func:`find_heavy` computed one table at a time (``lax.map``).

    Bit-identical to the vmapped form, but its segment-scan transients are
    (n,)-sized instead of (L, n)-sized — the registry pass of the
    memory-bounded chunked builder (DESIGN.md §13), where the all-tables
    scan would otherwise dominate peak build memory.
    """
    key, start, size, valid, overflow = jax.lax.map(
        lambda sk: _heavy_one_table(sk, alpha_n, h_max), tables.sorted_keys
    )
    return HeavyBuckets(key, start, size, valid, overflow)


def bucket_range(sorted_keys_row: jax.Array, key: jax.Array) -> tuple[jax.Array, jax.Array]:
    """[lo, hi) slice of one table's sorted arrays holding ``key``."""
    lo = jnp.searchsorted(sorted_keys_row, key, side="left")
    hi = jnp.searchsorted(sorted_keys_row, key, side="right")
    return lo.astype(jnp.int32), hi.astype(jnp.int32)


def gather_bucket(
    sorted_idx_row: jax.Array, lo: jax.Array, hi: jax.Array, budget: int
) -> jax.Array:
    """Up to ``budget`` dataset indices from [lo, hi); -1 where masked."""
    offs = lo + jnp.arange(budget, dtype=jnp.int32)
    ok = offs < hi
    idx = sorted_idx_row[jnp.clip(offs, 0, sorted_idx_row.shape[0] - 1)]
    return jnp.where(ok, idx, -1)
