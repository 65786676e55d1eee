"""Pallas TPU kernel: blocked masked L1 distance + running top-k.

This is the paper's measured bottleneck ("the linear search over the
candidates"): for each query, scan its gathered candidate vectors and keep
the K nearest under l1. The TPU formulation (DESIGN.md §4):

* candidates stream through VMEM in (B_BLK, D_PAD, C_BLK) tiles — the
  wrapper hands them over feature-major, so the candidate axis rides the
  128 lanes and the feature dim only pads to the 8-row sublane multiple
  (zero padding is l1-neutral),
* distances are VPU reductions over sublanes (no MXU — l1 is not a
  contraction), landing lane-major as a (B_BLK, C_BLK) block,
* selection is :func:`smallest_k`: ``k`` rounds of a float min-reduce plus
  an int32 min-reduce over the candidate positions. It merges the (B_BLK, K)
  running best that lives in the output refs with the block's distances
  without concatenating them, and it breaks equal distances toward the
  lower global position — the §6 backend-contract tie rule, bit-exact.

The outputs are therefore already sorted ascending; the wrapper never
re-sorts.

Grid: (B_blocks, C_blocks); C is the fastest-varying dimension so the
running best for one query block persists across its candidate stream.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def smallest_k(sources, k: int):
    """The ``k`` smallest values over several ``(values, positions)`` sources.

    Every source is ``(B, C_i)`` and reduces along its last axis; positions
    are global and unique across sources, and equal values resolve toward
    the lower position (the §6 tie rule ``lax.top_k`` gives on
    ``-values``). Returns ``(vals, pos)``, each ``(B, k)`` ascending. Slots
    past the live entries hold ``+inf`` and a position larger than any real
    one.

    Written in what Mosaic lowers: min-reductions, compares and selects, no
    sort, gather or concatenation.
    """
    big = jnp.iinfo(jnp.int32).max
    out_shape = (sources[0][0].shape[0], k)
    slot = jax.lax.broadcasted_iota(jnp.int32, out_shape, 1)
    vals = jnp.full(out_shape, jnp.inf, jnp.float32)
    pos = jnp.full(out_shape, big, jnp.int32)
    live = [jnp.ones(v.shape, jnp.bool_) for v, _ in sources]
    for r in range(k):
        cur = [jnp.where(lv, v, jnp.inf) for lv, (v, _) in zip(live, sources)]
        m = functools.reduce(
            jnp.minimum, [jnp.min(c, axis=1, keepdims=True) for c in cur]
        )
        p = functools.reduce(
            jnp.minimum,
            [
                jnp.min(jnp.where(lv & (c == m), ps, big), axis=1, keepdims=True)
                for lv, c, (_, ps) in zip(live, cur, sources)
            ],
        )
        live = [lv & (ps != p) for lv, (_, ps) in zip(live, sources)]
        vals = jnp.where(slot == r, m, vals)
        pos = jnp.where(slot == r, p, pos)
    return vals, pos


def _l1_kernel(
    q_ref,  # (B_BLK, D_PAD, 1) f32
    c_ref,  # (B_BLK, D_PAD, C_BLK) f32, feature-major
    m_ref,  # (B_BLK, C_BLK) int32 mask (1 = valid)
    *out_refs,
    k: int | None,
    c_blk: int,
):
    """Masked L1 distances of one block; with ``k`` merged into the running
    best ``(dist_ref, pos_ref)`` (B_BLK, K), without it written out whole."""
    ci = pl.program_id(1)

    d = jnp.sum(jnp.abs(c_ref[...] - q_ref[...]), axis=1)  # (B, C) lane-major
    d = jnp.where(m_ref[...] != 0, d, jnp.inf)
    if k is None:
        out_refs[0][...] = d
        return
    dist_ref, pos_ref = out_refs
    pos = ci * c_blk + jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)

    @pl.when(ci == 0)
    def _init():
        dist_ref[...] = jnp.full(dist_ref.shape, jnp.inf, jnp.float32)
        pos_ref[...] = jnp.full(pos_ref.shape, -1, jnp.int32)

    # running best first: its positions all precede this block's, so the
    # position tie-break keeps the lowest global position on equal distances
    dist_ref[...], pos_ref[...] = smallest_k(
        [(dist_ref[...], pos_ref[...]), (d, pos)], k
    )


@functools.partial(
    jax.jit, static_argnames=("k", "b_blk", "c_blk", "interpret")
)
def l1_pallas(
    q: jax.Array,  # (B, D_PAD, 1) f32
    cands: jax.Array,  # (B, D_PAD, C) f32, feature-major
    mask: jax.Array,  # (B, C) int32
    *,
    k: int | None,
    b_blk: int = 8,
    c_blk: int = 512,
    interpret: bool = True,
) -> tuple[jax.Array, ...]:
    """-> ``(dist (B, k), pos (B, k))`` ascending with ``k``; the masked
    distances ``(dist (B, C),)`` (inf where masked) without it."""
    b, d_pad, c = cands.shape
    assert b % b_blk == 0 and c % c_blk == 0, (b, c, b_blk, c_blk)
    grid = (b // b_blk, c // c_blk)
    if k is None:
        out_specs = [pl.BlockSpec((b_blk, c_blk), lambda bi, ci: (bi, ci))]
        out_shape = [jax.ShapeDtypeStruct((b, c), jnp.float32)]
    else:
        out_specs = [pl.BlockSpec((b_blk, k), lambda bi, ci: (bi, 0))] * 2
        out_shape = [
            jax.ShapeDtypeStruct((b, k), jnp.float32),
            jax.ShapeDtypeStruct((b, k), jnp.int32),
        ]
    return tuple(pl.pallas_call(
        functools.partial(_l1_kernel, k=k, c_blk=c_blk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((b_blk, d_pad, 1), lambda bi, ci: (bi, 0, 0)),
            pl.BlockSpec((b_blk, d_pad, c_blk), lambda bi, ci: (bi, 0, ci)),
            pl.BlockSpec((b_blk, c_blk), lambda bi, ci: (bi, ci)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(q, cands, mask))
