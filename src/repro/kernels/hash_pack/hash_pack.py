"""Pallas TPU kernel: fused LSH signature computation.

``bits = (x @ proj + bias) > 0`` packed into uint32 words, so m-bit
signatures never hit HBM as full float rows. The projection runs on the MXU
((T_BLK, D_PAD) @ (D_PAD, M_TOTAL)); sign extraction is a VPU op and the
32-way packing two small selector contractions (``_pack_words``), both on
the resident tile. Serves both LSH families (DESIGN.md §4):
sign random projection (cosine) directly, and l1 bit-sampling via a one-hot
selector matrix with bias = -thresholds.

The column axis carries *all tables of a family at once*: table ``t`` owns
columns ``[t*m_stride, (t+1)*m_stride)`` with its real ``m`` bits at the
front of the stride. One launch therefore hashes a batch against the whole
family (one MXU contraction) instead of a per-table swarm of small calls;
``m_stride == M_TOTAL`` recovers the single-table form.

Grid: (T_blocks,). proj/bias stay VMEM-resident across the grid — callers
chunk the table axis when L*m_stride*D_PAD floats would not fit VMEM
(see ops._family_pack).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _pack_words(bits):
    """Pack a (T_BLK, M_TOTAL) bit matrix into (T_BLK, M_TOTAL//32) words.

    Two small MXU contractions against power-of-two selector matrices give
    each word's low and high 16-bit halves; both operands are exact in
    bf16 and every half-word sum stays below 2^16, so the result is exact
    at any matmul precision. Mosaic lowers neither the lane-splitting
    reshape a per-word reduction needs nor any reduction over unsigned
    integers, so the halves are joined in int32 and bitcast.
    """
    m_total = bits.shape[1]
    w = m_total // 32
    row = jax.lax.broadcasted_iota(jnp.int32, (m_total, w), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (m_total, w), 1)
    bit = row % 32
    own = row // 32 == col
    b = bits.astype(jnp.float32)

    def half(lo_bit):
        sel = own & (bit >= lo_bit) & (bit < lo_bit + 16)
        scale = jnp.left_shift(jnp.int32(1), bit - lo_bit).astype(jnp.float32)
        part = jnp.dot(
            b, jnp.where(sel, scale, 0.0), preferred_element_type=jnp.float32
        )
        return part.astype(jnp.int32)

    words = half(0) | jnp.left_shift(half(16), 16)
    return jax.lax.bitcast_convert_type(words, jnp.uint32)


def _project(x, p):
    """``x @ p`` at full f32 precision: a one-hot selector must reproduce
    ``x[dim]`` exactly, and sign-projection bits must match the reference
    ``hashing.signature_bits`` (also full precision) on every platform."""
    return jnp.dot(
        x, p, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _hash_pack_kernel(x_ref, p_ref, b_ref, o_ref, *, m: int, m_stride: int):
    x = x_ref[...]  # (T_BLK, D_PAD)
    p = p_ref[...]  # (D_PAD, M_TOTAL)
    bias = b_ref[...]  # (1, M_TOTAL)
    s = _project(x, p) + bias  # MXU
    t_blk, m_total = s.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (t_blk, m_total), 1)
    bits = (s > 0.0) & (col % m_stride < m)  # zero out padded bit positions
    o_ref[...] = _pack_words(bits)


def _hash_pack_margins_kernel(
    x_ref, p_ref, b_ref, o_ref, g_ref, *, m: int, m_stride: int
):
    """``_hash_pack_kernel`` + per-bit quantizer margins in the same launch.

    For the one-hot bit-sampling formulation ``s = x[dim] - thr`` exactly
    (a one-hot dot reproduces the gathered coordinate bit-for-bit), so
    ``|s|`` is the multiprobe margin — emitting it here folds multiprobe
    key generation into the fused all-tables hash launch instead of
    re-gathering ``x`` afterwards (DESIGN.md §4). Padded columns carry
    ``bias = -inf`` so their margins are ``+inf`` (never flip candidates).
    """
    x = x_ref[...]
    p = p_ref[...]
    bias = b_ref[...]
    s = _project(x, p) + bias  # MXU
    t_blk, m_total = s.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (t_blk, m_total), 1)
    bits = (s > 0.0) & (col % m_stride < m)
    o_ref[...] = _pack_words(bits)
    g_ref[...] = jnp.abs(s)


def _bitsample_gather_kernel(x_ref, dims_ref, thr_ref, o_ref):
    """Interpret-mode bit-sampling: fused gather + compare + pack.

    The one-hot matmul in ``_hash_pack_kernel`` is the MXU formulation —
    off-TPU it buys nothing and costs a (D_PAD, M_TOTAL) contraction, so
    the interpret path samples coordinates directly (a lane gather Mosaic
    does not support, which is fine: this kernel only runs interpreted).
    Padded columns carry ``thr = +inf`` so their bits pack to zero.
    """
    x = x_ref[...]  # (T_BLK, D_PAD)
    g = x[:, dims_ref[...][0]]  # (T_BLK, M_TOTAL) coordinate gather
    o_ref[...] = _pack_words(g > thr_ref[...])


def _bitsample_gather_margins_kernel(x_ref, dims_ref, thr_ref, o_ref, g_ref):
    """Interpret-mode bit-sampling words + multiprobe margins, one launch.

    The gathered coordinates are already resident, so the margin
    ``|x[dim] - thr|`` is one extra VPU op; padded columns carry
    ``thr = +inf`` and so emit ``+inf`` margins (never flip candidates).
    """
    x = x_ref[...]
    thr = thr_ref[...]
    g = x[:, dims_ref[...][0]]
    o_ref[...] = _pack_words(g > thr)
    g_ref[...] = jnp.abs(g - thr)


@functools.partial(jax.jit, static_argnames=("t_blk",))
def bitsample_gather_pallas(
    x: jax.Array,  # (T, D_PAD) f32, T % t_blk == 0
    dims: jax.Array,  # (1, M_TOTAL) int32 sampled coordinate per column
    thrs: jax.Array,  # (1, M_TOTAL) f32, +inf on padded columns
    *,
    t_blk: int,
) -> jax.Array:
    t = x.shape[0]
    m_total = dims.shape[1]
    assert t % t_blk == 0 and m_total % 32 == 0
    w = m_total // 32
    return pl.pallas_call(
        _bitsample_gather_kernel,
        grid=(t // t_blk,),
        in_specs=[
            pl.BlockSpec((t_blk, x.shape[1]), lambda ti: (ti, 0)),
            pl.BlockSpec((1, m_total), lambda ti: (0, 0)),
            pl.BlockSpec((1, m_total), lambda ti: (0, 0)),
        ],
        out_specs=pl.BlockSpec((t_blk, w), lambda ti: (ti, 0)),
        out_shape=jax.ShapeDtypeStruct((t, w), jnp.uint32),
        interpret=True,
    )(x, dims, thrs)


@functools.partial(jax.jit, static_argnames=("t_blk",))
def bitsample_gather_margins_pallas(
    x: jax.Array,  # (T, D_PAD) f32, T % t_blk == 0
    dims: jax.Array,  # (1, M_TOTAL) int32 sampled coordinate per column
    thrs: jax.Array,  # (1, M_TOTAL) f32, +inf on padded columns
    *,
    t_blk: int,
) -> tuple[jax.Array, jax.Array]:
    """``bitsample_gather_pallas`` + margins: -> ((T, W) words, (T, M_TOTAL))."""
    t = x.shape[0]
    m_total = dims.shape[1]
    assert t % t_blk == 0 and m_total % 32 == 0
    w = m_total // 32
    return pl.pallas_call(
        _bitsample_gather_margins_kernel,
        grid=(t // t_blk,),
        in_specs=[
            pl.BlockSpec((t_blk, x.shape[1]), lambda ti: (ti, 0)),
            pl.BlockSpec((1, m_total), lambda ti: (0, 0)),
            pl.BlockSpec((1, m_total), lambda ti: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((t_blk, w), lambda ti: (ti, 0)),
            pl.BlockSpec((t_blk, m_total), lambda ti: (ti, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, w), jnp.uint32),
            jax.ShapeDtypeStruct((t, m_total), jnp.float32),
        ],
        interpret=True,
    )(x, dims, thrs)


@functools.partial(jax.jit, static_argnames=("m", "m_stride", "t_blk", "interpret"))
def hash_pack_margins_pallas(
    x: jax.Array,  # (T, D_PAD) f32, T % t_blk == 0
    proj: jax.Array,  # (D_PAD, M_TOTAL) f32, M_TOTAL % m_stride == 0
    bias: jax.Array,  # (1, M_TOTAL) f32, -inf on padded columns
    m: int,
    *,
    m_stride: int,
    t_blk: int = 256,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """``hash_pack_pallas`` + margins: -> ((T, W) words, (T, M_TOTAL) |s|)."""
    t, d_pad = x.shape
    m_total = proj.shape[1]
    assert t % t_blk == 0 and m_stride % 32 == 0 and m_total % m_stride == 0
    w = m_total // 32
    return pl.pallas_call(
        functools.partial(_hash_pack_margins_kernel, m=m, m_stride=m_stride),
        grid=(t // t_blk,),
        in_specs=[
            pl.BlockSpec((t_blk, d_pad), lambda ti: (ti, 0)),
            pl.BlockSpec((d_pad, m_total), lambda ti: (0, 0)),
            pl.BlockSpec((1, m_total), lambda ti: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((t_blk, w), lambda ti: (ti, 0)),
            pl.BlockSpec((t_blk, m_total), lambda ti: (ti, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, w), jnp.uint32),
            jax.ShapeDtypeStruct((t, m_total), jnp.float32),
        ],
        interpret=interpret,
    )(x, proj, bias)


@functools.partial(jax.jit, static_argnames=("m", "m_stride", "t_blk", "interpret"))
def hash_pack_pallas(
    x: jax.Array,  # (T, D_PAD) f32, T % t_blk == 0
    proj: jax.Array,  # (D_PAD, M_TOTAL) f32, M_TOTAL % m_stride == 0
    bias: jax.Array,  # (1, M_TOTAL) f32
    m: int,
    *,
    m_stride: int,
    t_blk: int = 256,
    interpret: bool = True,
) -> jax.Array:
    t, d_pad = x.shape
    m_total = proj.shape[1]
    assert t % t_blk == 0 and m_stride % 32 == 0 and m_total % m_stride == 0
    w = m_total // 32
    return pl.pallas_call(
        functools.partial(_hash_pack_kernel, m=m, m_stride=m_stride),
        grid=(t // t_blk,),
        in_specs=[
            pl.BlockSpec((t_blk, d_pad), lambda ti: (ti, 0)),
            pl.BlockSpec((d_pad, m_total), lambda ti: (0, 0)),
            pl.BlockSpec((1, m_total), lambda ti: (0, 0)),
        ],
        out_specs=pl.BlockSpec((t_blk, w), lambda ti: (ti, 0)),
        out_shape=jax.ShapeDtypeStruct((t, w), jnp.uint32),
        interpret=interpret,
    )(x, proj, bias)
