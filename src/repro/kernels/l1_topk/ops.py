"""jit'd public wrapper for the l1_topk kernel (padding + interpret policy).

Serves the *staged* pipeline's top-k stage (backends without a fused tail)
and standalone distance work; on the pallas backend the query hot path
runs stages 3-5 as the ``kernels/query_fused`` megakernel instead, whose
single-pass tile loop descends from this kernel's schedule (DESIGN.md §4).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import blocking
from repro.kernels.l1_topk.l1_topk import l1_pallas


def _blocked(q, cands, mask, *, b_blk, c_blk, interpret):
    """Pad to the launch blocks and hand the kernel its layout: queries
    ``(B, D_PAD, 1)``, candidates feature-major ``(B, D_PAD, C)``, the mask
    as int32. Block sizes default per execution mode: compiled Mosaic
    streams (8, 512)-wide candidate tiles (shrunk to the covering power of
    two for small C), while interpret mode (CPU/CI) runs the whole batch as
    one grid step, since interpret cost scales with grid steps x padded
    elements."""
    b, c0, _ = cands.shape
    if b_blk is None:
        # interpret: one grid step over the whole batch — per-step block
        # slicing is a real copy there, not a VMEM window
        b_blk = blocking.round_up(b, blocking.SUBLANE) if interpret else 8
    if c_blk is None:
        c_blk = (
            blocking.round_up(c0, 32)
            if interpret
            else blocking.clamp_pow2(c0, 512, lo=blocking.LANE)
        )
    else:
        c_blk = blocking.clamp_pow2(c0, c_blk, lo=32 if interpret else blocking.LANE)
    b_blk = blocking.clamp_sublane(b, b_blk)
    q = blocking.pad_axis(q.astype(jnp.float32), 1, blocking.SUBLANE)
    cands = blocking.pad_axis(cands.astype(jnp.float32), 2, blocking.SUBLANE)
    q = blocking.pad_axis(q, 0, b_blk)
    cands = blocking.pad_axis(blocking.pad_axis(cands, 0, b_blk), 1, c_blk)
    mask = blocking.pad_axis(
        blocking.pad_axis(mask, 0, b_blk, value=False), 1, c_blk, value=False
    )
    operands = (q[:, :, None], jnp.swapaxes(cands, 1, 2), mask.astype(jnp.int32))
    return operands, dict(b_blk=b_blk, c_blk=c_blk, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("k", "b_blk", "c_blk", "interpret"))
def l1_topk(
    q: jax.Array,  # (B, d)
    cands: jax.Array,  # (B, C, d)
    mask: jax.Array,  # (B, C) bool
    *,
    k: int,
    b_blk: int | None = None,
    c_blk: int | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Masked L1 top-k via the Pallas kernel; output sorted ascending.

    Returns (dists (B, k), positions-into-C (B, k)); inf/-1 where fewer than
    k valid candidates exist. Explicit block arguments override the
    per-mode defaults of ``_blocked``. ``interpret=None`` resolves to the
    platform default (auto-off on real TPU — DESIGN.md §6).
    """
    b, c0, _ = cands.shape
    operands, launch = _blocked(
        q, cands, mask, b_blk=b_blk, c_blk=c_blk,
        interpret=blocking.resolve_interpret(interpret),
    )
    dist, pos = l1_pallas(*operands, k=k, **launch)
    # kernel output is already sorted ascending (single-pass stable merge)
    dist, pos = dist[:b], pos[:b]
    return dist, jnp.where(jnp.isfinite(dist) & (pos < c0), pos, -1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def l1_dist(
    q: jax.Array,  # (B, d)
    cands: jax.Array,  # (B, C, d)
    mask: jax.Array,  # (B, C) bool
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """Masked L1 distances (B, C) via the same kernel (inf where masked)."""
    b, c0, _ = cands.shape
    operands, launch = _blocked(
        q, cands, mask, b_blk=None, c_blk=None,
        interpret=blocking.resolve_interpret(interpret),
    )
    (dist,) = l1_pallas(*operands, k=None, **launch)
    return dist[:b, :c0]
