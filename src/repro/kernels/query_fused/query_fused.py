"""Pallas megakernel: the query-pipeline tail fused into one launch.

The tail consumes a query chunk's raw candidate tensor and produces the
finished k-NN answer: merge the gather stage's sorted runs into one
ascending row (a bitonic concat-merge network — no general sort), mask
duplicate / padded slots, prefix-sum the survivor mask and compact the
first ``c_comp`` unique indices, gather their data rows, and reduce L1
distances to the top-k (DESIGN.md §4).

The fused launch is the interpret formulation (the off-TPU production +
CI path): one ``pallas_call`` with ``grid=(1,)`` and the whole chunk
resident; ``data`` is handed over in ``pl.ANY`` memory space and
candidate rows are gathered by vectorized indexing straight from the ref,
so the ``(Q, c_comp, d)`` gathered block never materializes as an
intermediate between stages.

Compiled on a TPU, Mosaic refuses the fused body (:data:`XLA_STAGES`). The
f32 tail then runs as the pipeline's staged stages 3-5, whose stage 5 is
the ``l1_topk`` kernel (``core/pipeline.py:_pallas_ops``); the
compressed-payload tail, which has no staged twin, runs as XLA ops around
the ``l1_topk`` kernel's distance mode (:func:`_tail_payload_compiled`).

Every formulation reproduces the §6 lowest-position tie rule: compacted
rows ascend by global index and the top-k prefers earlier positions on
equal distances, exactly like the staged reference tail.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.l1_topk import ops as l1_ops

# Kernel-internal sentinel: a plain int (kernels cannot capture array
# constants), equal to pipeline._IDX_SENTINEL — sorts after any real index.
_SENT = jnp.iinfo(jnp.int32).max

_CUMSUM_BLK = 16  # prefix-sum block: one triangular-matmul tile

# What runs as XLA ops instead of Mosaic on a TPU, each with the refusal
# that keeps it there (reported by chip_smoke.py and CHANGES.md).
XLA_STAGES = (
    "query tail stages 3-4 (run merge, dedup, compaction): the fused body"
    " is refused ('NotImplementedError: Only 2D gather is supported' — its"
    " searchsorted and take_along_axis compaction); the f32 tail runs the"
    " pipeline's staged sort dedup and sort compaction, the payload tail"
    " this module's merge-network dedup and compaction",
    "query tail candidate-row gather: a per-row DMA of a d=30 f32 row is"
    " refused ('Slice shape along dimension 1 must be aligned to tiling"
    " (128), but is 30'), and one-row DMAs of f16/i8 rows are refused"
    " ('... dimension 0 must be aligned to tiling (8), but is 1')",
    "compressed-payload tail: the c_rerank shortlist and the final top-k"
    " are XLA top_k: Mosaic refuses lax.top_k ('Unimplemented primitive in"
    " Pallas TPU lowering: top_k'), and the l1_topk kernel's replacement,"
    " smallest_k, would take c_rerank=128 serial min-reduce rounds for the"
    " shortlist (not measured on the chip)",
)


def merge_sorted_runs(x: jax.Array, run: int) -> jax.Array:
    """Merge each row's ascending length-``run`` runs into one sorted row.

    ``x (Q, C)`` with ``C = R * run`` and R a power of two; every
    ``run``-aligned slice is already ascending (the gather stage emits
    bucket slices in index order, sentinel-padded at the tail). Pairs of
    runs merge as bitonic sequences (ascending ++ reversed-descending), a
    log-depth network of element-wise min/max — O(C log R log C) compares
    but fully vectorized, versus a general sort's larger constant. This is
    the megakernel's stage-3 replacement and is exact: the output is a
    permutation of ``x`` per row, sorted ascending.

    The network runs on the transposed ``(C, Q)`` layout, keeping the query
    axis innermost: its late substages compare stride-``2^j`` element
    pairs, which degenerates to scalar code row-major but stays a dense
    vector op over the whole chunk when each compare spans ``Q`` contiguous
    lanes.
    """
    q_n, c = x.shape
    r, width = c // run, run
    y = x.T.reshape(r, width, q_n)
    while r > 1:
        a = y[0::2]
        b = y[1::2][:, ::-1, :]  # descending half -> bitonic pair
        z = jnp.concatenate([a, b], axis=1)  # (r//2, 2*width, Q)
        width *= 2
        dd = width // 2
        while dd >= 1:  # bitonic merge network, Q innermost
            w = z.reshape(-1, 2, dd, q_n)
            lo = jnp.minimum(w[:, 0], w[:, 1])
            hi = jnp.maximum(w[:, 0], w[:, 1])
            z = jnp.stack([lo, hi], axis=1).reshape(-1, width, q_n)
            dd //= 2
        y = z
        r //= 2
    return y.reshape(c, q_n).T


def _prefix_sum(u: jax.Array) -> jax.Array:
    """Inclusive prefix sum of a 0/1 mask (Q, C) -> int32 (Q, C).

    Where ``C`` tiles by :data:`_CUMSUM_BLK`, runs as two triangular
    matmuls (in-block prefix + block-offset prefix) — MXU/VPU-friendly and
    far cheaper than the serial ``cumsum`` lowering at C ~ thousands; f32
    accumulation is exact for any realistic candidate width (< 2^24).
    """
    q_n, c = u.shape
    if c % _CUMSUM_BLK:
        return jnp.cumsum(u.astype(jnp.int32), axis=-1)
    blk = _CUMSUM_BLK
    nb = c // blk
    u3 = u.reshape(q_n, nb, blk).astype(jnp.float32)
    row = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
    tri = (row <= col).astype(jnp.float32)
    part = jax.lax.dot_general(
        u3, tri, (((2,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Q, nb, blk) in-block inclusive prefix
    sums = part[:, :, -1]
    row2 = jax.lax.broadcasted_iota(jnp.int32, (nb, nb), 0)
    col2 = jax.lax.broadcasted_iota(jnp.int32, (nb, nb), 1)
    tri2 = (row2 < col2).astype(jnp.float32)  # strict: exclusive offsets
    offs = jnp.dot(sums, tri2, preferred_element_type=jnp.float32)
    return (offs[:, :, None] + part).reshape(q_n, c).astype(jnp.int32)


def _dedup_compact(
    cand: jax.Array, run: int, c_comp: int
) -> tuple[jax.Array, jax.Array]:
    """Fused stages 3+4 on raw candidate rows (shared by every tail body).

    Returns ``comp (Q, c_comp)`` — each row's unique candidate indices
    ascending, :data:`_SENT` beyond the survivor count — and
    ``comparisons (Q,)``. Rank-compaction is a searchsorted over the
    survivor prefix sum (rank r's position is the first index where the
    running unique count reaches r), replacing the staged path's second
    full-width sort.
    """
    x = jnp.where(cand < 0, _SENT, cand)
    srt = merge_sorted_runs(x, run)
    uniq = jnp.concatenate(
        [srt[:, :1] < _SENT, srt[:, 1:] != srt[:, :-1]], axis=-1
    ) & (srt < _SENT)
    comparisons = jnp.sum(uniq.astype(jnp.int32), axis=-1)
    cum = _prefix_sum(uniq)
    tgt = jax.lax.broadcasted_iota(jnp.int32, (c_comp,), 0) + 1
    pos = jax.vmap(lambda row: jnp.searchsorted(row, tgt, side="left"))(cum)
    inb = pos < srt.shape[1]
    comp = jnp.take_along_axis(srt, jnp.minimum(pos, srt.shape[1] - 1), axis=-1)
    return jnp.where(inb, comp, _SENT), comparisons


def _finish_topk(dist, comp, valid, k):
    """Top-k over compacted distances -> (kd, ki); inf/-1 padded."""
    if dist.shape[1] < k:  # fewer compacted slots than k: pad with inf
        pad = k - dist.shape[1]
        dist = jnp.pad(dist, ((0, 0), (0, pad)), constant_values=jnp.inf)
        comp = jnp.pad(comp, ((0, 0), (0, pad)), constant_values=_SENT)
        valid = jnp.pad(valid, ((0, 0), (0, pad)), constant_values=False)
    neg, p = jax.lax.top_k(-dist, k)
    ki = jnp.where(
        jnp.isfinite(neg),
        jnp.take_along_axis(
            jnp.where(valid, comp, -1), jnp.maximum(p, 0), axis=-1
        ),
        -1,
    )
    return -neg, ki


def _tail_kernel_interpret(
    data_ref, q_ref, cand_ref, kd_ref, ki_ref, cmp_ref, ovf_ref,
    *, run: int, c_comp: int, k: int, n: int,
):
    """Whole-chunk megakernel body (interpret formulation).

    ``data_ref`` lives in ``pl.ANY`` space: the candidate gather indexes
    it directly, so no block copy of the dataset ever happens.
    """
    cand = cand_ref[...]
    qs = q_ref[...]
    comp, comparisons = _dedup_compact(cand, run, c_comp)
    valid = comp != _SENT
    safe = jnp.clip(jnp.where(valid, comp, 0), 0, n - 1)
    pts = data_ref[safe]  # (Q, c_comp, d) — the one HBM touch per candidate
    dist = jnp.sum(jnp.abs(pts - qs[:, None, :]), axis=-1)
    dist = jnp.where(valid, dist, jnp.inf)
    kd_ref[...], ki_ref[...] = _finish_topk(dist, comp, valid, k)
    cmp_ref[...] = comparisons
    ovf_ref[...] = jnp.maximum(comparisons - jnp.int32(c_comp), 0)


def _tail_payload_compiled(
    data, qdata, meta, queries, cand, *, run, c_comp, c_rerank, k,
    interpret=False,
):
    """The compressed-payload tail's compiled formulation -> ``(kd, ki,
    comparisons, overflow, rerank_misses)``.

    Dedup, compaction and the gathers run as XLA ops, for the Mosaic
    refusals listed in :data:`XLA_STAGES`; the approximate and the exact
    distance passes run in the ``l1_topk`` kernel's distance mode. The
    gathers read the quantized rows (dequantized by XLA in the gather's
    fusion) and the f32 shortlist rows, and the shortlist and final top-k
    are XLA ``top_k``. ``interpret=True`` runs the same kernel through the
    Pallas interpreter (tests).
    """
    comp, comparisons = _dedup_compact(cand, run, c_comp)
    valid = comp != _SENT
    idx = jnp.where(valid, comp, 0)
    mrows = meta[idx]  # (Q, cc, 2) [scale, error bound]
    deq = qdata[idx].astype(jnp.float32) * mrows[..., 0:1]
    ad = l1_ops.l1_dist(queries, deq, valid, interpret=interpret)
    _, spos = jax.lax.top_k(-ad, c_rerank)  # ties -> lowest compacted position
    scand = jnp.take_along_axis(comp, spos, axis=1)
    svalid = jnp.take_along_axis(valid, spos, axis=1)
    ed = l1_ops.l1_dist(
        queries, data[jnp.where(svalid, scand, 0)], svalid, interpret=interpret
    )
    kd, ki, misses = _payload_finish(
        comp, valid, ad, mrows[..., 1], ed, spos, svalid, c_rerank, k
    )
    overflow = jnp.maximum(comparisons - jnp.int32(c_comp), 0)
    return kd, ki, comparisons, overflow, misses


def _payload_finish(
    comp, valid, ad, qerr, ed, spos, svalid, c_rerank: int, k: int
):
    """Shared payload-tail epilogue: position-ordered exact top-k + misses.

    ``ad``/``qerr`` cover the full compacted width; ``ed`` is the exact
    distance of shortlist entry ``spos[i]`` (inf where invalid). The exact
    distances scatter back into a position-ordered full-width row (inf off
    the shortlist), so ``lax.top_k`` keeps the §6 lowest-position tie rule
    without re-sorting; the miss predicate then reads the k-th exact
    distance off the finished ``kd``.
    """
    q_n, cc = ad.shape
    pos_iota = jax.lax.broadcasted_iota(jnp.int32, (q_n, cc, c_rerank), 1)
    match = (pos_iota == spos[:, None, :]) & svalid[:, None, :]  # (Q, cc, cr)
    ed_full = jnp.min(
        jnp.where(match, ed[:, None, :], jnp.inf), axis=-1
    )  # (Q, cc) exact distances in compacted-position order
    kd, ki = _finish_topk(ed_full, comp, valid, k)
    dk = kd[:, k - 1][:, None]
    in_short = jnp.any(match, axis=-1)
    miss = valid & (~in_short) & (ad - qerr <= dk)
    return kd, ki, jnp.sum(miss.astype(jnp.int32), axis=-1)


def _tail_kernel_payload_interpret(
    data_ref, qd_ref, meta_ref, q_ref, cand_ref,
    kd_ref, ki_ref, cmp_ref, ovf_ref, mis_ref,
    *, run: int, c_comp: int, c_rerank: int, k: int, n: int,
):
    """Whole-chunk compressed-payload megakernel body (interpret).

    ``qd_ref``/``meta_ref``/``data_ref`` live in ``pl.ANY`` space: the
    candidate gather streams *quantized* rows (the compressed HBM touch),
    and only the ``c_rerank`` shortlist rows are re-gathered from the f32
    dataset for the exact rerank (DESIGN.md §13).
    """
    cand = cand_ref[...]
    qs = q_ref[...]
    comp, comparisons = _dedup_compact(cand, run, c_comp)
    valid = comp != _SENT
    safe = jnp.clip(jnp.where(valid, comp, 0), 0, n - 1)
    mrows = meta_ref[safe]  # (Q, cc, 2)
    deq = qd_ref[safe].astype(jnp.float32) * mrows[..., 0:1]
    ad = jnp.sum(jnp.abs(deq - qs[:, None, :]), axis=-1)
    ad = jnp.where(valid, ad, jnp.inf)
    cr = min(c_rerank, ad.shape[1])
    _, spos = jax.lax.top_k(-ad, cr)  # ties -> lowest compacted position
    scand = jnp.take_along_axis(comp, spos, axis=-1)
    svalid = jnp.take_along_axis(valid, spos, axis=-1)
    pts = data_ref[jnp.clip(jnp.where(svalid, scand, 0), 0, n - 1)]
    ed = jnp.sum(jnp.abs(pts - qs[:, None, :]), axis=-1)
    ed = jnp.where(svalid, ed, jnp.inf)
    kd, ki, misses = _payload_finish(
        comp, valid, ad, mrows[..., 1], ed, spos, svalid, cr, k
    )
    kd_ref[...], ki_ref[...] = kd, ki
    cmp_ref[...] = comparisons
    ovf_ref[...] = jnp.maximum(comparisons - jnp.int32(c_comp), 0)
    mis_ref[...] = misses


@functools.partial(jax.jit, static_argnames=("run", "c_comp", "k"))
def query_tail_pallas(
    data: jax.Array,  # (n, d)
    queries: jax.Array,  # (Q, d)
    cand: jax.Array,  # (Q, C) int32, run-sorted, C = run * 2^e
    *,
    run: int,
    c_comp: int,
    k: int,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Launch the fused tail (interpreted) -> ``(kd, ki, comparisons,
    overflow)``.

    Callers go through :func:`repro.kernels.query_fused.ops.query_tail`,
    which pads ``cand`` to the power-of-two run count this launch requires.
    """
    q_n, c = cand.shape
    n, d = data.shape
    kern = functools.partial(
        _tail_kernel_interpret, run=run, c_comp=c_comp, k=k, n=n
    )
    return pl.pallas_call(
        kern,
        grid=(1,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # data stays HBM-side
            pl.BlockSpec((q_n, d), lambda i: (0, 0)),
            pl.BlockSpec((q_n, c), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((q_n, k), lambda i: (0, 0)),
            pl.BlockSpec((q_n, k), lambda i: (0, 0)),
            pl.BlockSpec((q_n,), lambda i: (0,)),
            pl.BlockSpec((q_n,), lambda i: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q_n, k), jnp.float32),
            jax.ShapeDtypeStruct((q_n, k), jnp.int32),
            jax.ShapeDtypeStruct((q_n,), jnp.int32),
            jax.ShapeDtypeStruct((q_n,), jnp.int32),
        ],
        interpret=True,
    )(data, queries, cand)


@functools.partial(
    jax.jit,
    static_argnames=("run", "c_comp", "c_rerank", "k", "interpret"),
)
def query_tail_payload_pallas(
    data: jax.Array,  # (n, d) exact f32 rows (rerank only)
    qdata: jax.Array,  # (n, d) quantized rows (runtime.payload)
    meta: jax.Array,  # (n, 2) f32 [dequant scale, L1 error bound]
    queries: jax.Array,  # (Q, d)
    cand: jax.Array,  # (Q, C) int32, run-sorted, C = run * 2^e
    *,
    run: int,
    c_comp: int,
    c_rerank: int,
    k: int,
    interpret: bool = True,
) -> tuple[jax.Array, ...]:
    """Launch the compressed-payload fused tail (DESIGN.md §13).

    Returns ``(kd, ki, comparisons, overflow, rerank_misses)``. Callers go
    through :func:`repro.kernels.query_fused.ops.query_tail_payload`, which
    pads ``cand``, clamps ``c_rerank`` to the compacted width, and resolves
    the interpret policy.
    """
    q_n, c = cand.shape
    n, d = data.shape
    cr = min(c_rerank, c_comp)
    out_shape = [
        jax.ShapeDtypeStruct((q_n, k), jnp.float32),
        jax.ShapeDtypeStruct((q_n, k), jnp.int32),
        jax.ShapeDtypeStruct((q_n,), jnp.int32),
        jax.ShapeDtypeStruct((q_n,), jnp.int32),
        jax.ShapeDtypeStruct((q_n,), jnp.int32),
    ]
    if interpret:
        kern = functools.partial(
            _tail_kernel_payload_interpret,
            run=run, c_comp=c_comp, c_rerank=cr, k=k, n=n,
        )
        return pl.pallas_call(
            kern,
            grid=(1,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),  # data: rerank gather
                pl.BlockSpec(memory_space=pl.ANY),  # qdata: compressed rows
                pl.BlockSpec(memory_space=pl.ANY),  # meta: scale + err
                pl.BlockSpec((q_n, d), lambda i: (0, 0)),
                pl.BlockSpec((q_n, c), lambda i: (0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((q_n, k), lambda i: (0, 0)),
                pl.BlockSpec((q_n, k), lambda i: (0, 0)),
                pl.BlockSpec((q_n,), lambda i: (0,)),
                pl.BlockSpec((q_n,), lambda i: (0,)),
                pl.BlockSpec((q_n,), lambda i: (0,)),
            ],
            out_shape=out_shape,
            interpret=True,
        )(data, qdata, meta, queries, cand)

    return _tail_payload_compiled(
        data, qdata, meta, queries, cand, run=run, c_comp=c_comp,
        c_rerank=cr, k=k,
    )
