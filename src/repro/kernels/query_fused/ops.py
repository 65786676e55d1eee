"""jit'd wrapper for the fused query-tail megakernel.

:func:`query_tail` is the ``BackendOps.query_tail`` implementation the
pallas pipeline backend registers (``core/pipeline.py``, DESIGN.md §6): it
replaces staged pipeline stages 3-5 (dedup -> compact -> gather + L1 +
top-k) with ``query_fused.query_tail_pallas``, one interpreted launch,
bit-exact with the staged reference path (``ref.query_tail_ref`` is the
oracle). The fused f32 tail has no compiled formulation (Mosaic refuses
its body, ``query_fused.XLA_STAGES``): compiled on a TPU, the pallas
backend runs the staged stages with the ``l1_topk`` kernel instead.

The wrapper owns the launch-shape policy so the kernel bodies stay pure:

* pad the candidate width to a multiple of ``run`` and then to a
  power-of-two run count (the merge network's only shape requirement),
  with ``-1`` columns that dedup discards;
* resolve the interpret policy (``blocking.resolve_interpret``); a
  compiled f32 tail is refused, never interpreted in its place.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import blocking
from repro.kernels.query_fused.query_fused import (
    query_tail_pallas,
    query_tail_payload_pallas,
)
from repro.obs.metrics import count_retrace


def _run_padded_width(c: int, run: int) -> int:
    """Candidate width padded so the merge network accepts it: the next
    multiple of ``run`` holding a power-of-two number of runs."""
    c_runs = blocking.round_up(max(c, 1), run)
    r = c_runs // run
    r_pow2 = 1 << max(0, r - 1).bit_length()
    return run * r_pow2


@functools.partial(
    jax.jit, static_argnames=("run", "c_comp", "k", "interpret")
)
def query_tail(
    data: jax.Array,  # (n, d) dataset rows
    queries: jax.Array,  # (Q, d) query chunk
    cand: jax.Array,  # (Q, C) int32 candidate indices, -1 where masked
    *,
    run: int,
    c_comp: int,
    k: int,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused tail over a candidate tensor -> ``(kd, ki, comparisons, overflow)``.

    ``cand`` rows must be run-sorted: every ``run``-aligned slice ascends,
    with ``-1`` only as a trailing pad inside its slice — exactly what the
    pipeline gather stage emits for ``run = gcd(c_max, c_in, slot)``
    (duplicates *across* runs are fine; the fused dedup removes them).
    Output contract matches the staged stages 3-5 bit-for-bit: ``kd (Q, k)``
    ascending L1 distances (inf-padded), ``ki (Q, k)`` global indices (-1
    padded, §6 lowest-position tie rule), ``comparisons (Q,)`` unique
    candidates, ``overflow (Q,)`` unique survivors beyond ``c_comp``.
    """
    # bumped once per (re)trace — the body runs only on jit cache misses.
    # ``repro.obs.retraces("query_tail")`` is the public counter the
    # compile-cache regression tests pin: runtime query knobs must never
    # re-trace the fused kernel (DESIGN.md §4/§12).
    count_retrace("query_tail")
    if not blocking.resolve_interpret(interpret):
        raise ValueError(
            "the fused f32 query tail has no compiled formulation"
            " (query_fused.XLA_STAGES); compiled, the pallas backend runs the"
            " staged tail with the l1_topk kernel"
        )
    c = cand.shape[1]
    c_pad = _run_padded_width(c, run)
    if c_pad != c:
        cand = blocking.pad_axis(cand, 1, c_pad, value=-1)
    return query_tail_pallas(
        data, queries.astype(jnp.float32), cand, run=run, c_comp=c_comp, k=k
    )


@functools.partial(
    jax.jit, static_argnames=("run", "c_comp", "c_rerank", "k", "interpret")
)
def query_tail_payload(
    data: jax.Array,  # (n, d) exact f32 rows (shortlist rerank)
    qdata: jax.Array,  # (n, d) quantized rows (runtime.payload)
    meta: jax.Array,  # (n, 2) f32 [dequant scale, L1 error bound]
    queries: jax.Array,  # (Q, d) query chunk
    cand: jax.Array,  # (Q, C) int32 candidate indices, -1 where masked
    *,
    run: int,
    c_comp: int,
    c_rerank: int,
    k: int,
    interpret: bool | None = None,
) -> tuple[jax.Array, ...]:
    """Compressed-payload fused tail -> ``(kd, ki, comparisons, overflow,
    rerank_misses)`` (DESIGN.md §13).

    Same candidate contract as :func:`query_tail`; the distance stage
    streams quantized rows, selects a ``c_rerank`` shortlist, and reranks
    it exactly in f32. ``rerank_misses`` counts excluded candidates whose
    approximate distance came within the row's quantization error bound of
    the k-th exact distance — zero everywhere certifies ``kd``/``ki``
    bit-identical to the f32 tail (``ref.query_tail_payload_ref`` is the
    oracle; tests/test_property_kernels.py holds both to it).
    """
    count_retrace("query_tail_payload")
    interp = blocking.resolve_interpret(interpret)
    c = cand.shape[1]
    c_pad = _run_padded_width(c, run)
    if c_pad != c:
        cand = blocking.pad_axis(cand, 1, c_pad, value=-1)
    return query_tail_payload_pallas(
        data, qdata, meta, queries.astype(jnp.float32), cand,
        run=run, c_comp=c_comp, c_rerank=c_rerank, k=k, interpret=interp,
    )
