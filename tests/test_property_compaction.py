"""Hypothesis properties for the pipeline's compaction stage (DESIGN.md §3).

The contract: compaction moves each query's unique survivors to the front
of a tight buffer without ever dropping or duplicating one (until the
``c_comp`` budget binds, in which case the excess is *counted* in
``QueryResult.compaction_overflow``), and the paper's ``comparisons``
metric is computed before compaction, so the budget never changes it.
Checked at the stage level on adversarial candidate rows and end-to-end on
both compute backends, with and without a streaming ``DeltaView``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis (requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro import stream
from repro.core import pipeline, slsh

jax.config.update("jax_platform_name", "cpu")


@given(
    rows=st.lists(
        st.lists(st.integers(-1, 30), min_size=12, max_size=12),
        min_size=1, max_size=6,
    ),
    c_comp=st.integers(1, 16),
)
@settings(max_examples=40, deadline=None)
def test_compact_stage_preserves_unique_candidates(rows, c_comp):
    """Stage property: the compacted buffer holds exactly the first
    ``c_comp`` unique valid candidates (ascending), the overflow counts the
    rest, and ``comparisons`` is the pre-compaction unique count."""
    cand = jnp.asarray(rows, jnp.int32)
    cand_sorted, uniq, comparisons = pipeline._stage_dedup(cand)
    comp, valid, overflow = pipeline._stage_compact(
        cand_sorted, uniq, comparisons, c_comp
    )
    for r, row in enumerate(rows):
        expect = sorted({v for v in row if v >= 0})
        got = np.asarray(comp[r])[np.asarray(valid[r])].tolist()
        assert got == expect[:c_comp], (expect, got)
        assert len(set(got)) == len(got)  # never duplicates
        assert int(comparisons[r]) == len(expect)  # unchanged by compaction
        assert int(overflow[r]) == max(len(expect) - c_comp, 0)
        # slots past the survivors are inert -1 pads
        assert (np.asarray(comp[r])[~np.asarray(valid[r])] == -1).all()


@st.composite
def _query_setup(draw):
    seed = draw(st.integers(0, 2**16))
    n = draw(st.integers(40, 120))
    n_stream = draw(st.integers(0, 24))
    backend = draw(st.sampled_from(["reference", "pallas"]))
    use_inner = draw(st.booleans())
    # the validator accepts c_comp >= k (k=4 below) or c_comp <= 0 (off)
    c_comp = draw(st.one_of(st.integers(4, 48), st.integers(-2, 0)))
    return seed, n, n_stream, backend, use_inner, c_comp


@given(_query_setup())
@settings(max_examples=12, deadline=None)
def test_query_compaction_is_exact_and_counts_overflow(setup):
    """End-to-end property: a c_comp budget changes nothing but the
    distance-stage width — ``comparisons``/``bucket_total`` are identical
    to the uncapped pipeline, overflow is exactly the excess over the
    effective width, and whenever no query overflows the K-NN results are
    bit-identical. Runs the streamed (DeltaView) path when n_stream > 0."""
    seed, n, n_stream, backend, use_inner, c_comp = setup
    d = 8
    data = jax.random.uniform(jax.random.PRNGKey(seed), (n + n_stream, d))
    cfg = slsh.SLSHConfig.compose(
        m_out=8, L_out=4, m_in=6, L_in=2, alpha=0.05, k=4, use_inner=use_inner,
        val_lo=0.0, val_hi=1.0, c_max=32, c_in=8, h_max=2, p_max=64,
        build_chunk=64, query_chunk=8, backend=backend, c_comp=c_comp,
    )
    cfg_full = cfg.replace(c_comp=0)
    q = data[:6]

    if n_stream:
        sidx = stream.stream_init(
            jax.random.PRNGKey(1), data[:n], cfg,
            capacity=n + n_stream, delta_cap=n_stream,
        )
        sidx = stream.insert_batch(sidx, data[n:], cfg)

        def run(c):
            return stream.query_batch(sidx, q, c)
    else:
        idx = slsh.build_index(jax.random.PRNGKey(1), data, cfg)

        def run(c):
            return pipeline.query_batch(idx, data, q, c)

    res = run(cfg)
    res_full = run(cfg_full)

    np.testing.assert_array_equal(
        np.asarray(res.comparisons), np.asarray(res_full.comparisons)
    )
    np.testing.assert_array_equal(
        np.asarray(res.bucket_total), np.asarray(res_full.bucket_total)
    )
    c_total = cfg.L_out * cfg.slot
    cc = pipeline._compact_width(cfg, c_total, n + n_stream)
    np.testing.assert_array_equal(
        np.asarray(res.compaction_overflow),
        np.maximum(np.asarray(res.comparisons) - cc, 0),
    )
    # the uncapped width covers every unique survivor by construction
    assert (np.asarray(res_full.compaction_overflow) == 0).all()
    if int(jnp.max(res.compaction_overflow)) == 0:
        np.testing.assert_array_equal(
            np.asarray(res.knn_idx), np.asarray(res_full.knn_idx)
        )
        np.testing.assert_array_equal(
            np.asarray(res.knn_dist), np.asarray(res_full.knn_dist)
        )
