"""Hypothesis property sweeps for the Pallas kernels (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis (requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.kernels.hash_pack import ops as hp_ops
from repro.kernels.hash_pack import ref as hp_ref
from repro.kernels.l1_topk import ops as l1_ops
from repro.kernels.l1_topk import ref as l1_ref
from repro.kernels.query_fused import ops as qf_ops
from repro.kernels.query_fused import query_fused as qf
from repro.kernels.query_fused import ref as qf_ref

jax.config.update("jax_platform_name", "cpu")


@given(
    b=st.integers(1, 6),
    c=st.integers(1, 80),
    d=st.integers(1, 40),
    k=st.integers(1, 12),
    seed=st.integers(0, 2**16),
)
@settings(max_examples=12, deadline=None)
def test_l1_topk_property(b, c, d, k, seed):
    key = jax.random.PRNGKey(seed)
    kq, kc, km = jax.random.split(key, 3)
    q = jax.random.uniform(kq, (b, d))
    cands = jax.random.uniform(kc, (b, c, d))
    mask = jax.random.bernoulli(km, 0.7, (b, c))
    rd, _ = l1_ref.l1_topk_ref(q, cands, mask, k)
    kd, kp = l1_ops.l1_topk(q, cands, mask, k=k, b_blk=4, c_blk=32)
    np.testing.assert_allclose(np.asarray(kd), np.asarray(rd), rtol=1e-5, atol=1e-5)
    # returned positions must be valid and masked-in
    pos = np.asarray(kp)
    m = np.asarray(mask)
    for i in range(b):
        for j in range(k):
            if pos[i, j] >= 0:
                assert m[i, pos[i, j]], (i, j)


@given(
    t=st.integers(1, 64),
    d=st.integers(1, 48),
    m=st.integers(1, 96),
    seed=st.integers(0, 2**16),
)
@settings(max_examples=12, deadline=None)
def test_hash_pack_property(t, d, m, seed):
    key = jax.random.PRNGKey(seed)
    kx, kp = jax.random.split(key)
    x = jax.random.normal(kx, (t, d))
    proj = jax.random.normal(kp, (d, m))
    got = hp_ops.signrp_pack(x, proj, t_blk=32)
    want = hp_ref.hash_pack_ref(x, proj, jnp.zeros((m,)))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _gather_shaped_candidates(key, q_n, windows, run, n, fill):
    """Candidates shaped like _stage_gather's output: ascending runs of
    indices into [0, n), each run -1-padded past a random fill count;
    ``fill`` == 0 yields fully-empty rows (no probe hit anything)."""
    kv, kc, kb = jax.random.split(key, 3)
    vals = jnp.sort(jax.random.randint(kv, (q_n, windows, run), 0, n,
                                       dtype=jnp.int32), axis=-1)
    cnt = jax.random.randint(kc, (q_n, windows, 1), 0, run + 1)
    hit = jax.random.bernoulli(kb, fill, (q_n, windows, 1))  # empty buckets
    cnt = jnp.where(hit, cnt, 0)
    pos = jnp.arange(run)[None, None, :]
    return jnp.where(pos < cnt, vals, -1).reshape(q_n, windows * run)


@given(
    q_n=st.integers(1, 5),
    d=st.integers(1, 40),  # includes non-128-multiple (and non-8) widths
    n=st.integers(4, 200),
    run_exp=st.integers(2, 4),  # run length in {4, 8, 16}
    windows=st.integers(1, 6),  # 3 windows -> non-power-of-two run count
    cc=st.integers(1, 48),  # cc=1 with dense fill -> all-overflow rows
    k=st.integers(1, 12),
    fill=st.floats(0.0, 1.0),
    seed=st.integers(0, 2**16),
)
@settings(max_examples=20, deadline=None)
def test_query_tail_fused_property(q_n, d, n, run_exp, windows, cc, k, fill, seed):
    """The fused megakernel tail is bit-exact against the staged oracle on
    every QueryResult field — values, positions, §6 lowest-position
    tie-breaks, comparison counts, and compaction overflow."""
    run = 1 << run_exp
    key = jax.random.PRNGKey(seed)
    kd_, kq_, kc_ = jax.random.split(key, 3)
    # quantized coordinates force exact distance ties, exercising the §6
    # lowest-compacted-position tie rule rather than leaving it to chance
    data = jnp.round(jax.random.uniform(kd_, (n, d)) * 4.0) / 4.0
    qs = jnp.round(jax.random.uniform(kq_, (q_n, d)) * 4.0) / 4.0
    cand = _gather_shaped_candidates(kc_, q_n, windows, run, n, fill)
    want = qf_ref.query_tail_ref(data, qs, cand, c_comp=cc, k=k)
    got = qf_ops.query_tail(data, qs, cand, run=run, c_comp=cc, k=k)
    for g, w, name in zip(got, want, ("kd", "ki", "comparisons", "overflow")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), err_msg=name)


@given(
    q_n=st.integers(1, 4),
    d=st.integers(1, 40),
    n=st.integers(4, 160),
    run_exp=st.integers(2, 4),
    windows=st.integers(1, 5),
    cc=st.integers(1, 40),
    cr=st.integers(1, 40),  # independent of cc: starved and saturated
    k=st.integers(1, 10),
    fmt=st.sampled_from(["f16", "i8"]),
    fill=st.floats(0.0, 1.0),
    seed=st.integers(0, 2**16),
)
@settings(max_examples=20, deadline=None)
def test_query_tail_payload_property(
    q_n, d, n, run_exp, windows, cc, cr, k, fmt, fill, seed
):
    """The compressed-payload tail is bit-exact against its staged oracle
    on every output, and certified-exact (misses == 0) results match the
    f32 tail bit-for-bit (DESIGN.md §13)."""
    from repro.runtime import payload as payload_mod

    run = 1 << run_exp
    key = jax.random.PRNGKey(seed)
    kd_, kq_, kc_ = jax.random.split(key, 3)
    data = jnp.round(jax.random.uniform(kd_, (n, d)) * 4.0) / 4.0
    qs = jnp.round(jax.random.uniform(kq_, (q_n, d)) * 4.0) / 4.0
    cand = _gather_shaped_candidates(kc_, q_n, windows, run, n, fill)
    p = payload_mod.make_payload(data, fmt)
    want = qf_ref.query_tail_payload_ref(
        data, p.qdata, p.meta, qs, cand, c_comp=cc, c_rerank=cr, k=k
    )
    got = qf_ops.query_tail_payload(
        data, p.qdata, p.meta, qs, cand, run=run, c_comp=cc, c_rerank=cr, k=k
    )
    # the compiled formulation (XLA stages around the l1_topk kernel), its
    # kernel interpreted, on cand padded as query_fused.ops pads it
    c_pad = qf_ops._run_padded_width(cand.shape[1], run)
    compiled = jax.jit(
        qf._tail_payload_compiled,
        static_argnames=("run", "c_comp", "c_rerank", "k", "interpret"),
    )(
        data, p.qdata, p.meta, qs,
        jnp.pad(cand, ((0, 0), (0, c_pad - cand.shape[1])), constant_values=-1),
        run=run, c_comp=cc, c_rerank=min(cr, cc), k=k, interpret=True,
    )
    names = ("kd", "ki", "comparisons", "overflow", "rerank_misses")
    for g, c, w, name in zip(got, compiled, want, names):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), err_msg=name)
        np.testing.assert_array_equal(np.asarray(c), np.asarray(w), err_msg=name)
    f32 = qf_ref.query_tail_ref(data, qs, cand, c_comp=cc, k=k)
    misses = np.asarray(got[4])
    for row in range(q_n):
        if misses[row] == 0:
            np.testing.assert_array_equal(
                np.asarray(got[0][row]), np.asarray(f32[0][row]),
                err_msg="certified kd row",
            )
            np.testing.assert_array_equal(
                np.asarray(got[1][row]), np.asarray(f32[1][row]),
                err_msg="certified ki row",
            )


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_query_tail_all_overflow(backend):
    """cc=1 with saturated candidate rows: every query overflows, and the
    overflow count equals comparisons - c_comp exactly."""
    del backend  # the kernel is backend-agnostic; param documents intent
    n, d, q_n, run, windows = 64, 7, 3, 8, 4
    data = jax.random.uniform(jax.random.PRNGKey(0), (n, d))
    qs = jax.random.uniform(jax.random.PRNGKey(1), (q_n, d))
    cand = _gather_shaped_candidates(jax.random.PRNGKey(2), q_n, windows, run, n, 1.0)
    want = qf_ref.query_tail_ref(data, qs, cand, c_comp=1, k=5)
    got = qf_ops.query_tail(data, qs, cand, run=run, c_comp=1, k=5)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert int(np.asarray(got[3]).min()) >= 0
    np.testing.assert_array_equal(
        np.asarray(got[3]), np.maximum(np.asarray(got[2]) - 1, 0)
    )


@given(seed=st.integers(0, 2**16), use_inner=st.booleans())
@settings(max_examples=6, deadline=None)
def test_fused_pipeline_matches_staged_with_delta(seed, use_inner):
    """Backend equality through the *streaming* path: the pallas backend's
    fused tail consumes _stage_gather's base+delta fan-out (DeltaView),
    and must match the reference staged pipeline bit-for-bit."""
    from repro.core import slsh
    from repro.stream import index as stream_index

    cfg = slsh.SLSHConfig.compose(
        m_out=10, L_out=6, m_in=6, L_in=2, alpha=0.05, k=4,
        val_lo=0.0, val_hi=1.0, c_max=16, c_in=8, h_max=2, p_max=64,
        use_inner=use_inner, build_chunk=128, query_chunk=8,
    )
    key = jax.random.PRNGKey(seed)
    k0, k1, k2, k3 = jax.random.split(key, 4)
    data = jax.random.uniform(k0, (96, 12))
    extra = jax.random.uniform(k1, (24, 12))
    qs = jax.random.uniform(k2, (17, 12))
    results = {}
    for backend in ("reference", "pallas"):
        cfg_b = cfg.replace(backend=backend)
        sidx = stream_index.stream_init(
            k3, data, cfg_b, capacity=160, delta_cap=32
        )
        sidx = stream_index.insert_batch(sidx, extra, cfg_b, t=1.0)
        results[backend] = stream_index.query_batch(sidx, qs, cfg_b)
    for field in results["reference"]._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(results["reference"], field)),
            np.asarray(getattr(results["pallas"], field)),
            err_msg=field,
        )
