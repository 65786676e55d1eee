"""Out-of-core build + compressed candidate payload (DESIGN.md §13).

Seeded deterministic tests so this module collects without hypothesis;
the randomized sweeps live in tests/test_property_build.py and
tests/test_property_kernels.py (requirements-dev.txt).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pipeline, slsh
from repro.kernels.query_fused import ops as qf_ops
from repro.kernels.query_fused import ref as qf_ref
from repro.runtime import memory as memory_mod
from repro.runtime import payload as payload_mod

jax.config.update("jax_platform_name", "cpu")


def _cfg(**kw):
    base = dict(
        m_out=12, L_out=6, m_in=6, L_in=3, alpha=0.02, k=5,
        val_lo=20.0, val_hi=180.0, c_max=32, c_in=8, h_max=4, p_max=64,
        c_comp=128, c_rerank=16, build_chunk=64,
    )
    base.update(kw)
    return pipeline.SLSHConfig.compose(**base)


def _data(n, d=30, seed=2):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, d)) * 20 + 80


def _assert_index_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ------------------------------------------------------- chunked build


@pytest.mark.parametrize(
    "n,chunk,backend",
    [
        (37, 1, "reference"),  # one point per chunk
        (100, 7, "reference"),  # non-dividing chunk
        (128, 64, "pallas"),  # exact multiple
        (100, 33, "pallas"),  # ragged tail chunk
        (50, 128, "reference"),  # chunk >= n (single run)
    ],
)
def test_chunked_build_bit_exact(n, chunk, backend):
    """build_mode='chunked' reproduces the monolithic tables bit-for-bit:
    the ladder merges ascending-index runs with left-wins ties, which is
    exactly one stable full sort."""
    cfg = _cfg(build_chunk=chunk, backend=backend)
    data = _data(n)
    mono = slsh.build_index(
        jax.random.PRNGKey(0), data, cfg.replace(build_mode="monolithic")
    )
    chnk = slsh.build_index(
        jax.random.PRNGKey(0), data, cfg.replace(build_mode="chunked")
    )
    _assert_index_equal(mono, chnk)


def test_chunked_build_traced_bit_exact():
    """Under an outer jit (simulate_build's cell programs) the chunked
    mode sorts each table whole in-graph and stays bit-exact."""
    cfg = _cfg(build_chunk=48, build_mode="chunked")
    data = _data(150)
    mono = slsh.build_index(
        jax.random.PRNGKey(0), data, cfg.replace(build_mode="monolithic")
    )
    traced = jax.jit(
        lambda d: pipeline.build_from_params(
            d, mono.outer_params, mono.inner_params, cfg
        )
    )(data)
    _assert_index_equal(mono, traced)


def test_build_mode_auto_threshold():
    """auto goes chunked only past build_chunk points — toy datasets and
    smoke-tier grid cells keep the monolithic single-dispatch path."""
    cfg = _cfg(build_chunk=64, build_mode="auto")
    small, large = _data(64), _data(65)
    # both modes are bit-exact, so equality can't distinguish them; the
    # dispatch decision itself is what this pins
    assert pipeline._pick_build_mode(cfg, 64) == "monolithic"
    assert pipeline._pick_build_mode(cfg, 65) == "chunked"
    assert pipeline._pick_build_mode(cfg.replace(build_mode="chunked"), 2) == "chunked"
    for data in (small, large):
        mono = slsh.build_index(
            jax.random.PRNGKey(0), data, cfg.replace(build_mode="monolithic")
        )
        auto = slsh.build_index(jax.random.PRNGKey(0), data, cfg)
        _assert_index_equal(mono, auto)


def test_build_mode_validation():
    with pytest.raises(pipeline.ConfigError):
        _cfg(build_mode="sideways")
    with pytest.raises(pipeline.ConfigError):
        _cfg(payload="f64")
    with pytest.raises(pipeline.ConfigError):
        _cfg(payload="f16")  # needs the pallas fused tail
    with pytest.raises(pipeline.ConfigError):
        _cfg(payload="f16", backend="pallas", c_rerank=3)  # c_rerank < k


# ------------------------------------------------------- payload module


@pytest.mark.parametrize("fmt", ["f16", "i8"])
def test_make_payload_error_bound(fmt):
    data = _data(200)
    p = payload_mod.make_payload(data, fmt)
    deq = p.qdata.astype(jnp.float32) * p.meta[:, 0:1]
    err = jnp.sum(jnp.abs(data - deq), axis=-1)
    np.testing.assert_allclose(np.asarray(err), np.asarray(p.meta[:, 1]), rtol=1e-4)
    assert p.nbytes == memory_mod.payload_nbytes(200, 30, fmt)
    assert p.nbytes < data.size * 4  # actually compressed


def test_make_payload_rejects_unknown_format():
    with pytest.raises(ValueError):
        payload_mod.make_payload(_data(4), "f64")


# ------------------------------------------------- payload kernel vs ref


def _tail_inputs(seed, q_n=4, d=13, n=90, run=8, windows=3, fill=0.7):
    key = jax.random.PRNGKey(seed)
    kd_, kq_, kv, kc, kb = jax.random.split(key, 5)
    # quantized coords force exact-distance ties (§6 tie-rule coverage)
    data = jnp.round(jax.random.uniform(kd_, (n, d)) * 4.0) / 4.0
    qs = jnp.round(jax.random.uniform(kq_, (q_n, d)) * 4.0) / 4.0
    vals = jnp.sort(
        jax.random.randint(kv, (q_n, windows, run), 0, n, dtype=jnp.int32),
        axis=-1,
    )
    cnt = jax.random.randint(kc, (q_n, windows, 1), 0, run + 1)
    hit = jax.random.bernoulli(kb, fill, (q_n, windows, 1))
    cnt = jnp.where(hit, cnt, 0)
    pos = jnp.arange(run)[None, None, :]
    cand = jnp.where(pos < cnt, vals, -1).reshape(q_n, windows * run)
    return data, qs, cand, run


@pytest.mark.parametrize("fmt", ["f16", "i8"])
@pytest.mark.parametrize("seed", [0, 3, 11])
def test_payload_tail_kernel_matches_ref(fmt, seed):
    data, qs, cand, run = _tail_inputs(seed)
    p = payload_mod.make_payload(data, fmt)
    kw = dict(c_comp=24, c_rerank=8, k=5)
    want = qf_ref.query_tail_payload_ref(data, p.qdata, p.meta, qs, cand, **kw)
    got = qf_ops.query_tail_payload(data, p.qdata, p.meta, qs, cand, run=run, **kw)
    names = ("kd", "ki", "comparisons", "overflow", "rerank_misses")
    for g, w, name in zip(got, want, names):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), err_msg=name)


@pytest.mark.parametrize("fmt", ["f16", "i8"])
def test_payload_tail_zero_misses_matches_f32(fmt):
    """rerank_misses == 0 certifies bit-identical kd/ki to the f32 tail;
    comparisons/overflow match unconditionally (stages 3-4 are shared)."""
    data, qs, cand, run = _tail_inputs(7)
    p = payload_mod.make_payload(data, fmt)
    kd32, ki32, cmp32, ovf32 = qf_ops.query_tail(
        data, qs, cand, run=run, c_comp=24, k=5
    )
    kd, ki, cmp_, ovf, misses = qf_ops.query_tail_payload(
        data, p.qdata, p.meta, qs, cand, run=run, c_comp=24, c_rerank=24, k=5
    )
    np.testing.assert_array_equal(np.asarray(cmp_), np.asarray(cmp32))
    np.testing.assert_array_equal(np.asarray(ovf), np.asarray(ovf32))
    # c_rerank == c_comp reranks every survivor exactly: misses impossible
    assert int(np.asarray(misses).sum()) == 0
    np.testing.assert_array_equal(np.asarray(ki), np.asarray(ki32))
    np.testing.assert_array_equal(np.asarray(kd), np.asarray(kd32))


def test_payload_tail_counts_starved_shortlist():
    """A shortlist smaller than the survivor set must *count* at-risk
    exclusions (i8's wide error bound flags them), never drop silently."""
    data, qs, cand, run = _tail_inputs(5, n=60, fill=1.0)
    # a tight cluster far from the origin: the i8 step (~amax/127) dwarfs
    # the inter-point spacing, so every excluded survivor is at risk
    data = 80.0 + data * 0.05
    qs = 80.0 + qs * 0.05
    p = payload_mod.make_payload(data, "i8")
    _, _, cmp_, _, misses = qf_ops.query_tail_payload(
        data, p.qdata, p.meta, qs, cand, run=run,
        c_comp=24, c_rerank=5, k=5,
    )
    assert int(np.asarray(misses).sum()) > 0
    # misses are bounded by candidates outside the shortlist
    outside = np.maximum(np.minimum(np.asarray(cmp_), 24) - 5, 0)
    assert (np.asarray(misses) <= outside).all()


# ------------------------------------------------ pipeline payload path


@pytest.mark.parametrize("fmt", ["f16", "i8"])
def test_pipeline_payload_query_bit_identical(fmt):
    cfg = _cfg(backend="pallas")
    data = _data(300)
    idx = slsh.build_index(jax.random.PRNGKey(0), data, cfg)
    qs = data[:23] + _data(23, seed=9) * 0.01
    r32 = pipeline.query_batch(idx, data, qs, cfg)
    rp = pipeline.query_batch(idx, data, qs, cfg.replace(payload=fmt))
    assert r32.rerank_misses is None
    assert int(np.asarray(rp.rerank_misses).sum()) == 0
    for name in ("knn_idx", "knn_dist", "comparisons", "compaction_overflow"):
        np.testing.assert_array_equal(
            np.asarray(getattr(rp, name)), np.asarray(getattr(r32, name)),
            err_msg=name,
        )


def test_pipeline_payload_query_traced():
    """The payload path under an outer jit (the api handle's one-jit
    wrapper) stays bit-identical to eager."""
    cfg = _cfg(backend="pallas", payload="f16")
    data = _data(150)
    idx = slsh.build_index(jax.random.PRNGKey(0), data, cfg)
    qs = data[:11]
    eager = pipeline.query_batch(idx, data, qs, cfg)
    traced = jax.jit(lambda q: pipeline.query_batch(idx, data, q, cfg))(qs)
    np.testing.assert_array_equal(np.asarray(eager.knn_idx), np.asarray(traced.knn_idx))
    np.testing.assert_array_equal(
        np.asarray(eager.rerank_misses), np.asarray(traced.rerank_misses)
    )


# ------------------------------------------------------ memory accountant


def test_memory_report_components_sum():
    cfg = _cfg()
    data = _data(256)
    idx = slsh.build_index(jax.random.PRNGKey(0), data, cfg)
    rep = memory_mod.index_report(idx, data, "i8")
    comp = rep.components
    assert rep.total == sum(comp.values())
    assert comp["tables"] == memory_mod.tree_nbytes(idx.outer)
    assert comp["data"] == 256 * 30 * 4
    assert comp["payload"] == 256 * (30 + 8)
    d = rep.to_dict()
    assert d["total_bytes"] == rep.total and d["cells"] == [1, 1]


def test_memory_report_per_cell_split():
    cfg = _cfg()
    data = _data(256)
    idx = slsh.build_index(jax.random.PRNGKey(0), data, cfg)
    rep = memory_mod.index_report(idx, data, "f32", cells=(2, 2))
    assert rep.components["payload"] == 0
    for name, b in rep.per_cell.items():
        assert b == rep.components[name] // 4


# --------------------------------------------------------- api surface


def test_api_payload_single_and_grid_guard():
    from repro import dslsh

    cfg = _cfg(backend="pallas", payload="f16")
    data = _data(256)
    idx = dslsh.build(jax.random.PRNGKey(1), data, cfg, dslsh.single())
    i32 = dslsh.build(
        jax.random.PRNGKey(1), data, cfg.replace(payload="f32"), dslsh.single()
    )
    qs = data[:9]
    res, r32 = idx.query(qs), i32.query(qs)
    assert res.rerank_miss_total == 0 and r32.rerank_misses is None
    np.testing.assert_array_equal(np.asarray(res.knn_idx), np.asarray(r32.knn_idx))
    assert res.rerank_misses.shape == (1, 1, 9)
    with pytest.raises(dslsh.ConfigError):
        dslsh.build(jax.random.PRNGKey(1), data, cfg, dslsh.grid(nu=2, p=2))
    rep = idx.memory_report()
    assert rep.components["payload"] == 256 * (30 * 2 + 8)
    assert rep.cells == (1, 1)
