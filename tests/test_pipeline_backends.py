"""Backend-equivalence tests for the staged SLSH pipeline (DESIGN.md §6).

``backend="pallas"`` (interpret mode on CPU) must match
``backend="reference"`` bit-for-bit: identical bucket keys out of
``build_index`` and identical top-k results out of ``query_batch`` —
including multiprobe and ``use_inner=False`` configs. Also pins the shared
builder: ``cell_build`` on a 1x1 grid must equal ``build_index`` exactly.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs, stream
from repro.core import distributed as D
from repro.core import pipeline, slsh
from repro.kernels.inner_probe import ops as ip_ops

jax.config.update("jax_platform_name", "cpu")


def _cfg(**kw):
    base = dict(
        m_out=12, L_out=8, m_in=8, L_in=4, alpha=0.02, k=10,
        val_lo=0.0, val_hi=1.0, c_max=64, c_in=16, h_max=4, p_max=128,
        build_chunk=200, query_chunk=16,
    )
    base.update(kw)
    return slsh.SLSHConfig.compose(**base)


def _data(n=512, d=12, seed=0):
    return jax.random.uniform(jax.random.PRNGKey(seed), (n, d))


def _assert_trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


CONFIG_VARIANTS = [
    pytest.param({}, id="inner"),
    pytest.param({"use_inner": False}, id="no_inner"),
    pytest.param({"multiprobe": 2}, id="inner+multiprobe"),
    pytest.param({"multiprobe": 2, "use_inner": False}, id="no_inner+multiprobe"),
]


@pytest.mark.parametrize("kw", CONFIG_VARIANTS)
def test_build_index_backends_identical(kw):
    """Pallas and reference builds must produce identical indices."""
    data = _data()
    cfg_r = _cfg(**kw)
    cfg_p = cfg_r.replace(backend="pallas")
    idx_r = slsh.build_index(jax.random.PRNGKey(1), data, cfg_r)
    idx_p = slsh.build_index(jax.random.PRNGKey(1), data, cfg_p)
    _assert_trees_equal(idx_r, idx_p)


@pytest.mark.parametrize("kw", CONFIG_VARIANTS)
def test_query_batch_backends_identical(kw):
    """Same index, both query backends: identical top-k and metrics."""
    data = _data()
    cfg_r = _cfg(**kw)
    cfg_p = cfg_r.replace(backend="pallas")
    idx = slsh.build_index(jax.random.PRNGKey(1), data, cfg_r)
    q = data[:24] + 0.01 * jax.random.normal(jax.random.PRNGKey(2), (24, 12))
    res_r = slsh.query_batch(idx, data, q, cfg_r)
    res_p = slsh.query_batch(idx, data, q, cfg_p)
    np.testing.assert_array_equal(np.asarray(res_r.knn_idx), np.asarray(res_p.knn_idx))
    np.testing.assert_array_equal(np.asarray(res_r.knn_dist), np.asarray(res_p.knn_dist))
    np.testing.assert_array_equal(
        np.asarray(res_r.comparisons), np.asarray(res_p.comparisons)
    )
    np.testing.assert_array_equal(
        np.asarray(res_r.bucket_total), np.asarray(res_p.bucket_total)
    )


def test_query_index_matches_query_batch_row():
    """The single-query path is the batched pipeline with Q=1."""
    data = _data()
    cfg = _cfg()
    idx = slsh.build_index(jax.random.PRNGKey(1), data, cfg)
    res_b = slsh.query_batch(idx, data, data[:4], cfg)
    for i in range(4):
        res_1 = slsh.query_index(idx, data, data[i], cfg)
        np.testing.assert_array_equal(
            np.asarray(res_1.knn_idx), np.asarray(res_b.knn_idx[i])
        )
        np.testing.assert_array_equal(
            np.asarray(res_1.knn_dist), np.asarray(res_b.knn_dist[i])
        )


def test_cell_build_matches_build_index_p1():
    """One shared builder: the p=1 distributed cell equals the single-shard
    index field-for-field (no duplicated build body to drift)."""
    data = _data(n=256)
    cfg = _cfg()
    grid = D.Grid(nu=1, p=1)
    a = slsh.build_index(jax.random.PRNGKey(0), data, cfg)
    b = D.cell_build(jax.random.PRNGKey(0), data, jnp.int32(0), cfg, grid)
    _assert_trees_equal(a, b)


def test_cell_build_slices_rows_of_full_family():
    """Core c of a p-way grid owns rows [c*L/p, (c+1)*L/p) of the family."""
    data = _data(n=256)
    cfg = _cfg(L_out=8)
    grid = D.Grid(nu=1, p=2)
    full, _ = pipeline.make_family(jax.random.PRNGKey(0), data.shape[1], cfg)
    cell1 = D.cell_build(jax.random.PRNGKey(0), data, jnp.int32(1), cfg, grid)
    np.testing.assert_array_equal(
        np.asarray(cell1.outer_params.dims), np.asarray(full.dims[4:])
    )
    np.testing.assert_array_equal(
        np.asarray(cell1.outer_params.salts), np.asarray(full.salts[4:])
    )


def test_simulate_query_backend_identical():
    """The distributed (simulated) path honours cfg.backend end-to-end."""
    data = _data()
    cfg_r = _cfg()
    cfg_p = cfg_r.replace(backend="pallas")
    grid = D.Grid(nu=2, p=2)
    idx = D.simulate_build(jax.random.PRNGKey(0), data, cfg_r, grid)
    q = data[:8]
    kd_r, ki_r, comps_r, ovf_r = D.simulate_query(idx, data, q, cfg_r, grid)
    kd_p, ki_p, comps_p, ovf_p = D.simulate_query(idx, data, q, cfg_p, grid)
    np.testing.assert_array_equal(np.asarray(ki_r), np.asarray(ki_p))
    np.testing.assert_array_equal(np.asarray(kd_r), np.asarray(kd_p))
    np.testing.assert_array_equal(np.asarray(comps_r), np.asarray(comps_p))
    np.testing.assert_array_equal(np.asarray(ovf_r), np.asarray(ovf_p))


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_compaction_budget_is_exact_and_counts_overflow(backend):
    """The compact stage (DESIGN.md §3): an ample budget is bit-exact with
    the uncapped pipeline; a binding budget never changes ``comparisons``
    and surfaces exactly the excess as ``compaction_overflow``."""
    data = _data()
    cfg = _cfg(backend=backend)
    idx = slsh.build_index(jax.random.PRNGKey(1), data, cfg)
    q = data[:24] + 0.01 * jax.random.normal(jax.random.PRNGKey(2), (24, 12))
    res_full = slsh.query_batch(idx, data, q, cfg.replace(c_comp=0))
    assert (np.asarray(res_full.compaction_overflow) == 0).all()

    # ample budget (the default covers min(n, gather width)): identical
    res = slsh.query_batch(idx, data, q, cfg)
    _assert_trees_equal(res, res_full)

    # binding budget: comparisons untouched, overflow counted, k-NN results
    # restricted to the c_comp smallest-index survivors (deterministic)
    tiny = cfg.replace(c_comp=16)
    res_t = slsh.query_batch(idx, data, q, tiny)
    np.testing.assert_array_equal(
        np.asarray(res_t.comparisons), np.asarray(res_full.comparisons)
    )
    np.testing.assert_array_equal(
        np.asarray(res_t.compaction_overflow),
        np.maximum(np.asarray(res_full.comparisons) - 16, 0),
    )
    assert int(np.asarray(res_t.compaction_overflow).max()) > 0


def test_unknown_backend_raises():
    # rejected at config construction now (§11.2), not at first build
    with pytest.raises(ValueError, match="unknown SLSH backend"):
        _cfg(backend="tpu-v9")
    # the build-time guard still covers configs that bypass validation
    with pytest.raises(ValueError, match="unknown SLSH backend"):
        pipeline.get_backend("tpu-v9")


def test_backend_registry_contract():
    """Registered custom backends dispatch through the pipeline."""
    calls = {"words": 0, "topk": 0}
    ref = pipeline.get_backend("reference")

    def words(params, x):
        calls["words"] += 1
        return ref.signature_words(params, x)

    def l1topk(q, cands, mask, k):
        calls["topk"] += 1
        return ref.l1_topk(q, cands, mask, k)

    pipeline.register_backend("_test", pipeline.BackendOps(words, l1topk))
    try:
        cfg = _cfg(backend="_test")
        data = _data(n=128)
        idx = slsh.build_index(jax.random.PRNGKey(0), data, cfg)
        slsh.query_batch(idx, data, data[:4], cfg)
        assert calls["words"] > 0 and calls["topk"] > 0
    finally:
        pipeline._BACKENDS.pop("_test", None)


@pytest.fixture
def slab_backend():
    """The reference backend plus the inner-probe kernel (interpreted):
    the staged pipeline with the slab probe the compiled pallas backend
    takes on a TPU."""
    ref = pipeline.get_backend("reference")
    probe = functools.partial(ip_ops.inner_probe, interpret=True)
    pipeline.register_backend("_slab", ref._replace(inner_probe=probe))
    yield "_slab"
    pipeline._BACKENDS.pop("_slab", None)


@pytest.mark.parametrize("deploy", ["grid", "stream_delta"])
def test_staged_query_with_inner_probe_identical(slab_backend, deploy):
    """A staged query with the slab probe injected and without it gives
    identical results: on a simulated grid (the probe runs in every
    cell), and on a streaming index, whose delta queries keep the element
    takes while its base alone takes the probe."""
    data = _data()
    cfg = _cfg()
    cfg_s = cfg.replace(backend=slab_backend)
    q = data[:24] + 0.01 * jax.random.normal(jax.random.PRNGKey(2), (24, 12))
    slabs = obs.inner_probe_count("slab")
    if deploy == "grid":
        grid = D.Grid(nu=2, p=1)
        idx = D.simulate_build(jax.random.PRNGKey(0), data, cfg, grid)
        _assert_trees_equal(
            D.grid_query(idx, data, q, cfg_s, grid),
            D.grid_query(idx, data, q, cfg, grid),
        )
    else:
        sidx = stream.stream_init(
            jax.random.PRNGKey(1), data[:400], cfg, capacity=512, delta_cap=112
        )
        sidx = stream.insert_batch(sidx, data[400:], cfg, t=1.0)
        _assert_trees_equal(
            stream.query_batch(sidx, q, cfg_s), stream.query_batch(sidx, q, cfg)
        )
        _assert_trees_equal(
            pipeline.query_batch(sidx.base, sidx.store, q, cfg_s),
            pipeline.query_batch(sidx.base, sidx.store, q, cfg),
        )
    assert obs.inner_probe_count("slab") > slabs
