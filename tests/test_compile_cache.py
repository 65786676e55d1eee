"""Compile-cache regression tests for the fused query-tail megakernel.

The fused kernel's jit cache is keyed on array shapes plus its static
launch parameters (``run``, ``c_comp``, ``k``, ``interpret``) — nothing
else. Runtime query knobs (``budget=`` / ``max_cells=`` / ``drop_mask``
on :meth:`dslsh.Index.query`) and repeat eager dispatch must therefore
never re-trace it; a retrace here means a Python value leaked into the
kernel's trace key and every degradation decision would recompile the
hot path (DESIGN.md §4). The counter these tests pin is the *public*
observability surface — ``repro.obs.retraces("query_tail")``, the
``dslsh_jit_retraces_total`` counter bumped once per (re)trace — so the
same contract is watchable in production (DESIGN.md §12).
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro import api as dslsh
from repro import obs
from repro.core import slsh

jax.config.update("jax_platform_name", "cpu")


def _cfg(**kw):
    base = dict(
        m_out=12, L_out=8, m_in=8, L_in=4, alpha=0.02, k=5,
        val_lo=0.0, val_hi=1.0, c_max=32, c_in=8, h_max=4, p_max=64,
        build_chunk=128, query_chunk=16, backend="pallas",
    )
    base.update(kw)
    return slsh.SLSHConfig.compose(**base)


def test_query_knobs_do_not_retrace_fused_kernel():
    """Every budget / max_cells / drop_mask combination reuses the fused
    kernel trace made at warmup — the per-cell candidate shapes and the
    static launch params are knob-independent."""
    cfg = _cfg()
    data = jax.random.uniform(jax.random.PRNGKey(0), (256, 16))
    q = jax.random.uniform(jax.random.PRNGKey(1), (32, 16))
    deploy = dslsh.grid(
        nu=2, p=2, routed=True, degrade=((0.05, None), (0.01, 2), (0.0, 1))
    )
    idx = dslsh.build(jax.random.PRNGKey(2), data, cfg, deploy)
    jax.block_until_ready(idx.query(q).knn_idx)  # warmup: traces once
    assert obs.retraces("query_tail") >= 1
    before = obs.retraces("query_tail")
    drop = np.zeros(2, bool)
    drop[1] = True
    variations = [
        dict(budget=1.0),  # degrades to no cap — the warmup program
        dict(budget=0.02),  # degrades to max_cells=2
        dict(budget=-1.0),  # below every level -> most degraded
        dict(max_cells=3),  # new outer program, same inner kernel
        dict(max_cells=1),
        dict(drop_mask=drop),
        dict(budget=0.02, drop_mask=drop),
    ]
    for kw in variations:
        jax.block_until_ready(idx.query(q, **kw).knn_idx)
    assert obs.retraces("query_tail") == before, (
        f"fused kernel re-traced by runtime query knobs: "
        f"{obs.retraces('query_tail') - before} extra trace(s)"
    )


def test_eager_dispatch_steady_state_no_retrace():
    """The eager per-stage fused schedule reuses every stage's trace
    across calls, including batch sizes that pad to the same chunk shape
    — pinned via the public per-stage retrace counters."""
    cfg = _cfg()
    data = jax.random.uniform(jax.random.PRNGKey(3), (256, 16))
    idx = slsh.build_index(jax.random.PRNGKey(4), cfg=cfg, data=data)
    q32 = jax.random.uniform(jax.random.PRNGKey(5), (32, 16))
    jax.block_until_ready(slsh.query_batch(idx, data, q32, cfg).knn_idx)
    stages = ("query_tail", "hash", "gather_work", "gather_select")
    before = {s: obs.retraces(s) for s in stages}
    jax.block_until_ready(slsh.query_batch(idx, data, q32, cfg).knn_idx)
    # 24 queries pad to the same 16-row chunks the warmup traced
    q24 = q32[:24]
    jax.block_until_ready(slsh.query_batch(idx, data, q24, cfg).knn_idx)
    after = {s: obs.retraces(s) for s in stages}
    assert after == before, f"eager schedule re-traced: {before} -> {after}"


def test_reference_backend_never_touches_fused_kernel():
    """The reference backend stays staged: no fused-kernel traces at all."""
    cfg = _cfg(backend="reference")
    data = jax.random.uniform(jax.random.PRNGKey(6), (128, 16))
    idx = slsh.build_index(jax.random.PRNGKey(7), cfg=cfg, data=data)
    q = jax.random.uniform(jax.random.PRNGKey(8), (8, 16))
    before = obs.retraces("query_tail")
    res = slsh.query_batch(idx, data, q, cfg)
    jax.block_until_ready(res.knn_idx)
    assert jnp.all(res.comparisons >= 0)
    assert obs.retraces("query_tail") == before


def test_mesh_query_steady_state_no_retrace():
    """A mesh deployment compiles one query program per (reducer,
    max_cells): the index, data, queries and route plan enter it as
    arguments, so repeated queries — new values, same batch shape —
    retrace nothing (the grid path's ``Index._grid_fn`` contract)."""
    from repro.launch.mesh import make_local_mesh

    cfg = _cfg()
    data = jax.random.uniform(jax.random.PRNGKey(9), (256, 16))
    q = jax.random.uniform(jax.random.PRNGKey(10), (16, 16))
    for routed in (False, True):
        deploy = dslsh.mesh(make_local_mesh(1, 1), routed=routed)
        idx = dslsh.build(jax.random.PRNGKey(11), data, cfg, deploy)
        first = idx.query(q)
        jax.block_until_ready(first.knn_idx)
        before = obs.retraces("mesh_query")
        assert before >= 1
        for qs in (q, q[::-1], q * 0.5):
            jax.block_until_ready(idx.query(qs).knn_idx)
        assert obs.retraces("mesh_query") == before, (
            f"mesh query re-traced: {obs.retraces('mesh_query') - before}"
            f" extra trace(s) (routed={routed})"
        )
        np.testing.assert_array_equal(
            np.asarray(idx.query(q).knn_idx), np.asarray(first.knn_idx)
        )
