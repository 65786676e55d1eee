"""Serving front-end tests: coalescer properties, admission, deadlines,
retrace pin, and RCU ingest-while-serving (DESIGN.md §15).

The coalescer contract rides a property sweep (hypothesis when installed,
always-run seeded cores regardless): any arrival sequence → every request
lands in exactly one micro-batch, padding never exceeds the gap to the
chosen rung, and per-request result rows are bit-identical to a solo
``Index.query`` when no degradation fired.
"""
import contextlib
import math
import time

import jax
import numpy as np
import pytest

from repro import api as dslsh
from repro import obs as obs_mod
from repro.core import slsh
from repro.serve import admission, coalesce
from repro.serve import frontend as frontend_mod

try:
    from hypothesis import given, settings, strategies as st

    HAS_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised on minimal installs
    HAS_HYPOTHESIS = False

jax.config.update("jax_platform_name", "cpu")

D = 12


def _cfg(**kw):
    base = dict(
        m_out=12, L_out=8, m_in=6, L_in=4, alpha=0.02, k=5,
        val_lo=0.0, val_hi=1.0, c_max=32, c_in=8, h_max=4, p_max=64,
        build_chunk=128, query_chunk=8,
    )
    base.update(kw)
    return slsh.SLSHConfig.compose(**base)


@pytest.fixture(scope="module")
def grid_index():
    rng = np.random.default_rng(0)
    data = rng.uniform(0.0, 1.0, (256, D)).astype(np.float32)
    idx = dslsh.build(
        jax.random.PRNGKey(0), data, _cfg(),
        dslsh.grid(nu=2, p=2, routed=True),
    )
    return idx, data


class _Stub:
    """A queue entry carrying just what the coalescer reads."""

    def __init__(self, rid, nq, deadline_at=math.inf):
        self.rid = rid
        self.queries = np.full((nq, 3), float(rid), np.float32)
        self.deadline_at = deadline_at


def _check_partition(sizes, ladder):
    """Drain `sizes` through a Coalescer and hold the packing contract."""
    co = coalesce.Coalescer(ladder)
    queue = [_Stub(i, n) for i, n in enumerate(sizes)]
    batches = []
    while queue:
        before = [r.rid for r in queue]
        mb = co.form(queue)
        batches.append(mb)
        # popped-from-front discipline: taken ++ remaining == before
        taken = [r.rid for r in mb.requests]
        assert taken + [r.rid for r in queue] == before
        # the chosen bucket is the smallest rung that fits: padding is
        # bounded by the gap below the rung (never reaches the rung before)
        assert mb.bucket == coalesce.bucket_for(mb.n_real, co.ladder)
        smaller = [r for r in co.ladder if r < mb.bucket]
        if smaller:
            assert mb.n_real > smaller[-1]
        assert mb.padding == mb.bucket - mb.n_real >= 0
        assert mb.queries.shape == (mb.bucket, 3)
        # spans tile [0, n_real) exactly, in request order
        lo = 0
        for r, (a, b) in zip(mb.requests, mb.spans):
            assert a == lo and b - a == r.queries.shape[0]
            np.testing.assert_array_equal(mb.queries[a:b], r.queries)
            lo = b
        assert lo == mb.n_real
        # padding rows replicate the first real row (in-domain values)
        np.testing.assert_array_equal(
            mb.queries[mb.n_real:],
            np.broadcast_to(mb.queries[:1], (mb.padding, 3)),
        )
    # exactly-once: every request appears in exactly one micro-batch
    seen = [r.rid for mb in batches for r in mb.requests]
    assert sorted(seen) == list(range(len(sizes)))
    assert len(seen) == len(set(seen))


def test_coalescer_partition_seeded_sweep():
    """Always-run core of the property: 200 random arrival sequences."""
    rng = np.random.default_rng(7)
    ladders = [(8, 32, 128, 512), (4, 16), (1, 2, 3, 5, 8), (7,)]
    for trial in range(200):
        ladder = ladders[trial % len(ladders)]
        sizes = rng.integers(1, ladder[-1] + 1, rng.integers(1, 12)).tolist()
        _check_partition(sizes, ladder)


if HAS_HYPOTHESIS:

    @settings(max_examples=60, deadline=None)
    @given(
        ladder=st.lists(
            st.integers(1, 64), min_size=1, max_size=5, unique=True
        ).map(lambda xs: tuple(sorted(xs))),
        data=st.data(),
    )
    def test_coalescer_partition_property(ladder, data):
        sizes = data.draw(
            st.lists(st.integers(1, ladder[-1]), min_size=1, max_size=12)
        )
        _check_partition(sizes, ladder)


def test_bucket_for_bounds():
    assert coalesce.bucket_for(1) == 8
    assert coalesce.bucket_for(512) == 512
    with pytest.raises(ValueError):
        coalesce.bucket_for(0)
    with pytest.raises(ValueError):
        coalesce.bucket_for(513)
    with pytest.raises(ValueError):
        coalesce.Coalescer((8, 8, 32))  # duplicate rung


def test_coalesced_results_bitexact_vs_solo_query(grid_index):
    """The exactness contract: no degradation fired → every request's
    result rows are bit-identical to querying its batch alone."""
    idx, data = grid_index
    rng = np.random.default_rng(3)
    fe = idx.frontend(frontend_mod.FrontendConfig(ladder=(8, 32)))
    reqs = []
    for i in range(5):
        nq = int(rng.integers(1, 7))
        q = (data[rng.integers(0, len(data), nq)]
             + rng.normal(0, 0.01, (nq, D))).astype(np.float32)
        reqs.append((fe.submit(q, now=0.0), q))
    fe.drain(now=0.0)
    for req, q in reqs:
        assert req.status == "done" and not req.degraded
        solo = idx.query(q)
        np.testing.assert_array_equal(req.knn_dist, np.asarray(solo.knn_dist))
        np.testing.assert_array_equal(req.knn_idx, np.asarray(solo.knn_idx))
    fe.assert_conserved()


def test_steady_state_serving_retraces_nothing(grid_index):
    """The §15 pin: after warmup, serving any arrival pattern on the
    ladder (all rungs, all degradation levels) triggers zero new query
    retraces — ``obs.query_retraces()`` stays flat."""
    idx, data = grid_index
    rng = np.random.default_rng(5)
    fe = idx.frontend(frontend_mod.FrontendConfig(
        ladder=(8, 32), degrade=((0.5, None), (0.0, 2)),
    ))
    fe.warmup()
    r0 = obs_mod.query_retraces()
    t = 0.0
    for i in range(12):
        nq = int(rng.integers(1, 30))
        q = data[rng.integers(0, len(data), nq)].astype(np.float32)
        # mix tight deadlines (degraded rung) and loose ones (exact rung)
        fe.submit(q, deadline_s=(0.1 if i % 3 else 1e6), now=t)
        fe.pump(now=t)
        t += 0.05
    fe.drain(now=t)
    assert obs_mod.query_retraces() == r0, "steady state must not retrace"
    fe.assert_conserved()


PUMP_CHILDREN = ("serve.coalesce", "serve.dispatch", "serve.device_wait",
                 "serve.fetch", "serve.scatter")


def _serve_rounds(idx, data, ob=None, hold_s=0.0):
    """Serve the same seeded arrivals pump by pump (optionally under an
    active ``ob``, each query held ``hold_s`` longer) -> (tickets, query
    retraces after warmup)."""
    rng = np.random.default_rng(11)
    handle = idx.with_obs(None)
    fe = handle.frontend(frontend_mod.FrontendConfig(ladder=(8, 32)))
    fe.warmup()
    if hold_s:
        query = handle.query

        def held(q, **kw):
            time.sleep(hold_s)
            return query(q, **kw)

        handle.query = held
    r0 = obs_mod.query_retraces()
    tickets = []
    with ob.activate() if ob is not None else contextlib.nullcontext():
        for _ in range(4):
            for _ in range(int(rng.integers(1, 5))):
                nq = int(rng.integers(1, 9))
                tickets.append(fe.submit(
                    data[rng.integers(0, len(data), nq)].astype(np.float32)))
            fe.pump()
    fe.assert_conserved()
    return tickets, obs_mod.query_retraces() - r0


def test_traced_pump_spans_tile_it(grid_index):
    """Under an active Obs each pump() records one ``serve.pump`` span with
    the micro-batch's args, tiled (within 1%) by its five children in
    order; every submit records ``serve.submit`` with its rid and
    verdict. Each query is held 100 ms, a pump as long as one on the chip:
    the spans' fixed cost on a CPU host, up to a few hundred µs a pump,
    would otherwise pass 1% of a CPU pump of a few ms."""
    idx, data = grid_index
    ob = obs_mod.Obs(metrics=False)
    tickets, _ = _serve_rounds(idx, data, ob, hold_s=0.1)
    ev = ob.tracer.events
    pumps = sorted((e for e in ev if e["name"] == "serve.pump"), key=lambda e: e["ts"])
    assert [p["args"]["batch"] for p in pumps] == [0, 1, 2, 3]
    for p in pumps:
        lo, hi = p["ts"], p["ts"] + p["dur"]
        inside = sorted((e for e in ev if e is not p and lo <= e["ts"] <= hi),
                        key=lambda e: e["ts"])
        kids = [e for e in inside if e["name"] in PUMP_CHILDREN]
        assert tuple(e["name"] for e in kids) == PUMP_CHILDREN
        for a, b in zip(kids, kids[1:]):
            assert a["ts"] + a["dur"] <= b["ts"]
        assert kids[-1]["ts"] + kids[-1]["dur"] <= hi
        assert sum(e["dur"] for e in kids) >= 0.99 * p["dur"]
        served = [t for t in tickets if t.batch == p["args"]["batch"]]
        assert p["args"]["requests"] == len(served)
        assert p["args"]["rows"] == sum(t.n_queries for t in served)
        assert p["args"]["bucket"] in (8, 32) and p["args"]["max_cells"] == -1
        query = [e for e in inside if e["name"] == "index.query"]
        assert len(query) == 1  # inside serve.dispatch
        assert kids[1]["ts"] <= query[0]["ts"]
        assert query[0]["ts"] + query[0]["dur"] <= kids[1]["ts"] + kids[1]["dur"]
    submits = [e["args"] for e in ev if e["name"] == "serve.submit"]
    assert [a["rid"] for a in submits] == [t.rid for t in tickets]
    assert {a["verdict"] for a in submits} == {admission.Verdict.ADMIT}


def test_served_requests_carry_queue_stamps(grid_index):
    """Every served request carries ``started_at`` and ``batch``, with
    ``submitted_at <= started_at <= submitted_at + latency_s``; tracing
    changes neither the answers (bit-identical) nor the compiled programs
    (no retrace)."""
    idx, data = grid_index
    bare, bare_retraces = _serve_rounds(idx, data)
    traced, traced_retraces = _serve_rounds(idx, data, obs_mod.Obs())
    assert bare_retraces == traced_retraces == 0
    assert len(bare) == len(traced)
    for a, b in zip(bare, traced):
        for t in (a, b):
            assert t.status == "done" and t.batch is not None
            assert t.submitted_at <= t.started_at <= t.submitted_at + t.latency_s
        assert a.batch == b.batch
        np.testing.assert_array_equal(a.knn_dist, b.knn_dist)
        np.testing.assert_array_equal(a.knn_idx, b.knn_idx)


def test_deadline_degradation_and_expiry(grid_index):
    idx, data = grid_index
    q = data[:4].astype(np.float32)
    fe = idx.frontend(frontend_mod.FrontendConfig(
        ladder=(8,), degrade=((0.5, None), (0.0, 2)),
    ))
    # loose slack → exact; tight slack → capped and flagged
    loose = fe.submit(q, deadline_s=10.0, now=0.0)
    fe.pump(now=0.0)
    assert loose.status == "done" and not loose.degraded
    tight = fe.submit(q, deadline_s=0.1, now=1.0)
    fe.pump(now=1.0)
    assert tight.status == "done" and tight.degraded and tight.max_cells == 2
    # already past the deadline in queue → expired without compute, flagged
    stale = fe.submit(q, deadline_s=1.0, now=2.0)
    out = fe.pump(now=10.0)
    assert stale in out and stale.status == "timed_out"
    assert stale.knn_dist is None
    s = fe.assert_conserved()
    assert s.timed_out == 1 and s.completed == 2


def test_degrade_config_requires_routed_deployment():
    rng = np.random.default_rng(0)
    data = rng.uniform(0.0, 1.0, (64, D)).astype(np.float32)
    idx = dslsh.build(jax.random.PRNGKey(0), data, _cfg(), dslsh.single())
    with pytest.raises(ValueError, match="routed"):
        idx.frontend(frontend_mod.FrontendConfig(degrade=((0.0, 2),)))


def test_admission_token_bucket_verdicts():
    ctl = admission.AdmissionController(
        {"t": admission.TenantQuota(rate_qps=2.0, burst=4.0,
                                    degrade_overdraft=2.0)},
        max_queue=100,
    )
    v = [ctl.admit("t", 2, 0, now=0.0) for _ in range(4)]
    # 4.0 burst: two ADMITs, then the overdraft band, then SHED
    assert v == ["admit", "admit", "degrade", "shed"]
    # the overdraft is a debt: 1 s of refill only climbs back to zero
    # tokens, so service is still degraded; 2 s restores exact service
    assert ctl.admit("t", 1, 0, now=1.0) == "degrade"
    assert ctl.admit("t", 1, 0, now=2.0) == "admit"
    s = ctl.stats
    assert (s.submitted, s.admitted, s.degraded, s.shed) == (6, 3, 2, 1)
    s.check()


def test_admission_queue_backpressure_and_default_quota():
    ctl = admission.AdmissionController(max_queue=10)
    assert ctl.admit("anyone", 8, 0, now=0.0) == "admit"  # unlimited quota
    assert ctl.admit("anyone", 8, 8, now=0.0) == "shed"  # queue would burst
    assert ctl.stats.shed_queue_full == 1
    ctl.stats.check()


def test_frontend_sheds_over_quota_and_counts(grid_index):
    idx, data = grid_index
    q = data[:4].astype(np.float32)
    fe = idx.frontend(frontend_mod.FrontendConfig(
        ladder=(8,),
        quotas=(("burst", admission.TenantQuota(rate_qps=1.0, burst=4.0)),),
    ))
    ok = fe.submit(q, tenant="burst", now=0.0)
    shed = fe.submit(q, tenant="burst", now=0.0)
    free = fe.submit(q, tenant="other", now=0.0)
    assert ok.status == "queued" and free.status == "queued"
    assert shed.status == "shed" and shed.verdict == "shed"
    fe.drain(now=0.0)
    s = fe.assert_conserved()
    assert (s.submitted, s.completed, s.shed) == (3, 2, 1)


def test_edf_orders_tightest_deadline_first(grid_index):
    """Two ladder-sized waves: the tighter deadline must ride the first
    micro-batch even though it was submitted second."""
    idx, data = grid_index
    q8 = data[:8].astype(np.float32)
    fe = idx.frontend(frontend_mod.FrontendConfig(ladder=(8,)))
    loose = fe.submit(q8, deadline_s=100.0, now=0.0)
    tight = fe.submit(q8, deadline_s=1.0, now=0.0)
    first = fe.pump(now=0.0)
    assert first == [tight] and loose.status == "queued"
    fe.drain(now=0.0)
    assert loose.status == "done"
    fe.assert_conserved()


def test_rcu_ingest_while_serving_swaps_epochs():
    """Streaming RCU: ingest builds aside and publishes one epoch swap;
    results before/after come from distinct epochs, pre-swap answers are
    bit-identical to the pre-swap index, and the swap retraces nothing."""
    rng = np.random.default_rng(2)
    data = rng.uniform(0.0, 1.0, (128, D)).astype(np.float32)
    extra = rng.uniform(0.0, 1.0, (32, D)).astype(np.float32)
    idx = dslsh.build(
        jax.random.PRNGKey(0), data, _cfg(),
        dslsh.streaming(nu=2, node_capacity=256, delta_cap=64),
    )
    q = data[:4] + rng.normal(0, 0.01, (4, D)).astype(np.float32)
    before_solo = idx.query(q)
    fe = idx.frontend()
    fe.warmup()
    r0 = obs_mod.query_retraces()
    a = fe.submit(q, now=0.0)
    fe.pump(now=0.0)
    n0 = fe.index.n_index()
    rep = fe.ingest(extra, ts=1.0)
    assert rep.inserted == 32
    b = fe.submit(q, now=1.0)
    fe.pump(now=1.0)
    assert (a.epoch, b.epoch) == (0, 1)
    assert fe.index.n_index() == n0 + 32
    np.testing.assert_array_equal(a.knn_dist, np.asarray(before_solo.knn_dist))
    np.testing.assert_array_equal(a.knn_idx, np.asarray(before_solo.knn_idx))
    # post-swap answers match a direct query of the swapped handle
    after_solo = fe.index.query(q)
    np.testing.assert_array_equal(b.knn_dist, np.asarray(after_solo.knn_dist))
    assert obs_mod.query_retraces() == r0, "RCU clones must share programs"
    fe.assert_conserved()


def test_snapshot_isolates_batch_and_streaming():
    """Batch snapshots are the handle itself (immutable); streaming
    snapshots share arrays but diverge after ingest."""
    rng = np.random.default_rng(4)
    data = rng.uniform(0.0, 1.0, (64, D)).astype(np.float32)
    b = dslsh.build(jax.random.PRNGKey(0), data, _cfg(), dslsh.single())
    assert b.snapshot() is b
    s = dslsh.build(
        jax.random.PRNGKey(0), data, _cfg(),
        dslsh.streaming(nu=2, node_capacity=128, delta_cap=32),
    )
    snap = s.snapshot()
    assert snap is not s
    snap.ingest(data[:8], 1.0)
    assert snap.n_index() == s.n_index() + 8  # the source never moved


def test_async_frontend_awaitable_submit(grid_index):
    import asyncio

    idx, data = grid_index
    q = data[:4].astype(np.float32)
    fe = idx.frontend(frontend_mod.FrontendConfig(ladder=(8,)))
    solo = idx.query(q)

    async def main():
        async with frontend_mod.AsyncFrontend(fe) as af:
            reqs = await asyncio.gather(
                *(af.submit(q, tenant=f"t{i}") for i in range(3))
            )
        return reqs

    reqs = asyncio.run(main())
    for r in reqs:
        assert r.status == "done" and not r.degraded
        np.testing.assert_array_equal(r.knn_dist, np.asarray(solo.knn_dist))
    fe.assert_conserved()


def test_oversized_request_rejected_at_submit(grid_index):
    idx, data = grid_index
    fe = idx.frontend(frontend_mod.FrontendConfig(ladder=(8,)))
    with pytest.raises(ValueError, match="ladder"):
        fe.submit(data[:9].astype(np.float32), now=0.0)
