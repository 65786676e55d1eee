"""Observability-layer tests (DESIGN.md §12): exporter golden formats,
near-zero-cost disabled path, monotonic-clock deadlines, per-stage spans.

The contract under test: one instrumented ``dslsh.Index.query`` yields a
Perfetto-loadable Chrome trace with per-stage spans plus a metrics
snapshot with latency histograms and the paper's accounting signals —
while an instrumented-but-*disabled* handle stays within 5% of a bare
one, and every deadline/heartbeat measures on the monotonic clock (a
wall-clock jump must never expire a straggler deadline).
"""
import json
import re

import jax
import numpy as np
import pytest

from repro import api as dslsh
from repro import obs
from repro.core import slsh
from repro.obs import clock, metrics, trace

jax.config.update("jax_platform_name", "cpu")


def _cfg(**kw):
    base = dict(
        m_out=12, L_out=8, m_in=8, L_in=4, alpha=0.02, k=5,
        val_lo=0.0, val_hi=1.0, c_max=32, c_in=8, h_max=4, p_max=64,
        build_chunk=128, query_chunk=16, backend="pallas",
    )
    base.update(kw)
    return slsh.SLSHConfig.compose(**base)


# --------------------------------------------------------------- exporters


def test_chrome_trace_golden_schema():
    """Every event is a complete event with the trace-format fields, the
    document is Perfetto's {traceEvents, displayTimeUnit} shape, and
    nesting shows up as time containment on one track."""
    tr = trace.Tracer(pid=7)
    with tr.span("outer", deployment="single"):
        with tr.span("inner", stage="hash"):
            pass
    doc = json.loads(json.dumps(tr.to_chrome_trace()))  # JSON round-trip
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    assert doc["displayTimeUnit"] == "ms"
    assert [e["name"] for e in doc["traceEvents"]] == ["inner", "outer"]
    for e in doc["traceEvents"]:
        assert set(e) == {"name", "ph", "ts", "dur", "pid", "tid", "args"}
        assert e["ph"] == "X" and e["pid"] == 7
        assert e["ts"] >= 0.0 and e["dur"] >= 0.0
    inner, outer = doc["traceEvents"]
    assert inner["args"] == {"stage": "hash"}
    assert outer["args"] == {"deployment": "single"}
    # complete events nest by time containment (no parent pointers)
    assert outer["ts"] <= inner["ts"]
    assert outer["ts"] + outer["dur"] >= inner["ts"] + inner["dur"]
    assert tr.depth() == 0  # stack fully unwound


def test_prometheus_text_golden_format():
    """The exposition parses line-by-line as the Prometheus text format:
    TYPE headers, label syntax, cumulative buckets ending at +Inf."""
    reg = metrics.MetricsRegistry()
    reg.counter("dslsh_queries_total", "queries").labels(
        deployment="grid"
    ).inc(3)
    reg.gauge("dslsh_nodes_up", "live nodes").set(4)
    h = reg.histogram("dslsh_query_latency_seconds", "latency")
    for v in (2e-6, 5e-4, 5e-4, 0.2, 99.0):  # 99 s lands in +Inf
        h.observe(v)
    text = reg.prometheus_text()
    sample_re = re.compile(
        r'^[a-zA-Z_:][a-zA-Z0-9_:]*'
        r'(\{[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"(,[a-zA-Z_][a-zA-Z0-9_]*="[^"]*")*\})?'
        r" -?[0-9.eE+\-]+(inf)?$"
    )
    for line in text.strip().splitlines():
        if line.startswith("#"):
            assert re.match(r"^# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* ", line)
        else:
            assert sample_re.match(line), f"bad exposition line: {line!r}"
    assert "# TYPE dslsh_queries_total counter" in text
    assert "# TYPE dslsh_nodes_up gauge" in text
    assert "# TYPE dslsh_query_latency_seconds histogram" in text
    assert 'dslsh_queries_total{deployment="grid"} 3' in text
    # cumulative buckets: non-decreasing, +Inf == _count == observations
    bucket_re = re.compile(
        r'dslsh_query_latency_seconds_bucket\{le="([^"]+)"\} (\d+)'
    )
    counts = [int(m.group(2)) for m in bucket_re.finditer(text)]
    assert counts == sorted(counts)
    assert counts[-1] == 5
    assert text.count('le="+Inf"') == 1
    assert "dslsh_query_latency_seconds_count 5" in text
    assert counts[-2] == 4, "the 99 s observation must sit in +Inf only"


def test_snapshot_json_roundtrip_and_kind_conflict():
    reg = metrics.MetricsRegistry()
    reg.counter("c_total", "help text").inc()
    reg.histogram("h_seconds").observe(1e-3)
    snap = json.loads(json.dumps(reg.snapshot()))
    assert snap["c_total"] == {
        "type": "counter", "help": "help text", "values": {"": 1.0}
    }
    hval = snap["h_seconds"]["values"][""]
    assert hval["count"] == 1 and hval["sum"] == pytest.approx(1e-3)
    assert hval["buckets"]["+Inf"] == 1
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("c_total")
    with pytest.raises(ValueError, match="log_buckets"):
        metrics.log_buckets(lo=0.0)


# ----------------------------------------------------- bucket properties

try:  # property tests ride along when hypothesis is installed; the
    # deterministic boundary tests below always run
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:

    @given(
        lo=st.floats(1e-9, 1e3),
        ratio=st.floats(1.5, 1e9),
        per_decade=st.integers(1, 12),
    )
    @settings(max_examples=200, deadline=None)
    def test_log_buckets_boundary_properties(lo, ratio, per_decade):
        """Boundaries are strictly increasing, start at ``lo``, and cover
        ``hi`` (up to the 4-significant-digit label rounding)."""
        hi = lo * ratio
        b = metrics.log_buckets(lo, hi, per_decade)
        assert all(x < y for x, y in zip(b, b[1:])), "not strictly increasing"
        assert b[0] == pytest.approx(lo, rel=5e-4)
        assert b[-1] >= hi * (1 - 1e-3), "top boundary must reach hi"
        # one decade spans per_decade steps (up to rounding)
        if len(b) > per_decade:
            assert b[per_decade] == pytest.approx(10 * b[0], rel=1e-3)

    @given(
        values=st.lists(st.floats(1e-8, 100.0), min_size=1, max_size=50),
    )
    @settings(max_examples=100, deadline=None)
    def test_histogram_observation_lands_in_first_covering_bucket(values):
        h = metrics.Histogram(metrics.LATENCY_BUCKETS)
        for v in values:
            h.observe(v)
        cum = h.cumulative()
        assert cum == sorted(cum)
        assert cum[-1] == h.count == len(values)
        assert h.sum == pytest.approx(sum(values))
        bounds = h.boundaries
        for v in set(values):
            i = next(
                (j for j, b in enumerate(bounds) if v <= b), len(bounds)
            )
            # cumulative count at i covers every observation <= bounds[i]
            assert cum[i] >= sum(1 for x in values if x <= v)


def test_log_buckets_deterministic_boundaries():
    """The deterministic core of the property: defaults span 1 µs..10 s,
    strictly increasing, decade-aligned every ``per_decade`` steps."""
    b = metrics.LATENCY_BUCKETS
    assert b[0] == 1e-6 and b[-1] >= 10.0
    assert all(x < y for x, y in zip(b, b[1:]))
    for i in range(0, len(b) - 4, 4):  # per_decade=4 -> decade alignment
        assert b[i + 4] == pytest.approx(10 * b[i], rel=1e-3)
    b2 = metrics.log_buckets(1.0, 1e6, per_decade=2)
    assert b2[0] == 1.0 and b2[-1] == pytest.approx(1e6, rel=1e-3)
    assert len(b2) == 13


def test_histogram_boundary_value_is_inclusive():
    """``v == boundary`` counts in that boundary's bucket (le semantics)."""
    b = (1.0, 10.0, 100.0)
    h = metrics.Histogram(b)
    for v in (1.0, 10.0, 100.0, 100.1):
        h.observe(v)
    assert h.counts == [1, 1, 1, 1]
    assert h.cumulative() == [1, 2, 3, 4]


# ------------------------------------------------------- monotonic clocks


class _FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


def test_heartbeat_monitor_immune_to_wall_clock_jumps(monkeypatch):
    """Heartbeats measure on the monotonic clock: a wall-clock jump must
    never mark a live node down (the PR-7 deadline bugfix)."""
    from repro.runtime import ft

    fake = _FakeClock()
    monkeypatch.setattr(clock, "monotonic", fake)
    monkeypatch.setattr("time.time", lambda: 1.7e9)  # never consulted
    hb = ft.HeartbeatMonitor(n_nodes=2, deadline_s=0.5)
    hb.beat(0)
    hb.beat(1)
    monkeypatch.setattr("time.time", lambda: 1.7e9 + 86400)  # wall jumps a day
    fake.t += 0.4  # monotonic: still inside the deadline
    assert hb.down_nodes() == []
    fake.t += 0.2  # now past it
    assert hb.down_nodes() == [0, 1]
    hb.beat(1)
    assert hb.down_nodes() == [0]
    assert hb.drop_mask().tolist() == [True, False]


class _SteppingClock:
    """A clock that advances ``step`` seconds on every read."""

    def __init__(self, t=0.0, step=0.0):
        self.t = t
        self.step = step

    def __call__(self):
        self.t += self.step
        return self.t


def test_serve_deadline_on_monotonic_clock(monkeypatch):
    """A straggler deadline expires by monotonic elapsed time only: the
    wall clock jumping an hour per read mid-decode neither expires nor
    revives it (under the old ``time.time()`` deadlines, every request
    here would time out instantly)."""
    from repro import configs
    from repro.models import api as models_api
    from repro.serve import engine

    cfg = configs.get("granite-8b", smoke=True)
    model = models_api.build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    # monotonic advances 5 ms per read (~one read per decode step); the
    # wall clock leaps an hour per read — consulting it at all breaks
    monkeypatch.setattr(clock, "monotonic", _SteppingClock(1000.0, 0.005))
    monkeypatch.setattr("time.time", _SteppingClock(1.7e9, 3600.0))
    eng = engine.ServeEngine(model, params, max_batch=2, max_len=64)
    healthy = engine.Request(
        rid=0, tokens=rng.integers(0, cfg.vocab, 8), max_new=4, deadline_s=5.0
    )
    straggler = engine.Request(
        rid=1, tokens=rng.integers(0, cfg.vocab, 8), max_new=64, deadline_s=0.012
    )
    done = eng.serve([healthy, straggler])
    assert done[0].done and not done[0].timed_out, (
        "wall-clock jumps must not expire a monotonic deadline"
    )
    assert len(done[0].result) == 4
    assert done[1].timed_out and done[1].latency_s > 0.012
    assert done[1].latency_s < 1.0, "latency must be monotonic elapsed, not wall"


# ------------------------------------------------- spans, sections, obs


def test_timed_section_records_span_and_histogram():
    ob = obs.Obs()
    with ob.activate():
        with obs.timed_section("unit.test") as sec:
            assert sec.elapsed_s >= 0.0
    assert sec.dur_s >= 0.0
    assert [e["name"] for e in ob.tracer.events] == ["unit.test"]
    snap = ob.snapshot()["dslsh_section_seconds"]
    assert snap["values"]['section="unit.test"']["count"] == 1


def test_timed_section_without_obs_is_silent():
    with obs.timed_section("nowhere") as sec:
        pass
    assert sec.dur_s >= 0.0 and sec.obs is None


def test_obs_activate_nests_and_restores():
    a, b = obs.Obs(), obs.Obs()
    assert obs.get_active() is None
    with a.activate():
        assert obs.get_active() is a
        with b.activate():
            assert obs.get_active() is b
        assert obs.get_active() is a
    assert obs.get_active() is None


def test_disabled_obs_has_no_recording_surface():
    ob = obs.Obs.disabled()
    assert not ob.enabled and not ob.tracing
    assert ob.span("x") is obs.NULL_SPAN
    with pytest.raises(ValueError, match="disabled"):
        ob.save_trace("/tmp/never.json")


# ------------------------------------------------------------ end-to-end


def test_instrumented_query_yields_trace_and_metrics(tmp_path, monkeypatch):
    """The acceptance scenario: one instrumented single-deployment query
    records an ``index.query`` span and a snapshot with latency histograms
    + the paper's accounting signals, while running the very jitted
    program the bare handle runs (no eager per-stage schedule, no new
    trace) — bit-identical to the uninstrumented result."""
    from repro.core import pipeline

    cfg = _cfg()
    data = jax.random.uniform(jax.random.PRNGKey(0), (256, 16))
    q = jax.random.uniform(jax.random.PRNGKey(1), (32, 16))
    ob = obs.Obs()
    idx = dslsh.build(jax.random.PRNGKey(2), data, cfg, dslsh.single(), obs=ob)
    bare = idx.with_obs(None)
    want = bare.query(q)
    compiled = (obs.retraces("single_query"), obs.query_retraces())

    def eager(*a, **k):
        raise AssertionError("tracing ran the eager per-stage schedule")

    monkeypatch.setattr(pipeline, "_query_batch_fused_eager", eager)
    res = idx.query(q)
    assert (obs.retraces("single_query"), obs.query_retraces()) == compiled
    for got, ref in zip(jax.tree.leaves(res), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    names = {e["name"] for e in ob.tracer.events}
    assert {"index.build", "index.query"} <= names
    assert not any(n.startswith("query.") for n in names)
    top = next(e for e in ob.tracer.events if e["name"] == "index.query")
    assert top["args"]["deployment"] == "single" and top["args"]["queries"] == 32
    snap = ob.snapshot()
    assert snap["dslsh_queries_total"]["values"]['deployment="single"'] == 1.0
    lat = snap["dslsh_query_latency_seconds"]["values"]['deployment="single"']
    assert lat["count"] == 1 and lat["sum"] > 0.0
    # only the eager build's phases feed the stage histogram
    stages = snap["dslsh_stage_latency_seconds"]["values"]
    assert stages and all(k.startswith('stage="build.') for k in stages)
    assert snap["dslsh_comparisons_total"]["values"][""] > 0
    assert snap["dslsh_compaction_overflow_total"]["values"][""] >= 0
    assert snap["dslsh_jit_retraces_total"]["values"]['stage="query_tail"'] >= 1
    # exports are loadable artifacts
    tr_path = ob.save_trace(str(tmp_path / "trace.json"))
    doc = json.loads(open(tr_path).read())
    assert doc["traceEvents"] and all(e["ph"] == "X" for e in doc["traceEvents"])
    m_path = ob.save_metrics(str(tmp_path / "metrics.json"))
    assert "dslsh_queries_total" in json.loads(open(m_path).read())
    assert "# TYPE dslsh_query_latency_seconds histogram" in ob.prometheus()


STAGE_SCOPES = ("dslsh.hash", "dslsh.gather", "dslsh.dedup", "dslsh.compact",
                "dslsh.topk")


def test_query_programs_carry_stage_scopes():
    """The compiled query program of a routed grid and of a single
    deployment holds each ``dslsh.<stage>`` scope in its ``op_name``
    metadata (the grid's also ``dslsh.route`` and ``dslsh.merge``), and
    lowering it under an active Obs gives the same program."""
    cfg = _cfg(backend="reference")  # the staged stages 3-5 the chip runs
    data = jax.random.uniform(jax.random.PRNGKey(0), (256, 16))
    q = jax.random.uniform(jax.random.PRNGKey(1), (8, 16))
    dm, dc = np.zeros((2,), bool), np.zeros((2, 1), bool)
    grid = dslsh.build(jax.random.PRNGKey(2), data, cfg,
                       dslsh.grid(nu=2, p=1, routed=True))
    single = dslsh.build(jax.random.PRNGKey(2), data, cfg, dslsh.single())
    g, s = grid._grid_fn(None), single._single_fn()
    lowered = {
        "grid": g.func.lower(*g.args, q, dm, dc),
        "single": s.func.lower(*s.args, q),
    }
    want = {"grid": STAGE_SCOPES + ("dslsh.route", "dslsh.merge"),
            "single": STAGE_SCOPES}
    for name, low in lowered.items():
        scopes = set(re.findall(r'op_name="[^"]*?(dslsh\.\w+)',
                                low.compile().as_text()))
        assert scopes == set(want[name]), name
    with obs.Obs().activate():
        traced = g.func.lower(*g.args, q, dm, dc)
    assert (traced.as_text(debug_info=True)
            == lowered["grid"].as_text(debug_info=True))


def test_inner_probe_path_counter():
    """Each query program counts, as it traces, the inner-layer probe it
    holds: ``slab`` where the backend has ``inner_probe`` and no delta is
    given (the compiled pallas backend; on the CPU, the reference backend
    with the kernel interpreted), ``element`` for the reference backend,
    the interpreted pallas backend and a delta query."""
    import functools

    from repro import stream
    from repro.core import pipeline
    from repro.kernels.inner_probe import ops as ip_ops

    data = jax.random.uniform(jax.random.PRNGKey(0), (256, 16))
    q = data[:8]
    cfg = _cfg(backend="reference")
    idx = slsh.build_index(jax.random.PRNGKey(1), data, cfg)
    sidx = stream.insert_batch(
        stream.stream_init(jax.random.PRNGKey(1), data[:200], cfg,
                           capacity=256, delta_cap=56),
        data[200:], cfg,
    )

    def paths(fn, run=True):
        """Path counts one fresh trace of ``fn(q)`` adds."""
        before = {p: obs.inner_probe_count(p) for p in ("slab", "element")}
        (jax.jit(fn) if run else functools.partial(jax.eval_shape, fn))(q)
        return {p: obs.inner_probe_count(p) - n for p, n in before.items()}

    # compiled pallas: traced only, its Mosaic kernels need the chip
    compiled = cfg.replace(backend="pallas", interpret=False)
    assert pipeline.get_backend("pallas", compiled).inner_probe is not None
    assert paths(lambda x: slsh.query_batch(idx, data, x, compiled),
                 run=False) == {"slab": 1, "element": 0}
    probe = functools.partial(ip_ops.inner_probe, interpret=True)
    pipeline.register_backend(
        "_slab_obs", pipeline.get_backend("reference")._replace(inner_probe=probe)
    )
    try:
        slab = cfg.replace(backend="_slab_obs")
        assert paths(lambda x: slsh.query_batch(idx, data, x, slab)) == {
            "slab": 1, "element": 0}
        assert paths(lambda x: stream.query_batch(sidx, x, slab)) == {
            "slab": 0, "element": 1}
    finally:
        pipeline._BACKENDS.pop("_slab_obs", None)
    for other in (cfg, cfg.replace(backend="pallas")):
        assert paths(lambda x: slsh.query_batch(idx, data, x, other)) == {
            "slab": 0, "element": 1}


def test_instrumented_chunked_build_spans_and_index_bytes():
    """An instrumented out-of-core build records the §13 build-stage spans
    (hash -> sort_runs -> merge -> heavy_inner) inside index.build, and
    the memory accountant feeds dslsh_index_bytes{component,cell}."""
    cfg = _cfg(build_chunk=64, build_mode="chunked")
    data = jax.random.uniform(jax.random.PRNGKey(0), (300, 16))
    ob = obs.Obs()
    idx = dslsh.build(jax.random.PRNGKey(2), data, cfg, dslsh.single(), obs=ob)
    names = {e["name"] for e in ob.tracer.events}
    assert {"index.build", "build.hash", "build.sort_runs", "build.merge",
            "build.heavy_inner"} <= names
    top = next(e for e in ob.tracer.events if e["name"] == "index.build")
    for e in ob.tracer.events:
        if e["name"].startswith("build."):
            assert e["ts"] >= top["ts"]
            assert e["ts"] + e["dur"] <= top["ts"] + top["dur"] + 1.0
    snap = ob.snapshot()
    gauges = snap["dslsh_index_bytes"]["values"]
    want = idx.memory_report().per_cell
    for name, b in want.items():
        assert gauges[f'cell="0/0",component="{name}"'] == float(b)
    assert gauges['cell="0/0",component="data"'] == 300 * 16 * 4.0
    # the instrumented chunked build answers queries identically to an
    # uninstrumented monolithic build (spans never change results)
    bare = dslsh.build(
        jax.random.PRNGKey(2), data, cfg.replace(build_mode="monolithic"),
        dslsh.single(),
    )
    q = jax.random.uniform(jax.random.PRNGKey(1), (8, 16))
    np.testing.assert_array_equal(
        np.asarray(idx.with_obs(None).query(q).knn_idx),
        np.asarray(bare.query(q).knn_idx),
    )


def test_instrumented_payload_query_counts_misses():
    """A compressed-payload query under obs feeds the rerank-miss counter
    (zero at default budgets — the §13 exactness certificate)."""
    cfg = _cfg(payload="f16", c_comp=64, c_rerank=64)
    data = jax.random.uniform(jax.random.PRNGKey(0), (256, 16))
    q = jax.random.uniform(jax.random.PRNGKey(1), (16, 16))
    ob = obs.Obs(trace=False)
    idx = dslsh.build(jax.random.PRNGKey(2), data, cfg, dslsh.single(), obs=ob)
    res = idx.query(q)
    snap = ob.snapshot()
    assert snap["dslsh_rerank_misses_total"]["values"][""] == float(
        res.rerank_miss_total
    )


def test_routed_grid_populates_routing_metrics():
    cfg = _cfg()
    data = jax.random.uniform(jax.random.PRNGKey(3), (256, 16))
    q = jax.random.uniform(jax.random.PRNGKey(4), (32, 16))
    ob = obs.Obs(trace=False)  # metrics-only: grid path stays jitted
    idx = dslsh.build(
        jax.random.PRNGKey(5), data, cfg,
        dslsh.grid(nu=2, p=2, routed=True), obs=ob,
    )
    idx.query(q)
    snap = ob.snapshot()
    assert snap["dslsh_routed_frac"]["values"][""]["count"] == 1
    cells = snap["dslsh_routed_queries_per_cell_total"]["values"]
    assert set(cells) == {f'cell="{j}/{c}"' for j in range(2) for c in range(2)}
    assert sum(cells.values()) > 0


def test_disabled_obs_query_overhead_within_5_percent():
    """The obs_overhead gate's testable form: an instrumented-but-disabled
    handle (sharing the bare handle's compile cache) pays at most 5% on
    ``Index.query`` — one attribute check and one ContextVar.get."""
    cfg = _cfg()
    data = jax.random.uniform(jax.random.PRNGKey(6), (512, 16))
    q = jax.random.uniform(jax.random.PRNGKey(7), (64, 16))
    bare = dslsh.build(jax.random.PRNGKey(8), data, cfg, dslsh.single())
    inst = bare.with_obs(obs.Obs.disabled())  # shares _compiled
    for _ in range(3):  # warm both paths
        jax.block_until_ready(bare.query(q).knn_idx)
        jax.block_until_ready(inst.query(q).knn_idx)
    ratios = []
    for _ in range(40):
        t0 = clock.monotonic()
        jax.block_until_ready(bare.query(q).knn_idx)
        t1 = clock.monotonic()
        jax.block_until_ready(inst.query(q).knn_idx)
        t2 = clock.monotonic()
        ratios.append((t2 - t1) / max(t1 - t0, 1e-9))
    med = float(np.median(ratios))
    assert med <= 1.05, f"disabled-path overhead {med:.3f}x exceeds 1.05x"


# ------------------------------------------------------- elastic (§14) obs


def test_elastic_spans_nest_under_controller_tick():
    """A tick that rebalances records the whole story on one track:
    ``elastic.rebalance`` (and the ``index.save`` / ``index.load``
    migration spans inside it) nests by time containment under
    ``elastic.tick``."""
    import chaos
    from repro.runtime import elastic as elastic_mod

    ob = obs.Obs()
    cl = chaos.make_cluster(seed=20, replication=2, obs=ob)
    ctl = elastic_mod.ElasticController(
        cl.elastic,
        elastic_mod.ElasticConfig(
            deadline_s=1.0, repair_ticks=2, scale_ticks=99
        ),
    )
    victim = cl.cell_devices(*cl.replicated_cell())[0]
    runner = chaos.ChaosRunner(
        cl, ctl, chaos.ChaosSchedule.kill_device(victim, t=1.0), dt=1.0
    )
    records = runner.run(6)
    assert any(r.report.rebalanced for r in records)
    names = [e["name"] for e in ob.tracer.events]
    assert "elastic.tick" in names and "elastic.rebalance" in names
    assert "index.save" in names and "index.load" in names
    reb = next(e for e in ob.tracer.events if e["name"] == "elastic.rebalance")
    ticks = [e for e in ob.tracer.events if e["name"] == "elastic.tick"]
    host = [
        t for t in ticks
        if t["ts"] <= reb["ts"]
        and reb["ts"] + reb["dur"] <= t["ts"] + t["dur"] + 1.0
    ]
    assert host, "elastic.rebalance must nest inside its elastic.tick"
    for name in ("index.save", "index.load"):
        e = next(ev for ev in ob.tracer.events if ev["name"] == name)
        assert e["ts"] >= reb["ts"]
        assert e["ts"] + e["dur"] <= reb["ts"] + reb["dur"] + 1.0


def test_elastic_counters_match_chaos_ground_truth():
    """The §14 counters are exact, not samples: failovers, degraded
    batches, migrated cells, and the replica gauge all equal what the
    chaos runner's records say actually happened."""
    import chaos
    from repro.runtime import elastic as elastic_mod

    ob = obs.Obs(trace=False)
    cl = chaos.make_cluster(seed=21, replication=2, obs=ob)
    ctl = elastic_mod.ElasticController(
        cl.elastic,
        elastic_mod.ElasticConfig(
            deadline_s=1.0, repair_ticks=3, scale_ticks=99
        ),
    )
    victim_cell = cl.replicated_cell()
    victim = cl.cell_devices(*victim_cell)[0]
    runner = chaos.ChaosRunner(
        cl, ctl, chaos.ChaosSchedule.kill_device(victim, t=1.0), dt=1.0
    )
    records = runner.run(8)
    snap = ob.snapshot()

    expected_failovers: dict = {}
    for r in records:
        for j, c in r.result.failover_cells:
            k = f'cell="{j}/{c}"'
            expected_failovers[k] = expected_failovers.get(k, 0) + 1
    assert expected_failovers, "scenario produced no failovers to check"
    assert snap["dslsh_failovers_total"]["values"] == {
        k: float(v) for k, v in expected_failovers.items()
    }

    swaps = [r for r in records if r.report.rebalanced]
    assert len(swaps) == 1
    assert snap["dslsh_rebalances_total"]["values"][""] == float(len(swaps))
    assert snap["dslsh_cells_migrated_total"]["values"][""] == float(
        sum(r.report.migrated_cells for r in swaps)
    )
    assert snap["dslsh_epoch"]["values"][""] == float(records[-1].epoch)
    # replica gauge reflects the last tick's live counts
    last_live = snap["dslsh_replicas"]["values"]
    plan = cl.elastic.index.plan
    for j in range(plan.replicas.shape[0]):
        for c in range(plan.replicas.shape[1]):
            assert last_live[f'cell="{j}/{c}"'] == float(plan.replicas[j, c])
    assert "dslsh_degraded_queries_total" not in snap  # replica covered it


def test_elastic_instrumented_equals_uninstrumented():
    """Instrumentation never changes a bit: the same chaos scenario with
    and without an obs bundle yields identical results step by step."""
    import chaos
    from repro.runtime import elastic as elastic_mod

    def run(obs_bundle):
        cl = chaos.make_cluster(seed=22, replication=2, obs=obs_bundle)
        ctl = elastic_mod.ElasticController(
            cl.elastic,
            elastic_mod.ElasticConfig(
                deadline_s=1.0, repair_ticks=2, scale_ticks=99
            ),
        )
        victim = cl.cell_devices(*cl.replicated_cell())[0]
        runner = chaos.ChaosRunner(
            cl, ctl, chaos.ChaosSchedule.kill_device(victim, t=1.0), dt=1.0
        )
        return runner.run(6)

    instrumented = run(obs.Obs())
    bare = run(None)
    assert len(instrumented) == len(bare)
    for a, b in zip(instrumented, bare):
        assert a.epoch == b.epoch
        assert a.result.failover_cells == b.result.failover_cells
        assert a.result.lost_cells == b.result.lost_cells
        np.testing.assert_array_equal(
            np.asarray(a.result.result.knn_dist),
            np.asarray(b.result.result.knn_dist),
        )
        np.testing.assert_array_equal(
            np.asarray(a.result.result.knn_idx),
            np.asarray(b.result.result.knn_idx),
        )
        np.testing.assert_array_equal(
            np.asarray(a.result.result.routed),
            np.asarray(b.result.result.routed),
        )
