"""One typed DSLSH handle — the ``repro.dslsh`` Deployment API.

The paper's system is a *service*: build the stratified-LSH deployment
once, then answer latency-critical AHE queries against it (§3, Fig. 2).
This module is that service's one front door (DESIGN.md §11): a frozen
:class:`Deployment` descriptor says *where* the index runs —

* :func:`single` — one shard, one device (the paper's single-node path),
* :func:`grid` — the nu x p cell grid simulated on one device (benchmark
  path; optional §10 routing + replication),
* :func:`mesh` — the same grid shard_mapped over a real device mesh,
* :func:`streaming` — the online deployment: delta-segment ingestion,
  automatic compaction, retention eviction (DESIGN.md §9),

and one typed handle runs the lifecycle: ``index = dslsh.build(key, data,
cfg, deploy)``, ``index.query(q)`` (always a single
:class:`~repro.core.distributed.DistributedQueryResult`, whatever the
deployment), ``index.ingest(xs, ts)`` / ``index.compact()`` for streaming
deployments, and ``index.save(path)`` / :func:`load` for persistence
(``checkpoint/store.py`` underneath).

Configuration is composed, not flat: :func:`make_config` combines a
:class:`~repro.core.pipeline.FamilyConfig`,
:class:`~repro.core.pipeline.BudgetConfig`, and
:class:`~repro.core.pipeline.RuntimeConfig` into the validated
:class:`~repro.core.pipeline.SLSHConfig` every execution path shares.

>>> import jax
>>> from repro import dslsh
>>> cfg = dslsh.make_config(m_out=8, L_out=4, m_in=4, L_in=2, alpha=0.05,
...                         k=3, val_lo=0.0, val_hi=1.0, c_max=16, c_in=8,
...                         h_max=2, p_max=32)
>>> data = jax.random.uniform(jax.random.PRNGKey(0), (64, 8))
>>> index = dslsh.build(jax.random.PRNGKey(1), data, cfg, dslsh.grid(nu=2, p=2))
>>> res = index.query(data[:4])
>>> [int(i) for i in res.knn_idx[:, 0]]  # each point finds itself first
[0, 1, 2, 3]
>>> res.comparisons.shape  # per-(node, core, query) counters, any deployment
(2, 2, 4)
>>> res.overflow_cells  # 0 certifies the compacted result is exact (§3)
0
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as obs_mod
from repro.checkpoint import store as ckpt_store
from repro.core import distributed as D
from repro.core import hashing, pipeline, routing, slsh, tables
from repro.core.distributed import (  # noqa: F401  (re-exported public API)
    DistributedQueryResult,
    Grid,
    pad_to_multiple,
    pknn_query,
)
from repro.core.pipeline import (  # noqa: F401  (re-exported public API)
    BudgetConfig,
    ConfigError,
    FamilyConfig,
    RuntimeConfig,
    SLSHConfig,
)
from repro.runtime import memory as memory_mod
from repro.runtime import payload as payload_mod
from repro.stream import delta as delta_mod
from repro.stream import shard as shard_mod

__all__ = [
    "BudgetConfig",
    "ConfigError",
    "Deployment",
    "DistributedQueryResult",
    "FamilyConfig",
    "Grid",
    "Index",
    "RuntimeConfig",
    "SLSHConfig",
    "build",
    "grid",
    "load",
    "make_config",
    "mesh",
    "pad_to_multiple",
    "pknn_query",
    "single",
    "streaming",
]

_KINDS = ("single", "grid", "mesh", "streaming")


def make_config(
    family: FamilyConfig | None = None,
    budget: BudgetConfig | None = None,
    runtime: RuntimeConfig | None = None,
    **overrides,
) -> SLSHConfig:
    """Compose a validated :class:`SLSHConfig` from its three parts.

    Flat field names in ``overrides`` route to the matching sub-config (the
    migration path from the deprecated flat ``SLSHConfig(...)``); every
    value passes the sub-config ``__post_init__`` checks, so broken
    combinations fail here with an actionable :class:`ConfigError` instead
    of silently mis-answering queries later.

    >>> make_config(FamilyConfig(m_out=16, L_out=8), BudgetConfig(k=5)).k
    5
    """
    return SLSHConfig.compose(family, budget, runtime, **overrides)


# ------------------------------------------------------------- deployments


@dataclasses.dataclass(frozen=True)
class Deployment:
    """Frozen descriptor of *where* a DSLSH index runs (DESIGN.md §11).

    Build one with :func:`single`, :func:`grid`, :func:`mesh`, or
    :func:`streaming` rather than by hand — the constructors fill the
    fields that matter per kind and :meth:`__post_init__` rejects
    inconsistent combinations with actionable errors.
    """

    kind: str
    nu: int = 1  # nodes (mesh axis "data")
    p: int = 1  # cores per node (mesh axis "model")
    replication: int = 1  # §10 replica factor for hot cells
    routed: bool = False  # §10 key→cell routing (bit-exact)
    route_bits: int = routing.DEFAULT_BITS
    reducer: str = "allgather"  # mesh Reducer: "allgather" | "tree"
    # deadline-degradation levels ((min_budget_s, max_cells), ...) consumed
    # by query(budget=...) — requires ``routed``
    degrade: tuple | None = None
    # streaming knobs (DESIGN.md §9)
    node_capacity: int | None = None
    delta_cap: int = 64
    retention_s: float = float("inf")
    # the jax device mesh (kind="mesh" only; never serialized)
    mesh: object | None = dataclasses.field(default=None, compare=False)

    def __post_init__(self):
        pipeline._require(
            self.kind in _KINDS,
            f"unknown deployment kind {self.kind!r}; one of {_KINDS}",
        )
        pipeline._require(
            self.nu >= 1 and self.p >= 1,
            f"nu={self.nu}, p={self.p}: the cell grid needs at least one"
            " node and one core",
        )
        pipeline._require(
            self.replication >= 1,
            f"replication={self.replication}: replica counts start at 1",
        )
        pipeline._require(
            self.replication == 1 or self.routed or self.kind == "mesh",
            f"replication={self.replication} without routed=True: replica"
            " placement rides the §10 routing plan — pass routed=True (the"
            " routed query stays bit-identical to the broadcast one)",
        )
        pipeline._require(
            not self.degrade or self.routed,
            "degrade levels require routed=True (degradation caps the"
            " cells the §10 router probes)",
        )
        pipeline._require(
            self.reducer in ("allgather", "tree"),
            f"unknown reducer {self.reducer!r}; one of ('allgather', 'tree')",
        )
        if self.kind == "streaming":
            pipeline._require(
                self.node_capacity is not None and self.node_capacity >= 1,
                "streaming deployments need node_capacity (the fixed"
                " per-node store size, >= warmup shard size)",
            )
            pipeline._require(
                self.delta_cap >= 1,
                f"delta_cap={self.delta_cap}: each node needs at least one"
                " delta slot to ingest into",
            )
        if self.kind == "mesh":
            pipeline._require(
                self.mesh is not None,
                "mesh deployments need the jax device mesh: pass"
                " dslsh.mesh(make_local_mesh(nu, p), ...)",
            )

    @property
    def grid(self) -> Grid:
        """The nu x p cell grid this deployment maps onto."""
        return Grid(nu=self.nu, p=self.p)

    @property
    def cells(self) -> int:
        """Total SLSH cells (the paper's nu*p)."""
        return self.nu * self.p


def single() -> Deployment:
    """One shard on one device — the paper's single-node path.

    >>> single().cells
    1
    """
    return Deployment(kind="single")


def grid(
    nu: int = 1,
    p: int = 1,
    *,
    replication: int = 1,
    routed: bool | None = None,
    route_bits: int = routing.DEFAULT_BITS,
    degrade: tuple | None = None,
) -> Deployment:
    """The nu x p cell grid simulated on one device (benchmark path).

    ``routed=True`` builds a §10 key→cell routing plan at build time and
    routes every query batch only to the cells its probe keys can land in —
    bit-identical results, fewer cells visited. ``replication > 1``
    replicates hot cells (implies ``routed``); ``degrade`` declares
    deadline-degradation levels for ``query(budget=...)``.

    >>> grid(nu=2, p=4, replication=2).routed
    True
    """
    if routed is None:
        routed = replication > 1 or degrade is not None
    return Deployment(
        kind="grid", nu=nu, p=p, replication=replication, routed=routed,
        route_bits=route_bits, degrade=degrade,
    )


def mesh(
    device_mesh,
    *,
    reducer: str = "allgather",
    routed: bool = False,
    route_bits: int = routing.DEFAULT_BITS,
    degrade: tuple | None = None,
) -> Deployment:
    """The cell grid shard_mapped over a real jax device mesh.

    ``device_mesh`` must carry ``data`` and ``model`` axes (see
    ``launch.mesh``); an optional leading ``rep`` axis replicates the index
    and row-shards query batches across replicas (§10). The grid shape is
    read off the mesh axes.
    """
    nu = int(device_mesh.shape["data"])
    p = int(device_mesh.shape["model"])
    rep = int(device_mesh.shape.get("rep", 1))
    return Deployment(
        kind="mesh", nu=nu, p=p, replication=rep, routed=routed,
        route_bits=route_bits, reducer=reducer, degrade=degrade,
        mesh=device_mesh,
    )


def streaming(
    nu: int = 1,
    p: int = 1,
    *,
    node_capacity: int,
    delta_cap: int = 64,
    retention_s: float = float("inf"),
    routed: bool = True,
    route_bits: int = routing.DEFAULT_BITS,
) -> Deployment:
    """The online deployment: ingest, auto-compact, evict (DESIGN.md §9).

    ``node_capacity`` fixes each node's store size (must cover its warmup
    shard); ``delta_cap`` sizes the append-only segments; windows older
    than ``retention_s`` are evicted during compaction. Routing is on by
    default — it is bit-exact for streaming too (delta segments inherit
    their cell's placement, §10).

    >>> streaming(nu=2, node_capacity=256).kind
    'streaming'
    """
    return Deployment(
        kind="streaming", nu=nu, p=p, routed=routed, route_bits=route_bits,
        node_capacity=node_capacity, delta_cap=delta_cap,
        retention_s=retention_s,
    )


# ------------------------------------------------------------------ handle


class Index:
    """The one typed DSLSH handle (DESIGN.md §11).

    Built by :func:`build` (or :func:`load`); holds the deployment
    descriptor, the composed config, and the deployment-specific state, and
    answers every lifecycle call:

    * :meth:`query` — always returns a single
      :class:`DistributedQueryResult`, whatever the deployment.
    * :meth:`ingest` / :meth:`compact` — streaming deployments only.
    * :meth:`save` — persist to a directory; :func:`load` restores.

    The handle layers strictly: handle -> deployment dispatch -> the staged
    pipeline (``core/pipeline.py``). It adds no math of its own, so every
    result is bit-identical to the underlying execution path.

    ``obs`` binds a :class:`repro.obs.Obs` bundle: lifecycle calls then
    record spans and the query path feeds the metrics registry
    (latency, comparisons, overflow, routed_frac — DESIGN.md §12).
    Observability is handle state, never config state: ``SLSHConfig``
    stays a hashable jit-cache key and serializes unchanged.
    """

    def __init__(
        self,
        deploy: Deployment,
        cfg: SLSHConfig,
        state: dict,
        obs: obs_mod.Obs | None = None,
    ):
        self.deploy = deploy
        self.cfg = cfg
        self._state = state
        self._compiled: dict = {}
        self._obs = obs

    # ------------------------------------------------------------- facts

    @property
    def grid(self) -> Grid:
        """The deployment's cell grid."""
        return self.deploy.grid

    @property
    def plan(self) -> routing.RoutingPlan | None:
        """The §10 routing plan (None for unrouted deployments)."""
        return self._state.get("plan")

    @property
    def pipeline_index(self):
        """The underlying pipeline state, for read-only introspection
        (e.g. ``heavy.overflowed``): the ``SLSHIndex`` (stacked ``(nu, p)``
        for grid/mesh) or, for streaming, the per-node state list."""
        if self.deploy.kind == "streaming":
            return self._state["core"].state
        return self._state["index"]

    def n_index(self) -> int:
        """Points queryable right now."""
        if self.deploy.kind == "streaming":
            return self._state["core"].n_index()
        return int(self._state["data"].shape[0])

    def memory_report(self) -> memory_mod.MemoryReport:
        """Per-cell byte accounting of the resident index (DESIGN.md §13).

        Decomposes tables/heavy/inner/data/payload bytes per (node, core)
        cell from shape metadata alone — no sync. Batch deployments only;
        streaming state lives in mutable per-node delta segments whose
        occupancy the ingest reports already track.
        """
        pipeline._require(
            self.deploy.kind != "streaming",
            "memory_report covers batch deployments — streaming capacity"
            " is tracked live by ingest/compact reports (DESIGN.md §9)",
        )
        cells = (
            (1, 1) if self.deploy.kind == "single"
            else (self.deploy.nu, self.deploy.p)
        )
        return memory_mod.index_report(
            self._state["index"], self._state["data"],
            self.cfg.payload, cells,
        )

    # ------------------------------------------------------------- query

    def query(
        self,
        queries,
        *,
        budget: float | None = None,
        max_cells: int | None = None,
        drop_mask=None,
        drop_cells=None,
    ) -> DistributedQueryResult:
        """Resolve a query batch -> one typed :class:`DistributedQueryResult`.

        ``budget`` (remaining latency seconds) maps through the
        deployment's ``degrade`` levels to a probe-cell cap; ``max_cells``
        caps it directly (both require a routed deployment and are
        approximate by design — the paper's latency-first mode).
        ``drop_mask`` (nu,) excludes straggler nodes from the Reducer
        (grid/mesh deployments). ``drop_cells`` (nu, p) excludes individual
        lost cells (grid deployments — the elastic failover channel,
        DESIGN.md §14): dropped cells flip off in ``res.routed`` so the
        degradation is flagged, never silent.

        With an obs bundle bound (``build(..., obs=...)``) or ambiently
        activated, the call records an ``index.query`` span around the same
        program an unbound handle runs; with metrics on, it also syncs the
        result and feeds the query metrics (latency, comparisons, overflow,
        routed_frac, per-cell routed load — DESIGN.md §12). Unbound handles
        take the bare fast path after one check.
        """
        queries = jnp.asarray(queries)
        if budget is not None:
            pipeline._require(
                self.deploy.degrade is not None,
                "query(budget=...) needs degrade levels on the deployment:"
                " dslsh.grid(..., routed=True, degrade=((0.05, None),"
                " (0.0, 4)))",
            )
            cap = routing.degrade_max_cells(budget, self.deploy.degrade)
            max_cells = cap if max_cells is None else min(max_cells, cap or max_cells)
        if max_cells is not None:
            pipeline._require(
                self.plan is not None,
                "max_cells requires a routed deployment (dslsh.grid(...,"
                " routed=True) or dslsh.mesh(..., routed=True)) — the cap"
                " rides the §10 routing plan",
            )
        if drop_cells is not None:
            pipeline._require(
                self.deploy.kind == "grid",
                "drop_cells (per-cell failover drops) applies to grid"
                " deployments — nodes on other deployments drop whole via"
                " drop_mask",
            )
        ob = self._obs if self._obs is not None else obs_mod.get_active()
        if ob is None or not ob.enabled:
            return self._query_impl(queries, max_cells, drop_mask, drop_cells)
        with ob.activate():
            with ob.span(
                "index.query", deployment=self.deploy.kind,
                queries=int(queries.shape[0]),
            ) as sp:
                res = self._query_impl(queries, max_cells, drop_mask, drop_cells)
                if ob.metrics is not None:
                    # the latency histogram is end to end; a trace alone
                    # reads device time off the profiler (§12.1)
                    jax.block_until_ready(res)
        if ob.metrics is not None:
            self._record_query_metrics(ob, res, sp.dur_s)
        return res

    def _query_impl(
        self, queries, max_cells: int | None, drop_mask, drop_cells=None
    ) -> DistributedQueryResult:
        """Deployment dispatch behind :meth:`query` (validation done)."""
        kind = self.deploy.kind
        if kind == "single":
            pipeline._require(
                drop_mask is None,
                "drop_mask only applies to grid/mesh deployments (a single"
                " shard has no straggler nodes to drop)",
            )
            return self._single_fn()(queries)
        if kind == "grid":
            dm = (
                jnp.zeros((self.deploy.nu,), bool)
                if drop_mask is None
                else jnp.asarray(drop_mask)
            )
            # drop_cells is always passed as an array so the jitted program
            # is knob-independent: the no-drop query shares the compiled
            # executable (and stays bit-identical — the masks are no-ops
            # when all-False; tests/test_compile_cache.py)
            dc = (
                jnp.zeros((self.deploy.nu, self.deploy.p), bool)
                if drop_cells is None
                else jnp.asarray(drop_cells)
            )
            return self._grid_fn(max_cells)(queries, dm, dc)
        if kind == "mesh":
            dm = None if drop_mask is None else jnp.asarray(drop_mask)
            return D.mesh_query(
                self.deploy.mesh, self._state["index"], self._state["data"],
                queries, self.cfg, self.grid, reducer=self.deploy.reducer,
                drop_mask=dm, plan=self.plan, max_cells=max_cells,
            )
        # streaming
        pipeline._require(
            drop_mask is None and max_cells is None,
            "streaming deployments answer with their live cells — drop_mask"
            " / max_cells degradation applies to grid/mesh deployments",
        )
        return self._state["core"].query(queries)

    def _record_query_metrics(
        self, ob: obs_mod.Obs, res: DistributedQueryResult, dur_s: float
    ) -> None:
        """Feed the §12 query metrics from one already-computed result."""
        m = ob.metrics
        kind = self.deploy.kind
        m.histogram(
            "dslsh_query_latency_seconds",
            "end-to-end Index.query wall time (synced)",
        ).labels(deployment=kind).observe(dur_s)
        m.counter(
            "dslsh_queries_total", "Index.query batches answered"
        ).labels(deployment=kind).inc()
        comps = np.asarray(res.comparisons)  # (nu, p, Q)
        m.counter(
            "dslsh_comparisons_total",
            "unique candidates scanned across all cells (paper's cost"
            " measure)",
        ).inc(float(comps.sum()))
        comp_hist = m.histogram(
            "dslsh_query_comparisons",
            "per-query max unique candidates scanned in any one cell",
            buckets=obs_mod.metrics.COUNT_BUCKETS,
        )
        for v in comps.max(axis=(0, 1)):
            comp_hist.observe(float(v))
        overflow = np.asarray(res.compaction_overflow)
        m.counter(
            "dslsh_compaction_overflow_total",
            "unique survivors beyond c_comp — non-zero means results are"
            " budget-truncated (DESIGN.md §3)",
        ).inc(float(overflow.sum()))
        if res.rerank_misses is not None:
            m.counter(
                "dslsh_rerank_misses_total",
                "compressed-payload shortlist misses — non-zero means the"
                " quantized L1 pass may have excluded a true neighbour"
                " (raise c_rerank; DESIGN.md §13)",
            ).inc(float(np.asarray(res.rerank_misses).sum()))
        m.histogram(
            "dslsh_routed_frac",
            "fraction of (cell, query) pairs the §10 router visited",
            buckets=obs_mod.log_buckets(0.01, 1.0, per_decade=8),
        ).observe(float(res.routed_frac))
        routed = np.asarray(res.routed)  # (nu, p, Q)
        per_cell = routed.sum(axis=2)
        cell_counter = m.counter(
            "dslsh_routed_queries_per_cell_total",
            "queries routed to each (node, core) cell — the load signal"
            " the routing plan's replicas balance",
        )
        for j in range(per_cell.shape[0]):
            for c in range(per_cell.shape[1]):
                cell_counter.labels(cell=f"{j}/{c}").inc(float(per_cell[j, c]))
        plan = self.plan
        if plan is not None and plan.r_max > 1:
            load = routing.device_load(plan, routed.transpose(2, 0, 1))
            dev_counter = m.counter(
                "dslsh_replica_routed_queries_total",
                "queries each replica device answered (replication load"
                " balance, §10)",
            )
            for d, v in enumerate(np.asarray(load)):
                dev_counter.labels(device=str(d)).inc(float(v))

    def with_obs(self, obs: obs_mod.Obs | None) -> "Index":
        """The same handle state bound to a (different) obs bundle —
        compiled query programs are shared, so instrumenting an existing
        index costs no recompile."""
        out = Index(self.deploy, self.cfg, self._state, obs)
        out._compiled = self._compiled
        return out

    def with_routing(
        self,
        *,
        replication: int = 1,
        route_bits: int = routing.DEFAULT_BITS,
        degrade: tuple | None = None,
    ) -> "Index":
        """A routed variant of this grid handle, sharing the built state.

        Builds the §10 key→cell map and replica placement from the already
        built cells (no re-hash of the data) and returns a new handle whose
        queries route — bit-identical results, fewer cells visited.
        """
        pipeline._require(
            self.deploy.kind == "grid",
            "with_routing derives a plan from a grid deployment — mesh"
            " and streaming deployments take routed=True at build time",
        )
        plan = routing.make_plan(
            self._state["index"], self.cfg, self.grid,
            replication=replication, bits=route_bits,
        )
        deploy = dataclasses.replace(
            self.deploy, routed=True, replication=replication,
            route_bits=route_bits, degrade=degrade,
        )
        return Index(deploy, self.cfg, {**self._state, "plan": plan}, self._obs)

    def query_with_stats(
        self, queries
    ) -> tuple[DistributedQueryResult, routing.RoutingStats]:
        """Routed-grid query + host-side :class:`routing.RoutingStats`
        (route mask, Reducer payload accounting, per-device load)."""
        pipeline._require(
            self.deploy.kind == "grid" and self.plan is not None,
            "query_with_stats needs a routed grid deployment"
            " (dslsh.grid(..., routed=True))",
        )
        return D.grid_query(
            self._state["index"], self._state["data"], jnp.asarray(queries),
            self.cfg, self.grid, plan=self.plan, return_stats=True,
        )

    def _payload(self) -> payload_mod.Payload | None:
        """The handle's quantized candidate payload, built once and cached
        (None for ``payload='f32'`` — exact rows serve directly)."""
        if "payload" not in self._compiled:
            self._compiled["payload"] = (
                None
                if self.cfg.payload == "f32"
                else payload_mod.make_payload(
                    self._state["data"], self.cfg.payload
                )
            )
        return self._compiled["payload"]

    def _single_fn(self):
        """``q -> result`` over this handle's state. The index, data and
        payload enter the jitted program as arguments: closed over, they
        would be baked into every compiled executable as constants."""
        if "q" not in self._compiled:
            state = (self._state["index"], self._state["data"], self._payload())
            cfg = self.cfg

            def run(st, q):
                index, data, payload = st
                obs_mod.count_retrace("single_query")
                res = pipeline.query_batch(index, data, q, cfg, payload=payload)
                return DistributedQueryResult(
                    res.knn_dist,
                    res.knn_idx,
                    res.comparisons[None, None],
                    res.compaction_overflow[None, None],
                    jnp.ones((1, 1, q.shape[0]), bool),
                    None if res.rerank_misses is None
                    else res.rerank_misses[None, None],
                )

            self._compiled["q"] = functools.partial(jax.jit(run), state)
        return self._compiled["q"]

    def _grid_fn(self, max_cells: int | None):
        """``(q, drop_mask, drop_cells) -> result``; the index, data and
        the plan's device-side occupancy map enter as arguments, as in
        :meth:`_single_fn` (the plan's placement fields are host-side
        numpy that shapes the trace)."""
        key = ("q", max_cells)
        if key not in self._compiled:
            host_plan = self.plan
            occupancy = None if host_plan is None else host_plan.occupancy
            state = (self._state["index"], self._state["data"], occupancy)
            cfg, g = self.cfg, self.grid

            def run(st, q, dm, dc):
                index, data, occ = st
                plan = None if occ is None else host_plan._replace(occupancy=occ)
                # count_retrace runs only while tracing: the §15 serving
                # pin reads this stage to prove steady state retraces
                # nothing after the ladder warmup
                obs_mod.count_retrace("grid_query")
                return D.grid_query(
                    index, data, q, cfg, g, plan=plan, max_cells=max_cells,
                    drop_mask=dm, drop_cells=dc,
                )

            self._compiled[key] = functools.partial(jax.jit(run), state)
        return self._compiled[key]

    # --------------------------------------------------------- streaming

    def _core(self) -> shard_mod.ShardedStream:
        pipeline._require(
            self.deploy.kind == "streaming",
            f"{self.deploy.kind!r} deployments are immutable — ingest /"
            " compact need dslsh.streaming(...) (build a fresh index to"
            " change batch deployments)",
        )
        return self._state["core"]

    def ingest(self, xs, ts: float = 0.0) -> shard_mod.IngestReport:
        """Ingest one batch of points stamped ``ts`` (streaming only).

        The Forwarder routes the batch to the next node round-robin; a node
        whose delta segment would overflow compacts (and, under the
        retention horizon, evicts) first. Returns the
        :class:`~repro.stream.shard.IngestReport` of what happened.
        """
        ob = self._obs if self._obs is not None else obs_mod.get_active()
        if ob is None or not ob.enabled:
            return self._core().ingest(xs, float(ts))
        with ob.activate(), ob.span("index.ingest", ts=float(ts)):
            return self._core().ingest(xs, float(ts))

    def compact(self, ts: float = 0.0) -> list:
        """Fold every node's delta segment into its base now (streaming
        only). Returns one ``(evicted, keep)`` pair per node — ``keep``
        (surviving old store rows, ascending; None when nothing was
        evicted) is the renumbering map for any per-point metadata the
        caller holds, exactly like ``IngestReport.keep``."""
        ob = self._obs if self._obs is not None else obs_mod.get_active()
        if ob is None or not ob.enabled:
            return self._core().compact_all(float(ts))
        with ob.activate(), ob.span("index.compact", ts=float(ts)):
            return self._core().compact_all(float(ts))

    def snapshot(self) -> "Index":
        """An RCU snapshot of this handle for ingest-while-serving
        (DESIGN.md §15).

        Batch deployments are immutable, so the snapshot is the handle
        itself. Streaming deployments get a new handle over a
        :meth:`~repro.stream.shard.ShardedStream.clone` of the core —
        the per-node state list is copied, every array and compiled
        program is shared — so the §15 front end can ingest into the
        snapshot aside and publish it with one epoch swap while
        in-flight queries keep the old state bit-exactly.
        """
        if self.deploy.kind != "streaming":
            return self
        state = dict(self._state)
        state["core"] = self._state["core"].clone()
        out = Index(self.deploy, self.cfg, state, self._obs)
        out._compiled = self._compiled  # shared jit cache: zero retraces
        return out

    # ----------------------------------------------------------- serving

    def frontend(self, cfg=None, **kw):
        """An async multi-tenant serving front end over this handle
        (DESIGN.md §15): admission control, micro-batch coalescing onto
        the ladder of static shapes, deadline-aware degradation, and
        (streaming) RCU ingest-while-serving. ``cfg`` is a
        :class:`repro.serve.frontend.FrontendConfig`; keywords pass
        through to :class:`repro.serve.frontend.ServeFrontend`.
        """
        from repro.serve import frontend as frontend_mod

        kw.setdefault("obs", self._obs)
        return frontend_mod.ServeFrontend(self, cfg, **kw)

    # ------------------------------------------------------- persistence

    def save(self, path: str) -> str:
        """Persist this index to ``path`` (a directory).

        Array state goes through ``checkpoint/store.py`` (atomic rename,
        per-leaf .npy); the deployment descriptor, config, and host-side
        cursors land in ``dslsh.json``. :func:`load` restores the handle;
        round-trips are bit-exact (tests/test_api.py).
        """
        with self._span("index.save", path=path):
            state, extra = _state_arrays(self)
            os.makedirs(path, exist_ok=True)
            ckpt_store.save({"state": state}, 0, path)
            meta = {
                "format": 1,
                "cfg": _cfg_dict(self.cfg),
                "deploy": _deploy_dict(self.deploy),
                "extra": extra,
            }
            with open(os.path.join(path, "dslsh.json"), "w") as f:
                json.dump(meta, f, indent=2)
            return path

    def _span(self, name: str, **args):
        """A span on the bound/ambient obs bundle (no-op when none)."""
        ob = self._obs if self._obs is not None else obs_mod.get_active()
        if ob is None:
            return obs_mod.NULL_SPAN
        return ob.span(name, **args)


# ------------------------------------------------------------- build / load


def build(
    key, data, cfg: SLSHConfig, deploy: Deployment, *, t0: float = 0.0,
    obs: obs_mod.Obs | None = None,
) -> Index:
    """Build a DSLSH index over ``data`` (n, d) for ``deploy`` -> :class:`Index`.

    ``key`` seeds the one root hash family every cell slices its tables
    from (the paper Root's broadcast). For grid/mesh deployments ``n`` must
    divide the cell grid — pad with :func:`pad_to_multiple` first. ``t0``
    stamps the warmup windows of a streaming deployment. ``obs`` binds an
    observability bundle: the build records an ``index.build`` span and
    the returned handle is instrumented (DESIGN.md §12).
    """
    if obs is not None and obs.enabled:
        with obs.activate(), obs.span(
            "index.build", deployment=deploy.kind, n=int(jnp.asarray(data).shape[0])
        ):
            out = _build_impl(key, data, cfg, deploy, t0=t0, obs=obs)
            jax.block_until_ready(out._state.get("index"))
            if obs.metrics is not None and deploy.kind != "streaming":
                out.memory_report().feed_gauges(obs.metrics)
            return out
    return _build_impl(key, data, cfg, deploy, t0=t0, obs=obs)


def _build_impl(
    key, data, cfg: SLSHConfig, deploy: Deployment, *, t0: float,
    obs: obs_mod.Obs | None,
) -> Index:
    data = jnp.asarray(data)
    n = data.shape[0]
    g = deploy.grid
    if deploy.kind != "single":
        pipeline._require(
            cfg.payload == "f32",
            f"payload={cfg.payload!r} (compressed candidate payload) rides"
            " the single-shard fused tail — grid/mesh/streaming"
            " deployments need payload='f32' (DESIGN.md §13)",
        )
        pipeline._require(
            cfg.L_out % deploy.p == 0,
            f"L_out={cfg.L_out} does not divide across p={deploy.p} cores"
            " (paper: each core owns L_out/p tables) — adjust L_out or p",
        )
        pipeline._require(
            n % g.nu == 0,
            f"n={n} does not divide across nu={g.nu} nodes — pad the"
            " dataset first (dslsh.pad_to_multiple(points, labels,"
            f" {g.cells}))",
        )
    if deploy.kind == "single":
        index = slsh.build_index(key, data, cfg)
        return Index(deploy, cfg, {"index": index, "data": data}, obs)
    if deploy.kind == "grid":
        index = D.simulate_build(key, data, cfg, g)
        state = {"index": index, "data": data}
        if deploy.routed:
            state["plan"] = routing.make_plan(
                index, cfg, g, replication=deploy.replication,
                bits=deploy.route_bits,
            )
        return Index(deploy, cfg, state, obs)
    if deploy.kind == "mesh":
        index = D.dslsh_build(deploy.mesh, key, data, cfg, g)
        state = {"index": index, "data": data}
        if deploy.routed:
            state["plan"] = routing.make_plan(
                index, cfg, g, replication=1, bits=deploy.route_bits
            )
        return Index(deploy, cfg, state, obs)
    # streaming
    core = shard_mod.ShardedStream(
        key, data, cfg, g,
        node_capacity=deploy.node_capacity, delta_cap=deploy.delta_cap,
        retention_s=deploy.retention_s, t0=t0, route=deploy.routed,
        route_bits=deploy.route_bits,
    )
    return Index(deploy, cfg, {"core": core}, obs)


def wrap_grid(
    index, data, cfg: SLSHConfig, grid_: Grid, plan=None,
    obs: obs_mod.Obs | None = None,
) -> Index:
    """Wrap a prebuilt ``simulate_build`` index into a grid-deployment
    handle (the bridge legacy call sites migrate through)."""
    deploy = Deployment(
        kind="grid", nu=grid_.nu, p=grid_.p, routed=plan is not None,
    )
    state = {"index": index, "data": jnp.asarray(data)}
    if plan is not None:
        state["plan"] = plan
    return Index(deploy, cfg, state, obs)


def wrap_single(
    index, data, cfg: SLSHConfig, obs: obs_mod.Obs | None = None
) -> Index:
    """Wrap a prebuilt ``slsh.build_index`` index into a single-shard
    handle (bridge for legacy call sites and the perf-gate benchmark)."""
    return Index(single(), cfg, {"index": index, "data": jnp.asarray(data)}, obs)


def load(path: str, *, device_mesh=None, obs: obs_mod.Obs | None = None) -> Index:
    """Restore an :class:`Index` saved by :meth:`Index.save`.

    Mesh deployments need the (unserializable) device mesh handed back in
    via ``device_mesh``; everything else restores from the directory
    alone. ``obs`` instruments the restored handle and records an
    ``index.load`` span around the restore.
    """
    with open(os.path.join(path, "dslsh.json")) as f:
        meta = json.load(f)
    cfg = SLSHConfig.compose(**meta["cfg"])
    dep = dict(meta["deploy"])
    retention = dep.get("retention_s")
    if retention is None:
        dep["retention_s"] = float("inf")
    if dep.get("degrade") is not None:
        dep["degrade"] = tuple(tuple(level) for level in dep["degrade"])
    if dep["kind"] == "mesh":
        pipeline._require(
            device_mesh is not None,
            "this index was saved from a mesh deployment; device meshes"
            " are not serializable — pass load(path,"
            " device_mesh=make_local_mesh(nu, p))",
        )
        dep["mesh"] = device_mesh
    deploy = Deployment(**dep)
    skeleton = _state_skeleton(deploy)
    if obs is not None and obs.enabled:
        with obs.activate(), obs.span("index.load", path=path):
            state = ckpt_store.restore({"state": skeleton}, 0, path)["state"]
            return _rehydrate(deploy, cfg, state, meta["extra"], obs)
    state = ckpt_store.restore({"state": skeleton}, 0, path)["state"]
    return _rehydrate(deploy, cfg, state, meta["extra"], obs)


# ----------------------------------------------------- persistence helpers


def _cfg_dict(cfg: SLSHConfig) -> dict:
    return {
        f.name: getattr(cfg, f.name) for f in dataclasses.fields(SLSHConfig)
    }


def _deploy_dict(deploy: Deployment) -> dict:
    out = {
        f.name: getattr(deploy, f.name)
        for f in dataclasses.fields(Deployment)
        if f.name != "mesh"
    }
    if not np.isfinite(out["retention_s"]):
        out["retention_s"] = None  # JSON has no inf
    return out


def _state_arrays(index: Index) -> tuple[dict, dict]:
    """(array pytree to checkpoint, host-side extras for the JSON sidecar)."""
    st = index._state
    if index.deploy.kind == "streaming":
        core: shard_mod.ShardedStream = st["core"]
        tree = {
            "nodes": list(core.state),
            "family": {"outer": core.family[0], "inner": core.family[1]},
        }
        return tree, {"rr": core.rr}
    tree = {"index": st["index"], "data": st["data"]}
    if st.get("plan") is not None:
        tree["plan"] = dict(st["plan"]._asdict())
    return tree, {}


def _skel_index() -> pipeline.SLSHIndex:
    """A structure-only SLSHIndex (dummy leaves) for checkpoint restore."""
    return pipeline.SLSHIndex(
        hashing.BitSampleParams(0, 0, 0),
        hashing.SignRPParams(0, 0),
        tables.TableSet(0, 0),
        tables.HeavyBuckets(0, 0, 0, 0, 0),
        0, 0, 0,
    )


def _state_skeleton(deploy: Deployment):
    if deploy.kind == "streaming":
        cell = shard_mod.CellState(
            _skel_index(), delta_mod.DeltaIndex(0, 0, 0, 0), 0
        )
        node = shard_mod.NodeState(0, 0, cell)
        return {
            "nodes": [node for _ in range(deploy.nu)],
            "family": {
                "outer": hashing.BitSampleParams(0, 0, 0),
                "inner": hashing.SignRPParams(0, 0),
            },
        }
    tree = {"index": _skel_index(), "data": 0}
    if deploy.routed:
        tree["plan"] = {
            "occupancy": 0, "replicas": 0, "heat": 0, "cell_device": 0
        }
    return tree


def _rehydrate(
    deploy: Deployment, cfg: SLSHConfig, state, extra: dict,
    obs: obs_mod.Obs | None = None,
) -> Index:
    if deploy.kind == "streaming":
        nodes = [jax.tree.map(jnp.asarray, nd) for nd in state["nodes"]]
        family = (
            jax.tree.map(jnp.asarray, state["family"]["outer"]),
            jax.tree.map(jnp.asarray, state["family"]["inner"]),
        )
        core = shard_mod.ShardedStream.from_state(
            nodes, family, cfg, deploy.grid,
            node_capacity=deploy.node_capacity, delta_cap=deploy.delta_cap,
            retention_s=deploy.retention_s, route=deploy.routed,
            route_bits=deploy.route_bits, rr=int(extra.get("rr", 0)),
        )
        return Index(deploy, cfg, {"core": core}, obs)
    index = jax.tree.map(jnp.asarray, state["index"])
    data = jnp.asarray(state["data"])
    if deploy.kind == "mesh":
        from jax.sharding import NamedSharding, PartitionSpec as P

        index = jax.tree.map(
            lambda a: jax.device_put(
                a, NamedSharding(deploy.mesh, P("data", "model"))
            ),
            index,
        )
        data = jax.device_put(
            data, NamedSharding(deploy.mesh, P("data", None))
        )
    new_state = {"index": index, "data": data}
    if deploy.routed and "plan" in state:
        p = state["plan"]
        new_state["plan"] = routing.RoutingPlan(
            occupancy=jnp.asarray(p["occupancy"]),
            replicas=np.asarray(p["replicas"]),
            heat=np.asarray(p["heat"]),
            cell_device=np.asarray(p["cell_device"]),
        )
    return Index(deploy, cfg, new_state, obs)
