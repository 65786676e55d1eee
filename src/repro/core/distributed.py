"""DSLSH — the paper's distributed SLSH system (§3), mapped to a JAX mesh.

Paper -> mesh mapping (DESIGN.md §2):
  * nu SLSH nodes, each owning O(n/nu) points  -> mesh axis ``data``
  * p cores per node, each owning L_out/p outer tables -> mesh axis ``model``
  * Root's hash-function broadcast -> same PRNG key everywhere; each core
    slices its own rows out of the full (L_out, m) family, so table t uses
    identical hash functions on every node (required for correctness).
  * Forwarder -> queries replicated to all cells — or, with a
    ``routing.RoutingPlan``, routed only to the cells their probe keys can
    land in (``grid_query(plan=...)`` / ``mesh_query(plan=...)``,
    DESIGN.md §10).
  * Reducer / Master -> top-K merges: all-gather (small K) or a ppermute
    tournament tree (any axis size); both implemented, selectable, and
    bit-identical including distance-tie resolution.

Two execution paths share the same per-cell functions, and both resolve to
the one typed :class:`DistributedQueryResult` (DESIGN.md §11):
  * ``dslsh_build`` + ``mesh_query`` — shard_map over a real device mesh
    (dry-run / production)
  * ``simulate_build`` + ``grid_query`` — vmap over the cell grid on one
    device (CPU benchmarks; the paper's #comparisons metric is
    device-count independent)

The positional-tuple entry points (``simulate_query``, ``dslsh_query``,
``simulate_query_routed``) are deprecated shims over those cores; hold a
``repro.dslsh`` Index instead.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import hashing, pipeline, routing, slsh, topk
from repro.obs.metrics import count_retrace


# --------------------------------------------------------------------- grid


@dataclasses.dataclass(frozen=True)
class Grid:
    nu: int  # nodes  (mesh axis "data")
    p: int  # cores  (mesh axis "model")

    @property
    def cells(self) -> int:
        """Total SLSH cells (one per (node, core) pair — the paper's nu*p)."""
        return self.nu * self.p


def pad_to_multiple(
    points, labels, multiple: int, sentinel: float = 1e9
):
    """Pad dataset so n divides the shard grid; pads never enter any K-NN
    (their coordinates are ``sentinel``-far, so with k <= n real points they
    always lose — tests/test_properties.py holds this as a property)."""
    n = points.shape[0]
    rem = (-n) % multiple
    if rem == 0:
        return points, labels, n
    pad_pts = np.full((rem, points.shape[1]), sentinel, points.dtype)
    pad_lab = np.zeros((rem,), labels.dtype)
    return (
        np.concatenate([points, pad_pts]),
        np.concatenate([labels, pad_lab]),
        n,
    )


# ---------------------------------------------------------------- per-cell


def _local_tables(cfg: slsh.SLSHConfig, p: int) -> int:
    assert cfg.L_out % p == 0, "L_out must divide across cores (paper: p < L_out)"
    return cfg.L_out // p


def cell_build(
    root_key: jax.Array,
    data_local: jax.Array,
    core_id: jax.Array,
    cfg: slsh.SLSHConfig,
    grid: Grid,
) -> slsh.SLSHIndex:
    """Build this cell's L_out/p tables over the node's data slice.

    The full (L_out, m) hash family is generated from the *root* key on every
    cell and each core keeps rows [core_id*L_loc, ...) — the SPMD form of the
    Root broadcasting the same family instances to all nodes. The index body
    itself is the shared ``pipeline.build_from_params`` builder, which takes
    the pre-sliced params instead of re-creating ``build_index``'s body.
    """
    l_loc = _local_tables(cfg, grid.p)
    d = data_local.shape[1]
    full, inner_params = pipeline.make_family(root_key, d, cfg)
    rows = core_id * l_loc + jnp.arange(l_loc)
    outer_params = hashing.BitSampleParams(
        full.dims[rows], full.thrs[rows], full.salts[rows]
    )
    return pipeline.build_from_params(data_local, outer_params, inner_params, cfg)


class CellResult(NamedTuple):
    knn_dist: jax.Array  # (Q, K) partial distances
    knn_idx: jax.Array  # (Q, K) GLOBAL indices (-1 pad)
    comparisons: jax.Array  # (Q,) unique candidates scanned in this cell
    # unique survivors beyond this cell's c_comp budget (DESIGN.md §3) —
    # carried alongside comparisons so no execution path truncates silently
    compaction_overflow: jax.Array  # (Q,)


class DistributedQueryResult(NamedTuple):
    """The one typed result every DSLSH query path returns (DESIGN.md §11).

    Whatever the deployment — single shard, simulated grid, real device
    mesh, or streaming — ``repro.dslsh`` queries (and the typed
    :func:`grid_query`/:func:`mesh_query` cores below) resolve to this
    NamedTuple: merged top-K neighbours plus the per-(node, core, query)
    counters that certify exactness (DESIGN.md §3) and routing behaviour
    (§10). Single-shard results use ``nu = p = 1``.
    """

    knn_dist: jax.Array  # (Q, K) merged distances, inf pad
    knn_idx: jax.Array  # (Q, K) merged GLOBAL indices, -1 pad
    comparisons: jax.Array  # (nu, p, Q) unique candidates scanned per cell
    compaction_overflow: jax.Array  # (nu, p, Q) survivors beyond c_comp
    # which (cell, query) pairs the Forwarder visited — all True for
    # broadcast deployments, the §10 route mask otherwise
    routed: jax.Array  # (nu, p, Q) bool
    # compressed-payload deployments only (None on the f32 path):
    # candidates excluded from the c_rerank shortlist whose approximate
    # distance came within the quantization error bound of the k-th exact
    # distance — counted, never silent; 0 everywhere certifies knn_idx
    # bit-identical to the f32 tail (DESIGN.md §13)
    rerank_misses: jax.Array | None = None  # (nu, p, Q) int32

    @property
    def routed_frac(self) -> float:
        """Fraction of (cell, query) pairs visited (1.0 = broadcast)."""
        return float(jnp.mean(self.routed.astype(jnp.float32)))

    @property
    def rerank_miss_total(self) -> int:
        """Total rerank-margin misses across cells and queries (0 for the
        f32 payload path — the shortlist rerank is then a no-op)."""
        if self.rerank_misses is None:
            return 0
        return int(jnp.sum(self.rerank_misses))

    @property
    def overflow_cells(self) -> int:
        """Count of (cell, query) partials whose c_comp budget overflowed
        (non-zero means the compacted result may not be exact — §3)."""
        return int(jnp.sum((self.compaction_overflow > 0).astype(jnp.int32)))

    @property
    def max_comparisons_per_cell(self) -> jax.Array:
        """Per-query max of comparisons over cells — the paper's
        per-processor work metric (its median is the headline number)."""
        return jnp.max(self.comparisons, axis=(0, 1))




def cell_query(
    index: slsh.SLSHIndex,
    data_local: jax.Array,
    node_offset: jax.Array,
    queries: jax.Array,
    cfg: slsh.SLSHConfig,
    grid: Grid,
) -> CellResult:
    """Query one cell's tables over its node's data slice.

    Runs the shared staged pipeline and lifts the shard-local neighbour
    indices to global dataset indices via ``node_offset`` (-1 pads stay
    -1) — the form every Reducer merge operates on.
    """
    del grid  # the pipeline derives this cell's table count from the index
    res = pipeline.query_batch(index, data_local, queries, cfg)
    gidx = jnp.where(res.knn_idx >= 0, res.knn_idx + node_offset, -1)
    return CellResult(res.knn_dist, gidx, res.comparisons, res.compaction_overflow)


# ----------------------------------------------------------------- reducers


def merge_axis_allgather(axis: str, kd: jax.Array, ki: jax.Array, k: int):
    """Reducer via all-gather: (Q,K)->(Q,K) merged over mesh axis ``axis``."""
    gd = jax.lax.all_gather(kd, axis)  # (S, Q, K)
    gi = jax.lax.all_gather(ki, axis)
    s = gd.shape[0]
    gd = jnp.moveaxis(gd, 0, 1).reshape(kd.shape[0], s * k)
    gi = jnp.moveaxis(gi, 0, 1).reshape(kd.shape[0], s * k)
    return jax.vmap(lambda d, i: topk.masked_topk_smallest(d, i, k))(gd, gi)


def merge_axis_tree(axis: str, kd: jax.Array, ki: jax.Array, k: int, size: int):
    """Reducer via a ppermute tournament tree + broadcast (DESIGN.md §10).

    ``routing.tournament_rounds`` supplies the (dst, src) exchange schedule:
    sources fold into ascending destinations over ``ceil(log2(size))``
    rounds (any ``size`` — non-power-of-two ranks just sit out rounds), rank
    0 ends with the full merge, and one broadcast round replicates it. The
    fold visits partials in ascending rank order, so the result is
    bit-identical to :func:`merge_axis_allgather` *including distance ties*
    (property-tested via the shared schedule in tests/test_routing.py).
    Payload: ``size - 1`` truncated partials + the broadcast, vs. the
    all-gather's ``size`` partials to every rank.
    """
    if size == 1:
        return kd, ki
    me = jax.lax.axis_index(axis)
    for rnd in routing.tournament_rounds(size):
        perm = [(src, dst) for dst, src in rnd]
        pd = jax.lax.ppermute(kd, axis, perm)
        pi = jax.lax.ppermute(ki, axis, perm)
        # ranks receiving nothing see zeros — neutralize before merging
        is_dst = jnp.any(me == jnp.asarray([d for d, _ in rnd], jnp.int32))
        pd = jnp.where(is_dst, pd, jnp.inf)
        pi = jnp.where(is_dst, pi, -1)
        kd, ki = jax.vmap(
            lambda a, b, c, d_: topk.merge_topk(a, b, c, d_, k)
        )(kd, ki, pd, pi)
    # broadcast rank 0's result back down the same tree (ppermute wants
    # unique sources, so the broadcast is the reduce tree reversed)
    for rnd in reversed(routing.tournament_rounds(size)):
        perm = list(rnd)  # dst -> src: holders push one level down
        bd = jax.lax.ppermute(kd, axis, perm)
        bi = jax.lax.ppermute(ki, axis, perm)
        is_recv = jnp.any(me == jnp.asarray([s for _, s in rnd], jnp.int32))
        kd = jnp.where(is_recv, bd, kd)
        ki = jnp.where(is_recv, bi, ki)
    return kd, ki


# ------------------------------------------------------------- shard_map API


def dslsh_build(mesh, root_key, data, cfg: slsh.SLSHConfig, grid: Grid):
    """Build the distributed index. data: (n, d) sharded over ``data`` axis.

    Returns a per-cell-stacked SLSHIndex with leading (nu, p) dims. Works on
    a 2-axis ``(data, model)`` mesh or a 3-axis ``(rep, data, model)`` one
    (the index replicates over ``rep`` — see ``dslsh_query``).

    >>> import jax
    >>> from repro.launch.mesh import make_local_mesh
    >>> cfg = slsh.SLSHConfig.compose(m_out=8, L_out=4, m_in=4, L_in=2,
    ...                               alpha=0.05, k=3, val_lo=0.0, val_hi=1.0,
    ...                               c_max=16, c_in=8, h_max=2, p_max=32)
    >>> grid, mesh = Grid(nu=1, p=1), make_local_mesh(1, 1)
    >>> data = jax.random.uniform(jax.random.PRNGKey(0), (64, 8))
    >>> index = dslsh_build(mesh, jax.random.PRNGKey(1), data, cfg, grid)
    >>> res = mesh_query(mesh, index, data, data[:2], cfg, grid)
    >>> [int(i) for i in res.knn_idx[:, 0]]  # indexed points find themselves
    [0, 1]
    >>> res.comparisons.shape  # counters are reported per (node, core, query)
    (1, 1, 2)
    """
    return _mesh_build_fn(mesh, cfg, grid)(root_key, data)


@functools.lru_cache(maxsize=16)
def _mesh_build_fn(mesh, cfg: slsh.SLSHConfig, grid: Grid):
    """One jitted shard_map build per (mesh, cfg, grid): eager, shard_map
    dispatches the build one op at a time (~15x slower at 32k points per
    node on CPU)."""

    def body(key, data_local):
        core = jax.lax.axis_index("model")
        idx = cell_build(key, data_local, core, cfg, grid)
        return jax.tree.map(lambda a: a[None, None], idx)

    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(), P("data", None)),
        out_specs=P("data", "model"), check_vma=False,
    ))


def mesh_query(
    mesh,
    index,
    data,
    queries,
    cfg: slsh.SLSHConfig,
    grid: Grid,
    reducer: str = "allgather",
    drop_mask: jax.Array | None = None,
    plan: routing.RoutingPlan | None = None,
    max_cells: int | None = None,
) -> DistributedQueryResult:
    """Resolve queries on the distributed index (shard_map execution path).

    Returns a :class:`DistributedQueryResult` — merged global top-K plus the
    per-cell counters and the §10 route mask.

    ``drop_mask`` (nu,) bool marks nodes dropped by the straggler deadline —
    the Reducer proceeds without their partials (paper's latency-first mode).

    ``plan`` routes each query only to the cells its probe keys can land in
    (DESIGN.md §10): the router hashes the batch once against the full
    family on the host, and each cell masks its partial by its slice of the
    route mask — bit-identical to the unrouted query because the key→cell
    map has no false negatives. ``max_cells`` additionally caps the probed
    cells per query (deadline degradation — approximate by design).

    Replication: on a mesh with a leading ``rep`` axis (``grid.cells * r``
    devices, ``launch.mesh.make_replicated_mesh``), the query batch row-
    shards across the ``r`` replicas of every cell; the Reducer then runs
    the two-stage §10 merge — cross-cell tournament on each replica's row
    block, replica reassembly via all-gather over ``rep``. Requires
    ``Q % r == 0``.
    """
    if drop_mask is None:
        drop_mask = jnp.zeros((grid.nu,), bool)
    if "rep" in mesh.axis_names:
        assert queries.shape[0] % mesh.shape["rep"] == 0, (
            "query batch must divide across the rep axis"
        )
    fn = _mesh_query_fn(mesh, cfg, grid, reducer, max_cells)
    return fn(index, data, queries, drop_mask,
              None if plan is None else plan.occupancy)


@functools.lru_cache(maxsize=64)
def _mesh_query_fn(
    mesh, cfg: slsh.SLSHConfig, grid: Grid, reducer: str, max_cells: int | None
):
    """One jitted query program per (mesh, cfg, grid, reducer, max_cells);
    the index, data, queries, drop mask and the plan's occupancy map enter
    as arguments, so repeated queries reuse the compiled executable."""
    has_rep = "rep" in mesh.axis_names

    def body(index_local, data_local, qs, dropm, routedm):
        index_local = jax.tree.map(lambda a: a[0, 0], index_local)
        node = jax.lax.axis_index("data")
        core = jax.lax.axis_index("model")
        n_loc = data_local.shape[0]
        res = cell_query(index_local, data_local, node * n_loc, qs, cfg, grid)
        with jax.named_scope("dslsh.merge"):
            r_q = routedm[:, node, core]  # this cell's slice of the route mask
            kd = jnp.where(r_q[:, None], res.knn_dist, jnp.inf)
            ki = jnp.where(r_q[:, None], res.knn_idx, -1)
            comps = jnp.where(r_q, res.comparisons, 0)
            overflow = jnp.where(r_q, res.compaction_overflow, 0)
            dropped = dropm[node]
            kd = jnp.where(dropped, jnp.inf, kd)
            ki = jnp.where(dropped, -1, ki)
            # Master: merge within the node (over cores), then across nodes
            if reducer == "tree":
                kd, ki = merge_axis_tree("model", kd, ki, cfg.k, grid.p)
                kd, ki = merge_axis_tree("data", kd, ki, cfg.k, grid.nu)
            else:
                kd, ki = merge_axis_allgather("model", kd, ki, cfg.k)
                kd, ki = merge_axis_allgather("data", kd, ki, cfg.k)
            if has_rep:
                # stage 2 of the §10 merge: replicas own disjoint contiguous
                # row blocks, so reassembly is a concat in rep order
                kd = jax.lax.all_gather(kd, "rep").reshape(-1, kd.shape[-1])
                ki = jax.lax.all_gather(ki, "rep").reshape(-1, ki.shape[-1])
        return kd, ki, comps[None, None], overflow[None, None]

    if has_rep:
        q_specs = (P("rep", None), P(), P("rep", None, None))
        counter_spec = P("data", "model", "rep")
    else:
        q_specs = (P(), P(), P())
        counter_spec = P("data", "model")
    cells = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P("data", "model"), P("data", None)) + q_specs,
        out_specs=(P(), P(), counter_spec, counter_spec),
        check_vma=False,
    )

    def run(index, data, queries, drop_mask, occupancy):
        # runs only while tracing: the retrace pin in
        # tests/test_compile_cache.py reads it
        count_retrace("mesh_query")
        if occupancy is not None:
            routed, _ = _route(index, occupancy, queries, cfg, grid, max_cells)
        else:
            routed = jnp.ones((queries.shape[0], grid.nu, grid.p), bool)
        qd, qi, comps, overflow = cells(index, data, queries, drop_mask, routed)
        return DistributedQueryResult(
            qd, qi, comps, overflow, jnp.transpose(routed, (1, 2, 0))
        )

    return jax.jit(run)


def _route(index, occupancy, queries, cfg: slsh.SLSHConfig, grid: Grid,
           max_cells: int | None):
    """The §10 router inside a query program, under the ``dslsh.route``
    scope: probe keys against the full family, the route mask (Q, nu, p)
    and its landing scores, then the ``max_cells`` budget."""
    with jax.named_scope("dslsh.route"):
        pk = routing.probe_keys(routing.family_from_index(index), queries, cfg)
        routed, scores = routing.route_mask(occupancy, pk, grid)
        if max_cells is not None:
            routed = routing.apply_cell_budget(routed, scores, max_cells)
    return routed, scores


def dslsh_query(
    mesh,
    index,
    data,
    queries,
    cfg: slsh.SLSHConfig,
    grid: Grid,
    reducer: str = "allgather",
    drop_mask: jax.Array | None = None,
    plan: routing.RoutingPlan | None = None,
    max_cells: int | None = None,
):
    """Deprecated positional-tuple form of :func:`mesh_query`.

    Returns (knn_dist, knn_idx, comparisons, compaction_overflow) — the
    pre-§11 contract. Kept for one release; new code should hold a
    ``repro.dslsh`` Index (or call :func:`mesh_query`) and read the typed
    :class:`DistributedQueryResult` instead.
    """
    warnings.warn(
        "dslsh_query is deprecated: build a repro.dslsh Index"
        " (dslsh.build(..., deploy=dslsh.mesh(...))) and call .query(), or"
        " use distributed.mesh_query for the typed result",
        DeprecationWarning,
        stacklevel=2,
    )
    res = mesh_query(
        mesh, index, data, queries, cfg, grid, reducer=reducer,
        drop_mask=drop_mask, plan=plan, max_cells=max_cells,
    )
    return res.knn_dist, res.knn_idx, res.comparisons, res.compaction_overflow


# ------------------------------------------------------------ simulated API


def simulate_build(root_key, data, cfg: slsh.SLSHConfig, grid: Grid):
    """vmap-over-cells build on a single device (benchmark path)."""
    n, d = data.shape
    assert n % grid.nu == 0
    data_n = data.reshape(grid.nu, n // grid.nu, d)

    def node_build(data_local):
        return jax.vmap(
            lambda c: cell_build(root_key, data_local, c, cfg, grid)
        )(jnp.arange(grid.p, dtype=jnp.int32))

    return jax.lax.map(node_build, data_n)  # leading dims (nu, p)


def _simulate_cells(index, data, queries, cfg: slsh.SLSHConfig, grid: Grid):
    """Per-cell partial results (CellResult stacked (nu, p, ...)) — the
    shared front half of ``simulate_query`` and ``simulate_query_routed``."""
    n, d = data.shape
    data_n = data.reshape(grid.nu, n // grid.nu, d)

    def node_query(args):
        node_id, data_local, index_node = args
        return jax.lax.map(
            lambda ix: cell_query(
                ix, data_local, node_id * (n // grid.nu), queries, cfg, grid
            ),
            index_node,
        )  # stacked over p

    return jax.lax.map(
        node_query,
        (jnp.arange(grid.nu, dtype=jnp.int32), data_n, index),
    )  # (nu, p, ...)


def grid_query(
    index,
    data,
    queries,
    cfg: slsh.SLSHConfig,
    grid: Grid,
    *,
    plan: routing.RoutingPlan | None = None,
    drop_mask: jax.Array | None = None,
    drop_cells: jax.Array | None = None,
    max_cells: int | None = None,
    return_stats: bool = False,
):
    """vmap-over-cells query + host-side reduction -> typed result.

    The single simulated-grid query core (DESIGN.md §11): with ``plan=None``
    the Forwarder broadcasts to every cell and the Reducer runs the flat
    masked top-K merge — the same math as :func:`mesh_query`. With a
    ``routing.RoutingPlan`` the batch is hashed once against the full
    family, routed only to the cells its probe keys can land in,
    block-split across each cell's replicas, and merged by the two-stage
    §10 tournament — **bit-identical** to the broadcast path (distances,
    indices, comparisons, overflow) because routed-out (cell, query) pairs
    are exactly the pairs whose candidate set is empty and the tournament
    visits partials in flat-concatenation order (tests/test_routing.py).

    ``max_cells`` enables deadline degradation: only the ``max_cells``
    best-landing cells are probed per query (approximate by design —
    requires a ``plan``). ``drop_mask`` (nu,) excludes straggler nodes from
    the Reducer. ``drop_cells`` (nu, p) excludes individual *lost* cells
    (elastic failover, DESIGN.md §14): a dropped cell contributes no
    partial, its counters zero, and its rows flip off in ``routed`` — so
    degradation is flagged through ``routed_frac``, never silent.
    ``return_stats`` appends a ``routing.RoutingStats`` with the route
    mask, per-device load, and Reducer payload accounting (``plan``
    required).
    """
    if drop_mask is None:
        drop_mask = jnp.zeros((grid.nu,), bool)
    if plan is None and (max_cells is not None or return_stats):
        raise ValueError(
            "max_cells / return_stats require a routing plan — build one"
            " with routing.make_plan(index, cfg, grid) (or use a routed"
            " repro.dslsh deployment)"
        )
    res = _simulate_cells(index, data, queries, cfg, grid)
    q = queries.shape[0]

    if plan is None:
        with jax.named_scope("dslsh.merge"):
            kd = jnp.where(drop_mask[:, None, None, None], jnp.inf, res.knn_dist)
            ki = jnp.where(drop_mask[:, None, None, None], -1, res.knn_idx)
            comps, overflow = res.comparisons, res.compaction_overflow
            visited = jnp.ones((grid.nu, grid.p, q), bool)
            if drop_cells is not None:
                dc = jnp.asarray(drop_cells)[:, :, None]  # (nu, p, 1) over Q
                kd = jnp.where(dc[..., None], jnp.inf, kd)
                ki = jnp.where(dc[..., None], -1, ki)
                comps = jnp.where(dc, 0, comps)
                overflow = jnp.where(dc, 0, overflow)
                visited = visited & ~dc
            kd = jnp.moveaxis(kd, 2, 0).reshape(q, -1)
            ki = jnp.moveaxis(ki, 2, 0).reshape(q, -1)
            fd, fi = jax.vmap(
                lambda a, b: topk.masked_topk_smallest(a, b, cfg.k)
            )(kd, ki)
        return DistributedQueryResult(fd, fi, comps, overflow, visited)

    routed, scores = _route(index, plan.occupancy, queries, cfg, grid, max_cells)
    if drop_cells is not None:
        routed = routed & ~jnp.asarray(drop_cells)[None, :, :]
    mask = jnp.transpose(routed, (1, 2, 0))  # (nu, p, Q)
    with jax.named_scope("dslsh.merge"):
        kd = jnp.where(mask[..., None], res.knn_dist, jnp.inf)
        ki = jnp.where(mask[..., None], res.knn_idx, -1)
        comps = jnp.where(mask, res.comparisons, 0)
        overflow = jnp.where(mask, res.compaction_overflow, 0)
        kd = jnp.where(drop_mask[:, None, None, None], jnp.inf, kd)
        ki = jnp.where(drop_mask[:, None, None, None], -1, ki)
        kd_s = kd.reshape(grid.cells, q, cfg.k)
        ki_s = ki.reshape(grid.cells, q, cfg.k)
        if plan.r_max > 1:
            # stage 1: split each cell's partial across its replicas by row
            # block, then reassemble — exercises the replica topology while
            # staying exact (replicas own disjoint rows of identical indices)
            owner = jnp.asarray(
                np.stack(
                    [
                        routing.replica_owner(q, int(plan.replicas[j, c]))
                        for j in range(grid.nu)
                        for c in range(grid.p)
                    ]
                )
            )  # (S, Q)
            kd_r, ki_r = jax.vmap(
                lambda a, b, o: routing.split_replicas(a, b, o, plan.r_max)
            )(kd_s, ki_s, owner)
            kd_s, ki_s = jax.vmap(
                lambda a, b: routing.merge_replica_partials(a, b, cfg.k)
            )(kd_r, ki_r)
        fd, fi = routing.merge_partials_tree(kd_s, ki_s, cfg.k)
    result = DistributedQueryResult(fd, fi, comps, overflow, mask)
    if not return_stats:
        return result
    routed_np = np.asarray(routed)
    stats = routing.RoutingStats(
        routed=routed_np,
        scores=np.asarray(scores),
        payload=routing.merge_payload(
            np.asarray(mask).reshape(grid.cells, q), cfg.k
        ),
        device_load=routing.device_load(plan, routed_np),
    )
    return result, stats


def simulate_query(
    index,
    data,
    queries,
    cfg: slsh.SLSHConfig,
    grid: Grid,
    drop_mask: jax.Array | None = None,
):
    """Deprecated positional-tuple form of the broadcast :func:`grid_query`.

    Returns (knn_dist, knn_idx, comparisons, compaction_overflow) — the
    pre-§11 contract, bit-identical to ``grid_query(...)`` fields. Kept for
    one release; new code should hold a ``repro.dslsh`` Index.
    """
    warnings.warn(
        "simulate_query is deprecated: build a repro.dslsh Index"
        " (dslsh.build(..., deploy=dslsh.grid(nu, p))) and call .query(),"
        " or use distributed.grid_query for the typed result",
        DeprecationWarning,
        stacklevel=2,
    )
    res = grid_query(index, data, queries, cfg, grid, drop_mask=drop_mask)
    return res.knn_dist, res.knn_idx, res.comparisons, res.compaction_overflow


def simulate_query_routed(
    index,
    data,
    queries,
    cfg: slsh.SLSHConfig,
    grid: Grid,
    plan: routing.RoutingPlan,
    drop_mask: jax.Array | None = None,
    max_cells: int | None = None,
    return_stats: bool = False,
):
    """Deprecated positional-tuple form of the routed :func:`grid_query`.

    Returns (knn_dist, knn_idx, comparisons, compaction_overflow[, stats]).
    Kept for one release; new code should hold a routed ``repro.dslsh``
    Index (``dslsh.grid(nu, p, replication=r, routed=True)``).
    """
    warnings.warn(
        "simulate_query_routed is deprecated: build a routed repro.dslsh"
        " Index (dslsh.grid(..., routed=True)) and call .query(), or use"
        " distributed.grid_query(plan=...) for the typed result",
        DeprecationWarning,
        stacklevel=2,
    )
    out = grid_query(
        index, data, queries, cfg, grid, plan=plan, drop_mask=drop_mask,
        max_cells=max_cells, return_stats=return_stats,
    )
    res, stats = out if return_stats else (out, None)
    flat = (res.knn_dist, res.knn_idx, res.comparisons, res.compaction_overflow)
    return flat + (stats,) if return_stats else flat


# ----------------------------------------------------------------- PKNN


def pknn_query(
    data: jax.Array, queries: jax.Array, k: int, grid: Grid
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Data-parallel exhaustive l1 K-NN baseline (paper's PKNN).

    Every processor scans n/(p*nu) points; comparisons are exactly that.
    Single-device evaluation (exhaustive search is shard-agnostic).
    """
    from repro.core import pknn as _p

    kd, ki = _p.knn_batch(data, queries, k)
    comps = jnp.full(
        (grid.nu, grid.p, queries.shape[0]), data.shape[0] // grid.cells, jnp.int32
    )
    return kd, ki, comps
