"""Staged SLSH execution pipeline with pluggable compute backends.

Every index build and query in the repo — single-shard (``slsh.build_index``
/ ``slsh.query_batch``), distributed (``distributed.cell_build`` /
``cell_query``), and the serving datastore — runs through this module. The
per-query hot path is decomposed into five explicit batched stages over a
query chunk (DESIGN.md §3):

  1. hash    — m-bit signatures for the whole chunk -> outer probe keys
               (incl. multiprobe bit-flips) + inner-layer keys
  2. gather  — probe buckets and gather candidates into a dense (Q, C)
               index tensor (C = L_out * slot, statically shaped); one
               batched searchsorted per table covers every query and probe
  3. dedup   — sort-based static dedup; yields the paper's #comparisons
  4. compact — sort each query's unique survivors to the front of a tight
               (Q, c_comp) buffer so downstream work scales with actual
               comparisons, not with the L_out*slot gather budget; unique
               survivors beyond the budget are counted in
               ``QueryResult.compaction_overflow``, never silently dropped
  5. top-k   — one masked L1 top-k over the compacted (Q, c_comp, d) block

Execution dispatches on ``SLSHConfig.backend`` (DESIGN.md §6):
``"reference"`` runs the five stages as pure jnp — the bit-exactness
oracle. ``"pallas"`` routes signatures (and multiprobe margins) through
the ``kernels/hash_pack`` fused all-tables launch and runs stages 3-5 as
the ``kernels/query_fused`` VMEM-resident megakernel behind a query-major
gather, so candidate vectors touch HBM exactly once and the compacted
(Q, c_comp, d) block never materializes (DESIGN.md §4);
``kernels/l1_topk`` still serves the staged form wherever a backend
provides no fused tail. ``query_batch`` owns its jit schedule: eager
calls hit cached whole-batch (reference) or per-stage fused (pallas)
programs, while traced calls fall back to the one-program chunked
pipeline (DESIGN.md §8.6). Backends are numerically equivalent —
enforced by tests/test_pipeline_backends.py and
tests/test_property_kernels.py.
"""
from __future__ import annotations

import contextvars
import dataclasses
import functools
import math
import warnings
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro import obs as obs_mod
from repro.core import hashing, merge, tables, topk
from repro.obs.metrics import count_inner_probe, count_retrace
from repro.runtime.payload import Payload, make_payload

# ------------------------------------------------------------ configuration


class ConfigError(ValueError):
    """A rejected SLSH configuration (every message says how to fix it)."""


def _require(ok: bool, msg: str) -> None:
    if not ok:
        raise ConfigError(msg)


@dataclasses.dataclass(frozen=True)
class FamilyConfig:
    """The hash-family half of an SLSH configuration (paper §2).

    ``m_out``/``L_out`` parameterize the outer l1 bit-sampling layer,
    ``m_in``/``L_in`` the inner cosine layer over heavy buckets,
    ``alpha`` the heavy-bucket threshold, and ``val_lo``/``val_hi`` the
    value range the bit-sampling thresholds are drawn from (mmHg for MAP
    data). Defaults are the paper's Table 1 settings. Invalid combinations
    raise :class:`ConfigError` at construction time.

    >>> FamilyConfig(m_out=16, L_out=8).L_out
    8
    """

    m_out: int = 125
    L_out: int = 120
    m_in: int = 65
    L_in: int = 20
    alpha: float = 0.005
    use_inner: bool = True
    multiprobe: int = 0  # extra low-margin bit-flip probes per outer table
    val_lo: float = 0.0
    val_hi: float = 200.0

    def __post_init__(self):
        _require(
            self.m_out >= 1 and self.L_out >= 1,
            f"m_out={self.m_out}, L_out={self.L_out}: the outer family needs"
            " at least one bit and one table (m_out >= 1, L_out >= 1)",
        )
        _require(
            not self.use_inner or (self.m_in >= 1 and self.L_in >= 1),
            f"m_in={self.m_in}, L_in={self.L_in} with use_inner=True: the"
            " stratified inner layer needs m_in >= 1 and L_in >= 1 — raise"
            " them or set use_inner=False",
        )
        _require(
            0.0 < self.alpha <= 1.0,
            f"alpha={self.alpha}: the heavy-bucket threshold is a population"
            " fraction and must lie in (0, 1]",
        )
        _require(
            0 <= self.multiprobe < self.m_out,
            f"multiprobe={self.multiprobe} with m_out={self.m_out}: each"
            " extra probe flips one distinct signature bit, so 0 <="
            " multiprobe < m_out must hold",
        )
        _require(
            self.val_lo < self.val_hi,
            f"val_lo={self.val_lo} >= val_hi={self.val_hi}: bit-sampling"
            " thresholds are drawn uniformly from [val_lo, val_hi), which"
            " must be a non-empty range",
        )


@dataclasses.dataclass(frozen=True)
class BudgetConfig:
    """The static-shape budget half of an SLSH configuration (DESIGN.md §8.4).

    ``k`` neighbours per query; ``c_max``/``c_in`` candidates gathered per
    outer/inner bucket probe; ``h_max`` heavy buckets indexed per table;
    ``p_max`` inner-layer population cap; ``c_comp`` the compacted distance
    buffer (§3 — unique survivors beyond it are counted in
    ``QueryResult.compaction_overflow``, never silently dropped; <= 0
    disables the cap); ``c_rerank`` the exact-rerank shortlist width of the
    compressed-payload tail (DESIGN.md §13 — only read when
    ``RuntimeConfig.payload != "f32"``). Invalid budgets raise
    :class:`ConfigError`.

    >>> BudgetConfig(k=5, c_comp=0).c_comp
    0
    """

    k: int = 10
    c_max: int = 128
    c_in: int = 32
    h_max: int = 8
    p_max: int = 512
    c_comp: int = 1024
    c_rerank: int = 128

    def __post_init__(self):
        _require(self.k >= 1, f"k={self.k}: need at least one neighbour")
        _require(
            self.c_max >= 1,
            f"c_max={self.c_max}: each outer probe must be able to gather"
            " at least one candidate",
        )
        _require(
            self.c_in >= 1 and self.p_max >= 1,
            f"c_in={self.c_in}, p_max={self.p_max}: inner-layer budgets must"
            " be >= 1 (set use_inner=False to disable the inner layer"
            " instead of zeroing its budgets)",
        )
        _require(
            self.h_max >= 0,
            f"h_max={self.h_max}: the heavy-bucket registry size cannot be"
            " negative",
        )
        _require(
            self.c_comp <= 0 or self.c_comp >= self.k,
            f"c_comp={self.c_comp} < k={self.k}: the compacted distance"
            " buffer cannot hold k candidates, so every query would"
            " silently return fewer than k neighbours — raise c_comp to at"
            " least k, or set c_comp <= 0 to disable compaction",
        )
        _require(
            self.c_rerank >= 1,
            f"c_rerank={self.c_rerank}: the payload rerank shortlist must"
            " hold at least one candidate",
        )


@dataclasses.dataclass(frozen=True)
class RuntimeConfig:
    """The execution half of an SLSH configuration (DESIGN.md §6).

    ``backend`` selects the compute backend for the hash and distance
    stages (``"reference"`` pure jnp, ``"pallas"`` the fused kernels);
    ``interpret`` overrides the Pallas interpret-mode platform policy;
    ``build_chunk``/``query_chunk`` bound per-step memory. ``build_mode``
    picks the index-construction schedule (DESIGN.md §13): ``"monolithic"``
    full-sorts all (L, n) keys in one launch (the bit-exactness oracle),
    ``"chunked"`` builds per-chunk sorted runs and k-way-merges them so
    peak build memory is O(chunk) + O(output), and ``"auto"`` (default)
    switches to chunked once ``n > build_chunk``. ``payload`` opts the
    fused query tail into compressed candidate rows (``"f16"``/``"i8"``,
    DESIGN.md §13) with an exact f32 rerank. Unknown backends are rejected
    at construction time, not at first build.

    >>> RuntimeConfig(backend="pallas").backend
    'pallas'
    """

    build_chunk: int = 4096
    query_chunk: int = 64
    backend: str = "reference"
    # Pallas interpret-mode override: None = platform policy (interpret
    # everywhere except real TPU), True/False forces it (DESIGN.md §6)
    interpret: bool | None = None
    build_mode: str = "auto"
    payload: str = "f32"

    def __post_init__(self):
        _require(
            self.build_chunk >= 1 and self.query_chunk >= 1,
            f"build_chunk={self.build_chunk}, query_chunk={self.query_chunk}:"
            " chunk sizes must be >= 1",
        )
        _require(
            self.backend in _BACKENDS,
            f"unknown SLSH backend {self.backend!r}; registered:"
            f" {sorted(_BACKENDS)}",
        )
        _require(
            self.build_mode in ("auto", "monolithic", "chunked"),
            f"build_mode={self.build_mode!r}: expected 'auto' (chunked once"
            " n > build_chunk), 'monolithic', or 'chunked'",
        )
        _require(
            self.payload in ("f32", "f16", "i8"),
            f"payload={self.payload!r}: expected 'f32' (uncompressed),"
            " 'f16', or 'i8' (compressed candidate rows + exact f32"
            " rerank, DESIGN.md §13)",
        )


_FAMILY_FIELDS = tuple(f.name for f in dataclasses.fields(FamilyConfig))
_BUDGET_FIELDS = tuple(f.name for f in dataclasses.fields(BudgetConfig))
_RUNTIME_FIELDS = tuple(f.name for f in dataclasses.fields(RuntimeConfig))

# Internal construction paths (compose/replace) flip this so only *direct*
# flat ``SLSHConfig(...)`` calls fire the deprecation warning.
_COMPOSED_CTOR: contextvars.ContextVar[bool] = contextvars.ContextVar(
    "slsh_composed_ctor", default=False
)


@dataclasses.dataclass(frozen=True)
class SLSHConfig:
    """Static configuration shared by every SLSH execution path.

    One frozen object carries the hash-family parameters
    (:class:`FamilyConfig`), the static-shape budgets
    (:class:`BudgetConfig`), and the execution knobs
    (:class:`RuntimeConfig`). Build it from those parts with
    :meth:`compose` (also exported as ``repro.dslsh.make_config``); the
    flat field list below is retained so every execution path keeps reading
    ``cfg.m_out`` etc., but constructing ``SLSHConfig(...)`` with flat
    keywords directly is **deprecated** (it emits a ``DeprecationWarning``
    and will be removed one release later).

    >>> cfg = SLSHConfig.compose(FamilyConfig(m_out=16, L_out=8, multiprobe=1),
    ...                          BudgetConfig(c_max=64))
    >>> cfg.slot  # per-table candidate slot width: max(2*64, L_in*c_in)
    640
    >>> cfg.replace(backend="pallas").backend
    'pallas'
    >>> cfg.family.m_out
    16
    """

    # hash-family parameters (FamilyConfig)
    m_out: int = 125
    L_out: int = 120
    m_in: int = 65
    L_in: int = 20
    alpha: float = 0.005
    k: int = 10
    use_inner: bool = True
    multiprobe: int = 0
    val_lo: float = 0.0
    val_hi: float = 200.0
    # static-shape budgets (BudgetConfig, DESIGN.md §8.4)
    c_max: int = 128
    c_in: int = 32
    h_max: int = 8
    p_max: int = 512
    c_comp: int = 1024
    c_rerank: int = 128
    # execution knobs (RuntimeConfig, DESIGN.md §6)
    build_chunk: int = 4096
    query_chunk: int = 64
    backend: str = "reference"
    interpret: bool | None = None
    build_mode: str = "auto"
    payload: str = "f32"

    def __post_init__(self):
        if not _COMPOSED_CTOR.get():
            warnings.warn(
                "constructing SLSHConfig(...) from flat keywords is"
                " deprecated; build it from parts with"
                " SLSHConfig.compose(FamilyConfig(...), BudgetConfig(...),"
                " RuntimeConfig(...)) (repro.dslsh.make_config), and derive"
                " variants with cfg.replace(...)",
                DeprecationWarning,
                stacklevel=3,
            )
        # Sub-config validation runs on the grouped views; the constructors
        # below raise ConfigError with actionable messages.
        self.family, self.budget, self.runtime  # noqa: B018
        # cross-group checks
        _require(
            not self.use_inner or self.h_max >= 1,
            f"h_max={self.h_max} with use_inner=True: stratification is on"
            " but the heavy-bucket registry holds zero buckets, so the"
            " inner layer would silently never fire — set h_max >= 1 or"
            " use_inner=False",
        )
        _require(
            self.payload == "f32" or self.backend == "pallas",
            f"payload={self.payload!r} with backend={self.backend!r}: the"
            " compressed candidate payload is a fused-tail feature — set"
            " backend='pallas' or payload='f32'",
        )
        _require(
            self.payload == "f32" or self.c_rerank >= self.k,
            f"c_rerank={self.c_rerank} < k={self.k} with"
            f" payload={self.payload!r}: the exact-rerank shortlist cannot"
            " hold k candidates, so every query would return approximate"
            " neighbours — raise c_rerank to at least k",
        )

    # -------------------------------------------------- composed interface

    @classmethod
    def compose(
        cls,
        family: FamilyConfig | None = None,
        budget: BudgetConfig | None = None,
        runtime: RuntimeConfig | None = None,
        **overrides,
    ) -> "SLSHConfig":
        """The canonical constructor: compose the three sub-configs.

        ``overrides`` accepts flat field names and routes each to its
        sub-config (a migration convenience for call sites still holding
        flat keyword dicts); unknown names raise :class:`ConfigError`.
        """
        parts = {
            "family": dataclasses.asdict(family or FamilyConfig()),
            "budget": dataclasses.asdict(budget or BudgetConfig()),
            "runtime": dataclasses.asdict(runtime or RuntimeConfig()),
        }
        for name, val in overrides.items():
            group = _field_group(name)
            parts[group][name] = val
        # re-validate each group after overrides land
        fam = FamilyConfig(**parts["family"])
        bud = BudgetConfig(**parts["budget"])
        run = RuntimeConfig(**parts["runtime"])
        tok = _COMPOSED_CTOR.set(True)
        try:
            return cls(
                **dataclasses.asdict(fam),
                **dataclasses.asdict(bud),
                **dataclasses.asdict(run),
            )
        finally:
            _COMPOSED_CTOR.reset(tok)

    def replace(self, **overrides) -> "SLSHConfig":
        """Derive a validated variant (the composed form of
        ``dataclasses.replace``); flat field names route to sub-configs."""
        return SLSHConfig.compose(
            self.family, self.budget, self.runtime, **overrides
        )

    @property
    def family(self) -> FamilyConfig:
        """This config's hash-family half as a :class:`FamilyConfig`."""
        return FamilyConfig(
            **{name: getattr(self, name) for name in _FAMILY_FIELDS}
        )

    @property
    def budget(self) -> BudgetConfig:
        """This config's budget half as a :class:`BudgetConfig`."""
        return BudgetConfig(
            **{name: getattr(self, name) for name in _BUDGET_FIELDS}
        )

    @property
    def runtime(self) -> RuntimeConfig:
        """This config's execution half as a :class:`RuntimeConfig`."""
        return RuntimeConfig(
            **{name: getattr(self, name) for name in _RUNTIME_FIELDS}
        )

    @property
    def slot(self) -> int:
        """Per-outer-table candidate slot width."""
        outer = (1 + self.multiprobe) * self.c_max
        return max(outer, self.L_in * self.c_in) if self.use_inner else outer


def _field_group(name: str) -> str:
    """Which sub-config a flat SLSH field name belongs to."""
    if name in _FAMILY_FIELDS:
        return "family"
    if name in _BUDGET_FIELDS:
        return "budget"
    if name in _RUNTIME_FIELDS:
        return "runtime"
    raise ConfigError(
        f"unknown SLSH config field {name!r}; family fields:"
        f" {_FAMILY_FIELDS}, budget fields: {_BUDGET_FIELDS}, runtime"
        f" fields: {_RUNTIME_FIELDS}"
    )


class SLSHIndex(NamedTuple):
    outer_params: hashing.BitSampleParams
    inner_params: hashing.SignRPParams
    outer: tables.TableSet  # (L, n)
    heavy: tables.HeavyBuckets  # (L, H)
    inner_keys: jax.Array  # (L, H, L_in, P) uint32 sorted
    inner_idx: jax.Array  # (L, H, L_in, P) int32 global idx, -1 pad
    n: jax.Array  # () int32 — points in this shard


class QueryResult(NamedTuple):
    knn_idx: jax.Array  # (..., K) int32, -1 pad
    knn_dist: jax.Array  # (..., K) float32, inf pad
    comparisons: jax.Array  # (...,) int32 — unique candidates scanned
    bucket_total: jax.Array  # (...,) int32 — sum of probed bucket populations
    # unique survivors beyond the c_comp budget, excluded from the distance
    # stage (0 everywhere means the compacted result is exact)
    compaction_overflow: jax.Array  # (...,) int32
    # compressed-payload tail only (None on the f32 path): candidates whose
    # approximate distance came within the quantization error bound of the
    # k-th exact distance but missed the c_rerank shortlist — counted,
    # never silent; 0 everywhere certifies knn_idx bit-identical to f32
    # (DESIGN.md §13)
    rerank_misses: jax.Array | None = None


class DeltaView(NamedTuple):
    """Streamed-in points exposed to the gather stage (DESIGN.md §9).

    A delta segment is an append-only buffer of ``cap`` slots holding points
    inserted *after* the base index was built. Slot ``s`` (when ``valid[s]``)
    holds the point with global dataset index ``gidx[s]``; slots fill in
    ascending global-index order, and every ``gidx`` exceeds every base
    index — the pair of facts the exact merge in ``_gather_one_table``
    relies on.
    """

    outer_keys: jax.Array  # (cap, L) uint32 bucket key per outer table
    inner_keys: jax.Array  # (cap, L_in) uint32 inner-layer keys
    gidx: jax.Array  # (cap,) int32 global dataset index of each slot
    valid: jax.Array  # (cap,) bool — slot occupied


_IDX_SENTINEL = jnp.int32(jnp.iinfo(jnp.int32).max)  # sorts after any index


# -------------------------------------------------------- backend dispatch


class BackendOps(NamedTuple):
    """The contract a compute backend implements (DESIGN.md §6).

    signature_words
        ``(params, x (n, d)) -> (n, L, W) uint32`` packed m-bit signatures
        for every table of the family; must equal
        ``hashing.pack_bits(hashing.signature_bits(params, x))`` exactly
        (bucket keys are derived from these words, so any mismatch silently
        changes candidate sets).
    l1_topk
        ``(q (Q, d), cands (Q, C, d), mask (Q, C), k) -> (dist, pos)`` with
        ``dist (Q, k)`` ascending (inf-padded) and ``pos (Q, k)`` positions
        into C (-1 where fewer than k valid candidates).
    probe_words (optional, default ``None``)
        ``(params, x (n, d)) -> (words (n, L, W), margins (n, L, m))`` —
        signature words *and* multiprobe quantizer margins from one fused
        launch, consumed by ``hashing.probe_keys_from_margins``. ``None``
        makes the hash stage recompute margins from ``x`` (the reference
        formulation); margins must equal ``|x[:, dims] - thrs|`` exactly.
    query_tail (optional, default ``None``)
        ``(data, queries, cand (Q, C), run=, c_comp=, k=) ->
        (kd, ki, comparisons, overflow)`` — pipeline stages 3-5 fused over
        the run-sorted candidate tensor (``kernels/query_fused``). ``None``
        keeps the staged dedup/compact/top-k path. A fused tail must be
        bit-exact with the staged stages, including the §6 lowest-position
        tie rule and ``compaction_overflow`` counts.
    query_tail_payload (optional, default ``None``)
        ``(data, qdata, meta, queries, cand, run=, c_comp=, c_rerank=, k=)
        -> (kd, ki, comparisons, overflow, rerank_misses)`` — the fused
        tail streaming quantized candidate rows (``runtime.payload``) with
        an exact f32 rerank of the ``c_rerank`` shortlist (DESIGN.md §13).
        Used only when ``cfg.payload != "f32"``; ``None`` falls back to
        the exact ``query_tail`` (correct, just uncompressed).
    inner_probe (optional, default ``None``)
        ``(inner_keys, inner_idx, h (L, Q), keys (Q, L_in), c_in=) ->
        (L, Q, L_in * c_in)`` — the stratified layer's probe of each
        (table, query) pair's heavy bucket ``h``, read as whole slabs
        (``kernels/inner_probe``, DESIGN.md §3). ``None`` keeps the
        staged gather's element takes; delta queries always keep them.
        Must equal the takes bit for bit: ``c_in`` indices of the key's
        run per inner table, -1 past it.
    """

    signature_words: Callable[..., jax.Array]
    l1_topk: Callable[..., tuple[jax.Array, jax.Array]]
    probe_words: Callable[..., tuple[jax.Array, jax.Array]] | None = None
    query_tail: Callable[..., tuple[jax.Array, ...]] | None = None
    query_tail_payload: Callable[..., tuple[jax.Array, ...]] | None = None
    inner_probe: Callable[..., jax.Array] | None = None


_BACKENDS: dict[str, BackendOps | Callable[["SLSHConfig | None"], BackendOps]] = {}


def register_backend(
    name: str, ops: BackendOps | Callable[["SLSHConfig | None"], BackendOps]
) -> None:
    """Register a backend: either a plain ``BackendOps`` or a factory
    ``cfg -> BackendOps`` for backends that bind per-config state (the
    pallas backend binds ``cfg.interpret`` — DESIGN.md §6)."""
    _BACKENDS[name] = ops


def get_backend(name: str, cfg: "SLSHConfig | None" = None) -> BackendOps:
    """Resolve a registered backend name to its ``BackendOps`` (factories
    are invoked with ``cfg``); raises ``ValueError`` for unknown names."""
    try:
        entry = _BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown SLSH backend {name!r}; registered: {sorted(_BACKENDS)}"
        ) from None
    return entry if isinstance(entry, BackendOps) else entry(cfg)


def _ref_signature_words(params: hashing.HashParams, x: jax.Array) -> jax.Array:
    return hashing.pack_bits(hashing.signature_bits(params, x))


def _pallas_signature_words(
    params: hashing.HashParams, x: jax.Array, *, interpret: bool | None = None
) -> jax.Array:
    from repro.kernels.hash_pack import ops as hp_ops

    return hp_ops.signature_words_kernel(params, x, interpret=interpret)


def _pallas_l1_topk(q, cands, mask, k, *, interpret: bool | None = None):
    from repro.kernels.l1_topk import ops as l1_ops

    return l1_ops.l1_topk(q, cands, mask, k=k, interpret=interpret)


def _pallas_probe_words(params, x, *, interpret: bool | None = None):
    from repro.kernels.hash_pack import ops as hp_ops

    return hp_ops.probe_words_kernel(params, x, interpret=interpret)


def _pallas_query_tail(
    data, queries, cand, *, run, c_comp, k, interpret: bool | None = None
):
    from repro.kernels.query_fused import ops as qf_ops

    return qf_ops.query_tail(
        data, queries, cand, run=run, c_comp=c_comp, k=k, interpret=interpret
    )


def _pallas_query_tail_payload(
    data, qdata, meta, queries, cand, *, run, c_comp, c_rerank, k,
    interpret: bool | None = None,
):
    from repro.kernels.query_fused import ops as qf_ops

    return qf_ops.query_tail_payload(
        data, qdata, meta, queries, cand,
        run=run, c_comp=c_comp, c_rerank=c_rerank, k=k, interpret=interpret,
    )


def _pallas_inner_probe(
    inner_keys, inner_idx, h, keys, *, c_in, interpret: bool | None = None
):
    from repro.kernels.inner_probe import ops as ip_ops

    return ip_ops.inner_probe(
        inner_keys, inner_idx, h, keys, c_in=c_in, interpret=interpret
    )


def _pallas_ops(cfg: "SLSHConfig | None") -> BackendOps:
    """The pallas backend: Mosaic kernels, compiled on TPU and interpreted
    elsewhere (``blocking.resolve_interpret``; ``cfg.interpret`` forces
    either). Compiled, Mosaic refuses the fused f32 tail's body
    (``kernels.query_fused.query_fused.XLA_STAGES``), so the backend has no
    ``query_tail`` there: the f32 tail runs the staged stages 3-5 as XLA
    ops, stage 5 in the ``l1_topk`` kernel, and the staged gather probes
    the inner layer with the ``inner_probe`` kernel. Interpreted, the fused
    path's flat gather takes its place (element takes are cheap there).
    The compressed-payload tail keeps its dedup, compaction, gathers and
    selections as XLA ops around the ``l1_topk`` kernel's distance mode."""
    from repro.kernels import blocking

    interp = None if cfg is None else cfg.interpret
    compiled = not blocking.resolve_interpret(interp)
    return BackendOps(
        functools.partial(_pallas_signature_words, interpret=interp),
        functools.partial(_pallas_l1_topk, interpret=interp),
        probe_words=functools.partial(_pallas_probe_words, interpret=interp),
        query_tail=(
            None if compiled
            else functools.partial(_pallas_query_tail, interpret=interp)
        ),
        query_tail_payload=functools.partial(
            _pallas_query_tail_payload, interpret=interp
        ),
        inner_probe=(
            functools.partial(_pallas_inner_probe, interpret=interp)
            if compiled else None
        ),
    )


register_backend("reference", BackendOps(_ref_signature_words, topk.masked_l1_topk_batch))
register_backend("pallas", _pallas_ops)


# ------------------------------------------------------------------- build


def make_family(key: jax.Array, d: int, cfg: SLSHConfig):
    """The full (outer, inner) hash family for dimensionality ``d``.

    Both the single-shard and the distributed builders derive their params
    from this one function, so a shared PRNG key reproduces the paper Root's
    broadcast of identical family instances to every node.
    """
    k_out, k_in = jax.random.split(key)
    outer = hashing.make_bitsample(k_out, cfg.L_out, cfg.m_out, d, cfg.val_lo, cfg.val_hi)
    # Inner family instances are shared across heavy buckets (independent
    # across the L_in tables) — see DESIGN.md §8.5; per-bucket instances
    # would cost (L_out*H*L_in*d*m_in) floats with no semantic gain.
    inner = hashing.make_signrp(k_in, cfg.L_in, cfg.m_in, d)
    return outer, inner


def hash_keys(
    params: hashing.HashParams, x: jax.Array, backend: BackendOps
) -> jax.Array:
    """Bucket keys for all tables: x (n, d) -> (n, L) uint32."""
    words = backend.signature_words(params, x)  # (n, L, W)
    return hashing.mix32(words, params.salts[None, :])


def _chunked_map(fn, x: jax.Array, chunk: int):
    """lax.map ``fn`` over row-chunks of ``x`` (n, d); results re-stacked to
    leading dim n (any pytree of (chunk, ...) outputs)."""
    n = x.shape[0]
    chunk = min(chunk, n)
    n_chunks = (n + chunk - 1) // chunk
    pad = n_chunks * chunk - n
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    res = jax.lax.map(fn, xp.reshape((n_chunks, chunk) + x.shape[1:]))
    return jax.tree.map(
        lambda a: a.reshape((n_chunks * chunk,) + a.shape[2:])[:n], res
    )


def hash_keys_chunked(
    params: hashing.HashParams, x: jax.Array, chunk: int, backend: BackendOps
) -> jax.Array:
    """Memory-bounded build hashing: x (n, d) -> (L, n) uint32."""
    return _chunked_map(lambda c: hash_keys(params, c, backend), x, chunk).T


def _build_inner_for_bucket(
    inner_params: hashing.SignRPParams,
    data: jax.Array,
    sorted_idx_row: jax.Array,
    start: jax.Array,
    size: jax.Array,
    valid: jax.Array,
    p_max: int,
) -> tuple[jax.Array, jax.Array]:
    """Inner LSH tables over one heavy bucket's (capped) population."""
    offs = start + jnp.arange(p_max, dtype=jnp.int32)
    in_pop = (jnp.arange(p_max) < size) & valid
    gidx = jnp.where(in_pop, sorted_idx_row[jnp.clip(offs, 0, sorted_idx_row.shape[0] - 1)], -1)
    pts = data[jnp.clip(gidx, 0, data.shape[0] - 1)]  # (P, d), garbage where pad
    keys = hashing.hash_points(inner_params, pts)  # (L_in, P)
    keys = jnp.where(in_pop[None, :], keys, tables.PAD_KEY)
    gidx_b = jnp.broadcast_to(gidx, keys.shape)
    sk, si = jax.vmap(lambda k, i: jax.lax.sort((k, i), num_keys=1))(keys, gidx_b)
    return sk, si


def build_inner(
    inner_params: hashing.SignRPParams,
    data: jax.Array,
    outer: tables.TableSet,
    heavy: tables.HeavyBuckets,
    cfg: SLSHConfig,
) -> tuple[jax.Array, jax.Array]:
    """Inner (stratified) tables for every heavy bucket of every table.

    Shared by the batch builder and the streaming compactor (stream/index.py),
    which refreshes stratification after folding a delta segment."""
    def per_table(args):
        si_row, hv_start, hv_size, hv_valid = args
        return jax.vmap(
            lambda s, z, v: _build_inner_for_bucket(
                inner_params, data, si_row, s, z, v, cfg.p_max
            )
        )(hv_start, hv_size, hv_valid)

    return jax.lax.map(
        per_table, (outer.sorted_idx, heavy.start, heavy.size, heavy.valid)
    )


def empty_inner(l_out: int, cfg: SLSHConfig) -> tuple[jax.Array, jax.Array]:
    """Inert inner tables for ``use_inner=False`` indices — the single
    definition shared by this builder and the streaming compactor."""
    shape = (l_out, cfg.h_max, cfg.L_in, cfg.p_max)
    return jnp.full(shape, tables.PAD_KEY), jnp.full(shape, -1, jnp.int32)


# Outer tables hashed + ladder-merged together per eager chunked-build pass:
# peak transient state scales with _BUILD_GROUP * n while the dispatch count
# scales with L / _BUILD_GROUP — 4 balances both at the bench shapes.
_BUILD_GROUP = 4


@functools.lru_cache(maxsize=64)
def _build_hash_fn(cfg: SLSHConfig):
    """Cached jit of one build chunk's hashing -> (L_g, c) keys."""
    backend = get_backend(cfg.backend, cfg)

    def run(params, x):
        count_retrace("build_hash")
        return hash_keys(params, x, backend).T

    return jax.jit(run)


@functools.lru_cache(maxsize=4)
def _sort_run_fn():
    """Cached jit sorting one chunk's (L_g, c) keys into a run (stable)."""

    def run(k, i):
        count_retrace("build_sort_run")
        return tuple(
            jax.vmap(lambda kk, ii: jax.lax.sort((kk, ii), num_keys=1))(k, i)
        )

    return jax.jit(run)


@functools.lru_cache(maxsize=4)
def _merge_pair_fn():
    """Cached jit of one ladder pair-merge (eager chunked build)."""

    def run(a, b):
        count_retrace("build_merge")
        return merge.merge_run_pair(a, b)

    return jax.jit(run)


@functools.lru_cache(maxsize=4)
def _write_rows_fn():
    """Donated row-group write into the preallocated (L, n) output tables.

    Donation makes XLA reuse the output buffers in place, so the eager
    chunked build never holds two (L, n) copies; ``t`` stays dynamic (one
    trace serves every row offset).
    """

    def run(out_k, out_i, rk, ri, t):
        return (
            jax.lax.dynamic_update_slice_in_dim(out_k, rk, t, 0),
            jax.lax.dynamic_update_slice_in_dim(out_i, ri, t, 0),
        )

    return jax.jit(run, donate_argnums=(0, 1))


def _chunk_bounds(n: int, chunk: int) -> list[tuple[int, int]]:
    return [(lo, min(lo + chunk, n)) for lo in range(0, n, chunk)]


def _build_tables_chunked_eager(
    outer_params: hashing.BitSampleParams,
    data: jax.Array,
    cfg: SLSHConfig,
    ob,
) -> tables.TableSet:
    """Chunked sorted-run construction, eager schedule (DESIGN.md §13).

    Per group of ``_BUILD_GROUP`` tables: hash each ``build_chunk`` of rows
    (a fresh hash of the group's tables costs the same total work as the
    monolithic all-tables hash), sort each chunk into a run, fold runs
    through the LSM-style binary-counter ladder (``core.merge``), and write
    the merged rows into the donated (L, n) output. Every step is its own
    cached jit dispatch — XLA CPU frees each transient between dispatches,
    which a whole-build program does not (its scheduler keeps far more
    live), so peak memory is O(group·n) + O(output) instead of the
    monolithic path's O(L·n) transient sort + segment-scan state.
    ``ob`` (an obs bundle with tracing enabled, or None) wraps each phase
    in ``build.*`` spans with real device-time sync points.
    """
    n = data.shape[0]
    l_out = outer_params.salts.shape[0]
    chunk = min(cfg.build_chunk, n)
    hash_fn = _build_hash_fn(cfg)
    sort_fn = _sort_run_fn()
    merge_fn = _merge_pair_fn()
    write_fn = _write_rows_fn()
    bounds = _chunk_bounds(n, chunk)
    out_k = jnp.full((l_out, n), tables.PAD_KEY, jnp.uint32)
    out_i = jnp.full((l_out, n), -1, jnp.int32)
    for t0 in range(0, l_out, _BUILD_GROUP):
        g = min(_BUILD_GROUP, l_out - t0)
        params_g = jax.tree.map(lambda a: a[t0 : t0 + g], outer_params)

        def hash_all():
            return [hash_fn(params_g, data[lo:hi]) for lo, hi in bounds]

        def sort_all(keys_list):
            runs = []
            for (lo, hi), kg in zip(bounds, keys_list):
                ig = jnp.broadcast_to(
                    jnp.arange(lo, hi, dtype=jnp.int32), kg.shape
                )
                runs.append(sort_fn(kg, ig))
            return runs

        def merge_all(runs):
            stack: list[merge.Run] = []
            for item in runs:
                merge.ladder_push(stack, item, merge_fn)
            return merge.ladder_collapse(stack, merge_fn)

        if ob is None:
            rk, ri = merge_all(sort_all(hash_all()))
        else:
            keys_list = _traced_stage(ob, "build.hash", hash_all)
            runs = _traced_stage(ob, "build.sort_runs", sort_all, keys_list)
            rk, ri = _traced_stage(ob, "build.merge", merge_all, runs)
        out_k, out_i = write_fn(out_k, out_i, rk, ri, t0)
    return tables.TableSet(out_k, out_i)


def _pick_build_mode(cfg: SLSHConfig, n: int) -> str:
    """Resolve ``cfg.build_mode`` for an ``n``-point build: ``"auto"``
    goes chunked only past one ``build_chunk`` of points (a single-chunk
    ladder is the monolithic sort with extra steps)."""
    mode = cfg.build_mode
    if mode == "auto":
        mode = "chunked" if n > cfg.build_chunk else "monolithic"
    return mode


def build_from_params(
    data: jax.Array,
    outer_params: hashing.BitSampleParams,
    inner_params: hashing.SignRPParams,
    cfg: SLSHConfig,
) -> SLSHIndex:
    """Shared index builder for the single-shard and distributed paths.

    ``outer_params`` may be a row-slice of a larger family (each distributed
    core slices its L_out/p tables out of the root broadcast family); the
    table count is taken from the params, never from ``cfg.L_out``.

    ``cfg.build_mode`` selects the construction schedule (DESIGN.md §13):
    the monolithic full-sort oracle, or chunked sorted-run construction
    whose peak memory is O(chunk) + O(output) — bit-exact with each other
    on every output (tests/test_property_build.py). ``"auto"`` goes
    chunked once ``n > build_chunk``. The chunked path also streams the
    heavy-bucket scan per table (``tables.find_heavy_streamed``), whose
    all-tables transients would otherwise dominate peak build memory; under
    a trace, that streamed scan is all it keeps of the chunked schedule.
    """
    n = data.shape[0]
    _require(n >= 1, "cannot build an SLSH index over zero points")
    backend = get_backend(cfg.backend, cfg)
    l_out = outer_params.salts.shape[0]
    traced = _contains_tracer(data, outer_params, inner_params)
    mode = _pick_build_mode(cfg, n)
    ob = obs_mod.get_active()
    if ob is not None and (traced or not ob.tracing):
        ob = None  # synced build.* spans only for a traced eager build (§12.1)
    # Under a trace (grid and mesh cell programs) even the chunked mode
    # sorts each table whole after chunk-mapped hashing: the sorted-run
    # ladder would unroll into the program, one hash kernel and one merge
    # per chunk — 168 of each per cell of a 2x2 mesh at 1.37M points, 186.5 s
    # of compile for a v5e — while XLA owns that program's memory schedule
    # either way. Both forms are bit-exact with each other.
    if mode == "chunked" and not traced:
        outer = _build_tables_chunked_eager(outer_params, data, cfg, ob)
    elif ob is None:
        keys = hash_keys_chunked(outer_params, data, cfg.build_chunk, backend)
        outer = tables.build_tables(keys)
    else:
        keys = _traced_stage(
            ob, "build.hash", hash_keys_chunked,
            outer_params, data, cfg.build_chunk, backend,
        )
        outer = _traced_stage(ob, "build.sort_runs", tables.build_tables, keys)
    find_heavy = (
        tables.find_heavy_streamed if mode == "chunked" else tables.find_heavy
    )
    alpha_n = jnp.maximum(jnp.int32(cfg.alpha * n), 1)

    def heavy_inner():
        heavy = find_heavy(outer, alpha_n, cfg.h_max)
        if cfg.use_inner:
            ik, ii = build_inner(inner_params, data, outer, heavy, cfg)
        else:
            ik, ii = empty_inner(l_out, cfg)
        return heavy, ik, ii

    if ob is None:
        heavy, inner_keys, inner_idx = heavy_inner()
    else:
        heavy, inner_keys, inner_idx = _traced_stage(
            ob, "build.heavy_inner", heavy_inner
        )
    return SLSHIndex(
        outer_params, inner_params, outer, heavy, inner_keys, inner_idx, jnp.int32(n)
    )


# ------------------------------------------------------------ query stages


def _stage(name: str):
    """Trace the decorated stage function under ``jax.named_scope(
    "dslsh.<name>")``: every operation it emits carries the stage in its
    HLO ``op_name`` metadata, so a profiler trace attributes device time to
    stages on any deployment (DESIGN.md §12.1). Metadata only: the program
    and its instruction names stay as they are."""

    def wrap(fn):
        @functools.wraps(fn)
        def run(*args, **kwargs):
            with jax.named_scope(f"dslsh.{name}"):
                return fn(*args, **kwargs)

        return run

    return wrap


@_stage("hash")
def _stage_hash(
    index: SLSHIndex, queries: jax.Array, cfg: SLSHConfig, backend: BackendOps
) -> tuple[jax.Array, jax.Array]:
    """Stage 1 — signatures for the whole chunk.

    Returns outer probe keys (Q, L, 1 + multiprobe) and inner-layer keys
    (Q, L_in) (zeros when the inner layer is disabled). Backends providing
    ``probe_words`` (pallas) emit the multiprobe quantizer margins from the
    same fused launch as the words, so the hash stage stays one kernel; the
    reference formulation recomputes margins from ``queries``.
    """
    if (
        cfg.multiprobe
        and backend.probe_words is not None
        and isinstance(index.outer_params, hashing.BitSampleParams)
    ):
        words, margins = backend.probe_words(index.outer_params, queries)
        probe_keys = hashing.probe_keys_from_margins(
            index.outer_params, words, margins, cfg.multiprobe
        )
    else:
        words = backend.signature_words(index.outer_params, queries)
        probe_keys = hashing.probe_keys_from_words(
            index.outer_params, queries, words, cfg.multiprobe
        )
    if cfg.use_inner:
        inner_keys = hash_keys(index.inner_params, queries, backend)  # (Q, L_in)
    else:
        inner_keys = jnp.zeros((queries.shape[0], cfg.L_in), jnp.uint32)
    return probe_keys, inner_keys


def _merge_capped(base_cand: jax.Array, delta_match: jax.Array, delta_gidx: jax.Array, budget: int) -> jax.Array:
    """Merge a base bucket gather with delta-segment matches, exactly.

    ``base_cand`` (budget,) holds ascending global indices (-1 pad at the
    end); ``delta_match`` (cap,) marks delta slots in the same bucket. A
    from-scratch build over base ∪ delta would gather the ``budget`` smallest
    global indices of the union bucket (CSR rows are stably sorted, so equal
    keys order by index) — which is exactly the selection below.

    The delta segment is an unsorted append-cheap memtable (the LSM
    tradeoff), so each probe scans it; top-k selection keeps that
    O(cap log budget) rather than a full O(cap log cap) sort, and
    compaction folds the cost away entirely.
    """
    base = jnp.where(base_cand < 0, _IDX_SENTINEL, base_cand)
    vals = jnp.where(delta_match, delta_gidx, _IDX_SENTINEL)
    k = min(budget, vals.shape[0])
    delta = -jax.lax.top_k(-vals, k)[0]  # k smallest, ascending
    if k < budget:
        delta = jnp.pad(delta, (0, budget - k), constant_values=_IDX_SENTINEL)
    merged = jnp.sort(jnp.concatenate([base, delta]))[:budget]
    return jnp.where(merged == _IDX_SENTINEL, -1, merged)


def _heavy_match(
    index: SLSHIndex, base_keys: jax.Array  # (Q, L) base probe key per table
) -> tuple[jax.Array, jax.Array]:
    """Match each (query, table) base key against the heavy-bucket registry.

    Returns ``found (Q, L)`` and the registry slot ``h (Q, L)``: the first
    valid slot holding the key (argmax's tie rule), 0 where none does.
    (Streaming note: the registry is the *base* one — stratification is
    frozen between compactions, DESIGN.md §9.)
    """
    match = (
        index.heavy.keys[None, :, :] == base_keys[:, :, None]
    ) & index.heavy.valid[None, :, :]  # (Q, L, H)
    return jnp.any(match, axis=-1), jnp.argmax(match, axis=-1).astype(jnp.int32)


def _gather_one_table(
    index: SLSHIndex,
    cfg: SLSHConfig,
    l: jax.Array,
    probe_keys_t: jax.Array,  # (Q, 1 + multiprobe) base key first
    inner_keys: jax.Array,  # (Q, L_in)
    delta: DeltaView | None = None,
    heavy: tuple[jax.Array, jax.Array, jax.Array | None] | None = None,
) -> tuple[jax.Array, jax.Array]:
    """All queries' candidates (Q, slot) for one outer table; -1 where masked.

    Also returns the base-bucket populations (Q,) (for stats). Every bucket
    range for the table resolves through *one* batched searchsorted pair
    over all Q*(1+multiprobe) probe keys — the former per-query scalar form
    lowered to a swarm of tiny binary-search gathers. When ``delta`` is
    given, each probe fans out over base + delta segments and the merged
    candidate set equals the one a from-scratch build over the union would
    gather (DESIGN.md §9). ``heavy`` (``use_inner`` only) is this table's
    registry match ``(found (Q,), h (Q,), inner)``; ``inner`` is the inner
    layer already probed by the backend's ``inner_probe`` ``(Q, L_in *
    c_in)``, or None for the element takes below (DESIGN.md §3).
    """
    sk_row = index.outer.sorted_keys[l]
    si_row = index.outer.sorted_idx[l]
    q_n, p_n = probe_keys_t.shape
    flat = probe_keys_t.reshape(-1)
    lo = jnp.searchsorted(sk_row, flat, side="left").astype(jnp.int32)
    hi = jnp.searchsorted(sk_row, flat, side="right").astype(jnp.int32)
    lo, hi = lo.reshape(q_n, p_n), hi.reshape(q_n, p_n)
    bucket_sz = hi[:, 0] - lo[:, 0]
    if delta is not None:
        d_outer = delta.valid[None, :] & (
            delta.outer_keys[None, :, l] == probe_keys_t[:, :1]
        )  # (Q, cap)
        bucket_sz = bucket_sz + jnp.sum(d_outer.astype(jnp.int32), axis=-1)
    else:
        d_outer = jnp.zeros((q_n, 1), bool)  # unused vmap carrier

    slot = cfg.slot

    def element_probe(h, in_keys_q, d_outer_q):
        if delta is not None:
            # Delta members of this heavy bucket join its inner-layer
            # population in global-index order until the P_max cap —
            # mirroring the first min(size, P_max) rows a union build
            # would stratify.
            rank = jnp.cumsum(d_outer_q.astype(jnp.int32)) - 1
            d_in_pop = d_outer_q & (index.heavy.size[l, h] + rank < cfg.p_max)

        def inner_one(li):
            ik = index.inner_keys[l, h, li]
            ii = index.inner_idx[l, h, li]
            lo2, hi2 = tables.bucket_range(ik, in_keys_q[li])
            cand = tables.gather_bucket(ii, lo2, hi2, cfg.c_in)
            if delta is None:
                return cand
            dm = d_in_pop & (delta.inner_keys[:, li] == in_keys_q[li])
            return _merge_capped(cand, dm, delta.gidx, cfg.c_in)

        return jax.vmap(inner_one)(jnp.arange(cfg.L_in)).reshape(-1)

    def per_query(lo_q, hi_q, keys_q, in_keys_q, d_outer_q, heavy_q):
        def probe(lo1, hi1, key1):
            cand = tables.gather_bucket(si_row, lo1, hi1, cfg.c_max)
            if delta is None:
                return cand
            dm = delta.valid & (delta.outer_keys[:, l] == key1)
            return _merge_capped(cand, dm, delta.gidx, cfg.c_max)

        outer_cand = jax.vmap(probe)(lo_q, hi_q, keys_q).reshape(-1)
        outer_cand = jnp.pad(
            outer_cand, (0, slot - outer_cand.shape[0]), constant_values=-1
        )

        if heavy_q is None:
            return outer_cand

        # Stratified bucket: its inner-layer candidates replace the outer.
        found, h, inner_cand = heavy_q
        if inner_cand is None:
            inner_cand = element_probe(h, in_keys_q, d_outer_q)
        inner_cand = jnp.pad(
            inner_cand, (0, slot - cfg.L_in * cfg.c_in), constant_values=-1
        )
        return jnp.where(found, inner_cand, outer_cand)

    cand = jax.vmap(per_query)(lo, hi, probe_keys_t, inner_keys, d_outer, heavy)
    return cand, bucket_sz


@_stage("gather")
def _stage_gather(
    index: SLSHIndex,
    cfg: SLSHConfig,
    probe_keys: jax.Array,  # (Q, L, 1 + multiprobe)
    inner_keys: jax.Array,  # (Q, L_in)
    delta: DeltaView | None = None,
    inner_probe: Callable[..., jax.Array] | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Stage 2 — dense candidate tensor (Q, L*slot) + probed bucket sizes.

    Tables are the outer (vmapped) axis so each table's probes resolve in
    one batched binary search (``_gather_one_table``); the per-(query,
    table) candidate blocks then transpose back to query-major rows. Row
    order differs from the old query-major gather only *within* a row —
    irrelevant after the dedup sort. The heavy-registry match runs once
    for the chunk (``_heavy_match``); with the backend's ``inner_probe``
    and no delta, the kernel reads each pair's heavy bucket as one slab
    (DESIGN.md §3). The path taken is counted as the program traces
    (``count_inner_probe``).
    """
    l_out = index.outer.sorted_keys.shape[0]
    pk_lt = jnp.moveaxis(probe_keys, 1, 0)  # (L, Q, 1 + multiprobe)
    heavy = None
    if cfg.use_inner:
        found, h = _heavy_match(index, probe_keys[:, :, 0])  # (Q, L)
        inner_cand = None
        if inner_probe is not None and delta is None:
            inner_cand = inner_probe(
                index.inner_keys, index.inner_idx, h.T, inner_keys, c_in=cfg.c_in
            )  # (L, Q, L_in * c_in)
        count_inner_probe("element" if inner_cand is None else "slab")
        heavy = (found.T, h.T, inner_cand)
    cand, bucket_sz = jax.vmap(
        lambda l, pk, hv: _gather_one_table(
            index, cfg, l, pk, inner_keys, delta, hv
        )
    )(jnp.arange(l_out), pk_lt, heavy)  # (L, Q, slot), (L, Q)
    cand = jnp.moveaxis(cand, 0, 1).reshape(probe_keys.shape[0], -1)
    return cand, jnp.sum(bucket_sz, axis=0)


def _segmented_searchsorted(
    pool: jax.Array,  # (S,) flat concatenation of sorted segments
    base: jax.Array,  # (...,) int32 segment start offsets into pool
    key: jax.Array,  # (...,) search keys, same shape as base
    width: int,  # segment length (static)
    side_right: bool,
) -> jax.Array:
    """Vectorized binary search inside fixed-width sorted segments.

    Returns the first offset in ``[0, width)`` of ``pool[base:base+width]``
    whose value is ``>= key`` (left) / ``> key`` (right) — one fused
    log-width loop over the whole batch, replacing a vmap swarm of
    per-segment ``searchsorted`` calls in the fast gather.
    """
    lo = jnp.zeros_like(base)
    hi = jnp.full_like(base, width)
    steps = max(1, width.bit_length())  # == ceil(log2(width + 1))
    for _ in range(steps):
        mid = (lo + hi) >> 1
        v = pool[base + jnp.minimum(mid, width - 1)]
        go = (v <= key) if side_right else (v < key)
        go = go & (mid < width)
        lo = jnp.where(go, mid + 1, lo)
        hi = jnp.where(go, hi, mid)
    return lo


@_stage("gather")
def _gather_fast_parts(
    index: SLSHIndex,
    cfg: SLSHConfig,
    probe_keys: jax.Array,  # (Q, L, 1 + multiprobe)
    inner_keys: jax.Array,  # (Q, L_in)
) -> tuple[jax.Array, jax.Array | None, jax.Array | None, jax.Array]:
    """Fast-gather stage, both branch tensors: the work half of stage 2.

    Returns ``(outer_cand (Q, L, slot), inner_cand | None,
    found (Q, L) | None, bucket_total (Q,))``; ``_gather_fast_select``
    blends the branches. Split so the eager schedule can dispatch the two
    halves as separate programs — one program makes XLA CPU fold both
    gather chains into the final select's loop (DESIGN.md §8.6).
    """
    l_out, n = index.outer.sorted_keys.shape
    q_n, _, p_n = probe_keys.shape
    slot, c_max, c_in, l_in = cfg.slot, cfg.c_max, cfg.c_in, cfg.L_in
    pk = jnp.moveaxis(probe_keys, 1, 0)  # (L, Q, P) — small transpose
    lo = jax.vmap(lambda sk, ks: jnp.searchsorted(sk, ks, side="left"))(
        index.outer.sorted_keys, pk.reshape(l_out, -1)
    ).astype(jnp.int32).reshape(l_out, q_n, p_n)
    hi = jax.vmap(lambda sk, ks: jnp.searchsorted(sk, ks, side="right"))(
        index.outer.sorted_keys, pk.reshape(l_out, -1)
    ).astype(jnp.int32).reshape(l_out, q_n, p_n)
    bucket_sz = jnp.sum(hi[:, :, 0] - lo[:, :, 0], axis=0)  # (Q,)
    loq = jnp.moveaxis(lo, 0, 1)  # (Q, L, P)
    hiq = jnp.moveaxis(hi, 0, 1)
    offs = loq[..., None] + jnp.arange(c_max, dtype=jnp.int32)  # (Q,L,P,c_max)
    ok = offs < hiq[..., None]
    flat = (
        jnp.arange(l_out, dtype=jnp.int32)[None, :, None, None] * n
        + jnp.clip(offs, 0, n - 1)
    )
    outer_cand = jnp.where(
        ok,
        index.outer.sorted_idx.reshape(-1)[flat.reshape(-1)].reshape(
            q_n, l_out, p_n, c_max
        ),
        -1,
    ).reshape(q_n, l_out, p_n * c_max)
    outer_cand = jnp.pad(
        outer_cand, ((0, 0), (0, 0), (0, slot - p_n * c_max)), constant_values=-1
    )  # (Q, L, slot)

    if not cfg.use_inner:
        return outer_cand, None, None, bucket_sz

    count_inner_probe("element")
    h_max = index.heavy.keys.shape[1]
    found, h = _heavy_match(index, probe_keys[:, :, 0])  # (Q, L)

    p_in = index.inner_keys.shape[-1]
    ik_pool = index.inner_keys.reshape(-1)
    seg = (
        (jnp.arange(l_out, dtype=jnp.int32)[None, :, None] * h_max + h[:, :, None])
        * l_in
        + jnp.arange(l_in, dtype=jnp.int32)[None, None, :]
    ) * p_in  # (Q, L, L_in) segment bases into the pooled inner tables
    keyq = jnp.broadcast_to(inner_keys[:, None, :], (q_n, l_out, l_in))
    lo2 = _segmented_searchsorted(ik_pool, seg, keyq, p_in, False)
    hi2 = _segmented_searchsorted(ik_pool, seg, keyq, p_in, True)
    offs2 = lo2[..., None] + jnp.arange(c_in, dtype=jnp.int32)  # (Q,L,L_in,c_in)
    ok2 = offs2 < hi2[..., None]
    flat2 = seg[..., None] + jnp.clip(offs2, 0, p_in - 1)
    inner_cand = jnp.where(
        ok2,
        index.inner_idx.reshape(-1)[flat2.reshape(-1)].reshape(
            q_n, l_out, l_in, c_in
        ),
        -1,
    ).reshape(q_n, l_out, l_in * c_in)
    inner_cand = jnp.pad(
        inner_cand, ((0, 0), (0, 0), (0, slot - l_in * c_in)), constant_values=-1
    )
    return outer_cand, inner_cand, found, bucket_sz


@_stage("gather")
def _gather_fast_select(
    cfg: SLSHConfig,
    outer_cand: jax.Array,  # (Q, L, slot)
    inner_cand: jax.Array | None,
    found: jax.Array | None,  # (Q, L)
) -> jax.Array:
    """Blend the fast-gather branches into the (Q, L*slot) candidate rows.

    Selects on the flattened layout: XLA CPU schedules the 2D select
    without folding both gather chains into its loop, which the
    (Q, L, slot) broadcast-select form provokes (~0.6ms/chunk at the
    BENCH_pipeline shape).
    """
    q_n = outer_cand.shape[0]
    if inner_cand is None:
        return outer_cand.reshape(q_n, -1)
    return jnp.where(
        jnp.repeat(found, cfg.slot, axis=1),
        inner_cand.reshape(q_n, -1),
        outer_cand.reshape(q_n, -1),
    )


@_stage("gather")
def _stage_gather_fast(
    index: SLSHIndex,
    cfg: SLSHConfig,
    probe_keys: jax.Array,  # (Q, L, 1 + multiprobe)
    inner_keys: jax.Array,  # (Q, L_in)
) -> tuple[jax.Array, jax.Array]:
    """Stage 2, fused-path formulation: query-major flat gather.

    Produces the same candidate *sets* per (query, table, probe) as
    ``_stage_gather`` — identical results after dedup (pinned by the
    backend-equivalence suite) — but emits the (Q, L*slot) tensor directly
    from flat takes over the CSR arrays: batched searchsorted per table,
    one flat gather for every outer probe window, a segmented binary
    search (``_segmented_searchsorted``) over the pooled inner tables, and
    a single heavy-registry match — no per-query vmap bodies. Rows keep the
    run structure the fused tail's merge network consumes: every
    ``gcd(c_max, c_in, slot)``-aligned slice ascends with -1 only as
    trailing padding. Base (no-delta) path only; delta queries reuse
    ``_stage_gather``'s exact merge and feed the same fused tail. The
    eager schedule dispatches the two halves as separate cached programs
    (``_fused_gather_parts_fn`` / ``_fused_gather_select_fn``).
    """
    outer_cand, inner_cand, found, bucket_sz = _gather_fast_parts(
        index, cfg, probe_keys, inner_keys
    )
    return _gather_fast_select(cfg, outer_cand, inner_cand, found), bucket_sz


@_stage("dedup")
def _stage_dedup(cand: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Stage 3 — static dedup: sort each row; first occurrence survives."""
    cand_sorted = jnp.sort(cand, axis=-1)
    uniq = jnp.concatenate(
        [cand_sorted[:, :1] >= 0, cand_sorted[:, 1:] != cand_sorted[:, :-1]],
        axis=-1,
    ) & (cand_sorted >= 0)
    comparisons = jnp.sum(uniq.astype(jnp.int32), axis=-1)
    return cand_sorted, uniq, comparisons


def _compact_width(cfg: SLSHConfig, c_total: int, n: int) -> int:
    """Static compacted-buffer width for a query chunk.

    Unique survivors are bounded by both the gather width ``c_total`` and
    the indexed point count ``n``, so clamping ``cfg.c_comp`` to either
    never costs exactness — it only trims dead slots (small-n indices get
    tight buffers for free). ``n`` rounds up to the 128-lane width to keep
    the distance-kernel tile shape stable across nearby dataset sizes.
    """
    cc = c_total if cfg.c_comp <= 0 else min(cfg.c_comp, c_total)
    return max(1, min(cc, -(-n // 128) * 128))


@_stage("compact")
def _stage_compact(
    cand_sorted: jax.Array,  # (Q, C)
    uniq: jax.Array,  # (Q, C)
    comparisons: jax.Array,  # (Q,)
    c_comp: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Stage 4 — sort-compact unique survivors into a tight (Q, c_comp) buffer.

    Non-survivors become the max-int sentinel, so one value sort moves the
    deduped candidates (already ascending) to the row front; the gather and
    distance work downstream then scale with the comparison budget instead
    of the ``L_out*slot`` gather width. Unique survivors beyond ``c_comp``
    are *counted* (returned overflow, surfaced in ``QueryResult``), never
    silently dropped; ``comparisons`` itself is untouched by compaction.
    """
    comp = jnp.sort(jnp.where(uniq, cand_sorted, _IDX_SENTINEL), axis=-1)
    comp = comp[:, :c_comp]
    valid = comp != _IDX_SENTINEL
    overflow = jnp.maximum(comparisons - jnp.int32(c_comp), 0)
    return jnp.where(valid, comp, -1), valid, overflow


@_stage("topk")
def _stage_topk(
    data: jax.Array,
    queries: jax.Array,
    cand: jax.Array,  # (Q, c_comp) compacted, ascending, -1 pad
    valid: jax.Array,  # (Q, c_comp)
    cfg: SLSHConfig,
    backend: BackendOps,
) -> tuple[jax.Array, jax.Array]:
    """Stage 5 — one masked L1 top-k over the compacted (Q, c_comp, d) block."""
    pts = data[jnp.clip(cand, 0, data.shape[0] - 1)]  # (Q, c_comp, d)
    kd, pos = backend.l1_topk(queries, pts, valid, cfg.k)
    ki = jnp.where(
        pos >= 0, jnp.take_along_axis(cand, jnp.maximum(pos, 0), axis=-1), -1
    )
    return kd, ki


def _fused_run(cfg: SLSHConfig) -> int:
    """The fused tail's merge-run length for a config's gather layout.

    Every probe window the gather emits is an ascending slice of length
    ``c_max`` (outer) or ``c_in`` (inner), padded to ``slot`` — so every
    ``gcd``-aligned slice of a candidate row ascends, which is the run
    structure ``kernels/query_fused`` merges (DESIGN.md §4).
    """
    run = math.gcd(cfg.c_max, cfg.slot)
    if cfg.use_inner:
        run = math.gcd(run, cfg.c_in)
    return run


def _head_chunk(
    index: SLSHIndex,
    queries: jax.Array,
    cfg: SLSHConfig,
    backend: BackendOps,
    delta: DeltaView | None,
) -> tuple[jax.Array, jax.Array]:
    """Fused-path head (stages 1+2) -> (cand (Q, L*slot), bucket_total (Q,)).

    The base path uses the flat query-major gather; delta queries keep
    ``_stage_gather``'s exact streaming merge (same run structure, so both
    feed the same fused tail — DESIGN.md §9).
    """
    probe_keys, inner_keys = _stage_hash(index, queries, cfg, backend)
    if delta is None:
        return _stage_gather_fast(index, cfg, probe_keys, inner_keys)
    return _stage_gather(index, cfg, probe_keys, inner_keys, delta)


def _use_payload(cfg: SLSHConfig, backend: BackendOps) -> bool:
    """Whether this config runs the compressed-payload fused tail."""
    return cfg.payload != "f32" and backend.query_tail_payload is not None


def _fused_tail(cfg: SLSHConfig, backend: BackendOps) -> bool:
    """Whether stages 3-5 run as one backend tail call (the compressed-
    payload tail, or the backend's f32 ``query_tail``) rather than staged."""
    return _use_payload(cfg, backend) or backend.query_tail is not None


def query_chunk(
    index: SLSHIndex,
    data: jax.Array,
    queries: jax.Array,
    cfg: SLSHConfig,
    delta: DeltaView | None = None,
    payload: Payload | None = None,
) -> QueryResult:
    """Run the pipeline for one (Q, d) chunk of queries.

    ``delta`` fans the gather stage out over base + delta segments (the
    streaming path, DESIGN.md §9); the merged candidates flow through the
    same dedup, compaction, and L1 top-k work, so ``cfg.backend`` dispatch
    covers streaming queries too. Backends providing ``query_tail``
    (pallas, interpreted) run stages 3-5 as one fused megakernel launch
    (``kernels/query_fused``, DESIGN.md §4); the staged form below is the
    reference path, the bit-exactness oracle, and the compiled pallas f32
    tail. When ``cfg.payload`` is
    compressed, the tail streams quantized rows from ``payload`` (built
    here from ``data`` when the caller holds none — handles precompute it
    once) and reranks exactly in f32 (DESIGN.md §13).
    """
    backend = get_backend(cfg.backend, cfg)
    if _fused_tail(cfg, backend):
        cand, bucket_total = _head_chunk(index, queries, cfg, backend, delta)
        cc = _compact_width(cfg, cand.shape[1], data.shape[0])
        if _use_payload(cfg, backend):
            if payload is None:
                payload = make_payload(data, cfg.payload)
            kd, ki, comparisons, overflow, misses = backend.query_tail_payload(
                data, payload.qdata, payload.meta, queries, cand,
                run=_fused_run(cfg), c_comp=cc, c_rerank=cfg.c_rerank, k=cfg.k,
            )
            return QueryResult(
                ki, kd, comparisons, bucket_total, overflow, misses
            )
        kd, ki, comparisons, overflow = backend.query_tail(
            data, queries, cand, run=_fused_run(cfg), c_comp=cc, k=cfg.k
        )
        return QueryResult(ki, kd, comparisons, bucket_total, overflow)
    probe_keys, inner_keys = _stage_hash(index, queries, cfg, backend)
    cand, bucket_total = _stage_gather(
        index, cfg, probe_keys, inner_keys, delta, backend.inner_probe
    )
    cand_sorted, uniq, comparisons = _stage_dedup(cand)
    cc = _compact_width(cfg, cand.shape[1], data.shape[0])
    comp_cand, comp_valid, overflow = _stage_compact(
        cand_sorted, uniq, comparisons, cc
    )
    kd, ki = _stage_topk(data, queries, comp_cand, comp_valid, cfg, backend)
    return QueryResult(ki, kd, comparisons, bucket_total, overflow)


def _contains_tracer(*trees) -> bool:
    """True when any leaf is a tracer (we are inside someone else's jit)."""
    return any(
        isinstance(leaf, jax.core.Tracer)
        for tree in trees
        for leaf in jax.tree.leaves(tree)
    )


@functools.lru_cache(maxsize=64)
def _staged_batch_fn(cfg: SLSHConfig, has_delta: bool):
    """Cached whole-batch jit of the staged pipeline (eager entry points).

    Each jitted body bumps the public ``dslsh_jit_retraces_total``
    counter (``repro.obs``): the body runs only on a compile-cache miss,
    so steady-state dispatch records nothing (DESIGN.md §12).
    """
    if has_delta:
        def run_delta(index, data, queries, delta):
            count_retrace("staged_batch")
            return _chunked_map(
                lambda qs: query_chunk(index, data, qs, cfg, delta),
                queries,
                cfg.query_chunk,
            )

        return jax.jit(run_delta)

    def run(index, data, queries):
        count_retrace("staged_batch")
        return _chunked_map(
            lambda qs: query_chunk(index, data, qs, cfg), queries, cfg.query_chunk
        )

    return jax.jit(run)


@functools.lru_cache(maxsize=64)
def _fused_hash_fn(cfg: SLSHConfig):
    """Cached jit of stage 1 (hash + probe keys) for one config."""
    backend = get_backend(cfg.backend, cfg)

    def run(index, queries):
        count_retrace("hash")
        return _stage_hash(index, queries, cfg, backend)

    return jax.jit(run)


@functools.lru_cache(maxsize=64)
def _fused_gather_parts_fn(cfg: SLSHConfig):
    """Cached jit of the fast gather's work half (base path, stage 2).

    Kept as its *own* dispatch rather than fused with the hash: letting
    XLA schedule the searchsorted/gather stream into the hash program's
    fusions costs ~20% of the head on CPU, the same composition penalty
    that motivates keeping the megakernel tail out of the head program
    (DESIGN.md §8.6).
    """

    def run(index, pk, ik):
        count_retrace("gather_work")
        return _gather_fast_parts(index, cfg, pk, ik)

    return jax.jit(run)


@functools.lru_cache(maxsize=64)
def _fused_gather_select_fn(cfg: SLSHConfig):
    """Cached jit of the fast gather's branch select (base path, stage 2)."""

    def run(oc, ic, f):
        count_retrace("gather_select")
        return _gather_fast_select(cfg, oc, ic, f)

    return jax.jit(run)


@functools.lru_cache(maxsize=64)
def _fused_gather_delta_fn(cfg: SLSHConfig):
    """Cached jit of the exact streaming gather (delta path, stage 2)."""

    def run(index, pk, ik, delta):
        count_retrace("gather_delta")
        return _stage_gather(index, cfg, pk, ik, delta)

    return jax.jit(run)


def _traced_stage(ob, name: str, fn, *args):
    """One traced build phase: span + ``block_until_ready`` sync so the
    span covers real device time, and the duration observed into the
    per-stage latency histogram. Called only when tracing an eager build,
    whose chunked schedule is the same traced or not (DESIGN.md §12.1)."""
    with ob.span(name) as sp:
        out = fn(*args)
        jax.block_until_ready(out)
    if ob.metrics is not None:
        ob.metrics.histogram(
            "dslsh_stage_latency_seconds",
            "device time per eager index-build phase (build.* spans;"
            " recorded only under tracing)",
        ).labels(stage=name).observe(sp.dur_s)
    return out


def _query_batch_fused_eager(
    index: SLSHIndex,
    data: jax.Array,
    queries: jax.Array,
    cfg: SLSHConfig,
    delta: DeltaView | None,
    backend: BackendOps,
    payload: Payload | None = None,
) -> QueryResult:
    """Eager fused execution: hash, gather, and tail as cached jit dispatches.

    Composing pipeline stages into *one* jit makes XLA schedule each
    stage's ops into the previous stage's fusions (each stage output is a
    data dependency), which measurably regresses the chunk — both for the
    megakernel tail behind the head and for the gather stream behind the
    hash. So when the caller is not tracing, the fused path runs a Python
    chunk loop issuing a short schedule of cached dispatches per chunk:
    the hash jit, the gather jits (work + branch select on the base path,
    one exact-merge program on the delta path), and the kernel wrapper.
    Inside an outer jit (tracers
    present) ``query_batch`` falls back to the traceable one-jit
    composition: bit-identical, just not dispatch-optimal (DESIGN.md §4).
    Tracing changes nothing here: the stages' ``dslsh.*`` name scopes
    attribute device time in a profiler trace (DESIGN.md §12.1).
    """
    q_n = queries.shape[0]
    chunk = min(cfg.query_chunk, q_n)
    n_chunks = -(-q_n // chunk)
    pad = n_chunks * chunk - q_n
    qp = jnp.pad(queries, ((0, pad), (0, 0))) if pad else queries
    hash_fn = _fused_hash_fn(cfg)
    if delta is None:
        parts_fn = _fused_gather_parts_fn(cfg)
        select_fn = _fused_gather_select_fn(cfg)
    else:
        gather_fn = _fused_gather_delta_fn(cfg)
    run = _fused_run(cfg)
    cc = _compact_width(cfg, index.outer.sorted_keys.shape[0] * cfg.slot, data.shape[0])
    use_payload = _use_payload(cfg, backend)
    if use_payload and payload is None:
        payload = make_payload(data, cfg.payload)

    def tail(d, q, c):
        if use_payload:
            return backend.query_tail_payload(
                d, payload.qdata, payload.meta, q, c,
                run=run, c_comp=cc, c_rerank=cfg.c_rerank, k=cfg.k,
            )
        return backend.query_tail(d, q, c, run=run, c_comp=cc, k=cfg.k)

    outs = []
    for i in range(n_chunks):
        qs = qp[i * chunk : (i + 1) * chunk]
        pk, ik = hash_fn(index, qs)
        if delta is None:
            oc, ic, fnd, bucket_total = parts_fn(index, pk, ik)
            cand = select_fn(oc, ic, fnd)
        else:
            cand, bucket_total = gather_fn(index, pk, ik, delta)
        out = tail(data, qs, cand)
        if use_payload:
            kd, ki, comparisons, overflow, misses = out
        else:
            (kd, ki, comparisons, overflow), misses = out, None
        outs.append(
            QueryResult(ki, kd, comparisons, bucket_total, overflow, misses)
        )
    if len(outs) == 1:
        res = outs[0]
    else:
        res = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *outs)
    return jax.tree.map(lambda a: a[:q_n], res) if pad else res


def query_batch(
    index: SLSHIndex,
    data: jax.Array,
    queries: jax.Array,
    cfg: SLSHConfig,
    delta: DeltaView | None = None,
    payload: Payload | None = None,
) -> QueryResult:
    """Chunked pipeline over queries -> stacked QueryResult (Q, ...).

    This is each backend's production query path, jit-managed internally:
    called eagerly, the reference backend runs one cached whole-batch jit
    and the pallas backend runs the per-stage fused schedule
    (``_query_batch_fused_eager``). Called under an outer jit (tracer
    inputs), both trace through the chunked pipeline unchanged — results
    are bit-identical either way. ``payload`` is the precomputed quantized
    dataset for compressed-payload configs (``cfg.payload != "f32"``,
    DESIGN.md §13); omitted, the quantization is derived from ``data``.
    """
    if _contains_tracer(index, data, queries, delta, payload):
        backend = get_backend(cfg.backend, cfg)
        if _use_payload(cfg, backend) and payload is None:
            payload = make_payload(data, cfg.payload)
        return _chunked_map(
            lambda qs: query_chunk(index, data, qs, cfg, delta, payload),
            queries,
            cfg.query_chunk,
        )
    backend = get_backend(cfg.backend, cfg)
    if _fused_tail(cfg, backend):
        return _query_batch_fused_eager(
            index, data, queries, cfg, delta, backend, payload
        )
    fn = _staged_batch_fn(cfg, delta is not None)
    if delta is None:
        return fn(index, data, queries)
    return fn(index, data, queries, delta)
