"""Plain reference of the DSLSH query semantics (arXiv:1712.00206, Sec. 2-3).

Written from the paper's description and the configuration's stated
parameters, with nothing of the program imported and nothing the program
built taken in: the hash family is drawn again from the build key, every
table is hashed again from the points, and each query is answered by
straightforward set operations in numpy.

The semantics, per cell (node ``i`` holds rows ``[i*n_loc, (i+1)*n_loc)``,
core ``j`` owns outer tables ``[j*L_loc, (j+1)*L_loc)``):

* outer table ``t``: bit ``b`` of a point is ``x[dims[t, b]] > thrs[t, b]``;
  the ``m_out`` bits pack little-endian into 32-bit words and FNV-1a mixes
  the words' bytes, seeded by the table's salt, into the bucket key;
* a bucket is heavy when it holds more than ``int(alpha * n_loc)`` points;
  a table's registry keeps the ``h_max`` largest (ties to the smaller key);
* a query in a registered heavy bucket takes, from each inner table
  (sign random projections, ``x . proj >= 0``), the ``c_in`` smallest
  indices among the bucket's first ``p_max`` points (by index) that share
  its inner key; otherwise it takes the ``c_max`` smallest indices of its
  outer bucket;
* the cell's candidates are the union over its tables; ``comparisons`` is
  their count, the ``c_comp`` smallest indices are kept and the rest are
  counted as ``compaction_overflow``; the cell answers the ``k`` nearest
  kept candidates by L1 distance (ties to the smaller index);
* the cells' answers merge into the ``k`` nearest distinct points over the
  cells' lists taken in cell order (node-major), ties to the earlier entry:
  a point that two cores of one node both return is one neighbour, so the
  answer does not depend on how a node's tables are split over its cores
  (the paper's "parallelism does not influence the prediction output").

The outer and inner keys of every point are computed on the accelerator
(``jax``, in blocks of rows; the projections at full float32 precision),
since at the paper's sizes (120 tables of 125 bits and 20 of 65 over 1.37M
points) numpy would take minutes; the rest is numpy.

``precision="bfloat16"`` computes the same answers from inputs rounded to
bfloat16, the projections in one bfloat16 pass and distances rounded to
bfloat16: the control that a sound comparison has to reject.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

FNV_PRIME = 16777619
FNV_BASIS = 2166136261


@dataclasses.dataclass(frozen=True)
class Family:
    dims: np.ndarray  # (L_out, m_out) int
    thrs: np.ndarray  # (L_out, m_out) float32
    salts: np.ndarray  # (L_out,) uint32
    proj: np.ndarray  # (L_in, d, m_in) float32
    inner_salts: np.ndarray  # (L_in,) uint32


def draw_family(seed: int, d: int, p: dict) -> Family:
    """The hash family the build draws from ``jax.random.PRNGKey(seed)``:
    one key for the outer l1 bit-sampling family (dims, thresholds, salts)
    and one for the inner sign-random-projection family."""
    import jax

    draw = _drawer(d, p["L_out"], p["m_out"], p["L_in"], p["m_in"],
                   float(p["val_lo"]), float(p["val_hi"]))
    dims, thrs, salts, proj, isalts = (
        np.asarray(a) for a in draw(jax.random.PRNGKey(seed))
    )
    return Family(dims, thrs, salts.astype(np.uint32), proj, isalts.astype(np.uint32))


@functools.lru_cache(maxsize=8)
def _drawer(d: int, l_out: int, m_out: int, l_in: int, m_in: int,
            lo: float, hi: float):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def draw(key):
        k_out, k_in = jax.random.split(key)
        kd, kt, ks = jax.random.split(k_out, 3)
        kp, ks2 = jax.random.split(k_in)
        return (
            jax.random.randint(kd, (l_out, m_out), 0, d, dtype=jnp.int32),
            jax.random.uniform(kt, (l_out, m_out), jnp.float32, lo, hi),
            jax.random.randint(ks, (l_out,), 0, 2**31 - 1, dtype=jnp.int32),
            jax.random.normal(kp, (l_in, d, m_in), jnp.float32),
            jax.random.randint(ks2, (l_in,), 0, 2**31 - 1, dtype=jnp.int32),
        )

    return draw


def _bf16(x: np.ndarray) -> np.ndarray:
    """Round float32 values to the nearest bfloat16 (ties to even)."""
    b = np.ascontiguousarray(x, np.float32).view(np.uint32).astype(np.uint64)
    b = (b + 0x7FFF + ((b >> 16) & 1)) & 0xFFFF0000
    return b.astype(np.uint32).view(np.float32)


def _keys(bits, salts):
    """Signature bits (B, L, m) -> bucket keys (B, L) uint32: bit ``b`` of
    a signature is bit ``b % 32`` of word ``b // 32``, and FNV-1a mixes the
    words' bytes, lowest byte first, seeded by each table's salt."""
    import jax.numpy as jnp

    m = bits.shape[-1]
    n_words = -(-m // 32)
    bits = jnp.pad(bits.astype(jnp.uint32), ((0, 0), (0, 0), (0, 32 * n_words - m)))
    words = (bits.reshape(*bits.shape[:2], n_words, 32)
             << jnp.arange(32, dtype=jnp.uint32)).sum(-1, dtype=jnp.uint32)
    h = jnp.broadcast_to(jnp.uint32(FNV_BASIS) ^ salts.astype(jnp.uint32), words.shape[:2])
    for w in range(n_words):
        for shift in (0, 8, 16, 24):
            h = (h ^ ((words[..., w] >> shift) & 0xFF)) * jnp.uint32(FNV_PRIME)
    return h


@functools.lru_cache(maxsize=4)
def _hashers(low: bool):
    """The outer keys (``x[dims] > thrs``) and inner keys (``x . proj >= 0``)
    of a block of rows ``x (B, d)``: (B, L_out) and (B, L_in) uint32. The
    projections run at full float32 precision, or for the control at the
    chip's default, one bfloat16 pass."""
    import jax
    import jax.numpy as jnp

    prec = jax.lax.Precision.DEFAULT if low else jax.lax.Precision.HIGHEST

    @jax.jit
    def outer(x, dims, thrs, salts):
        return _keys(x[:, dims] > thrs[None], salts)

    @jax.jit
    def inner(x, proj, salts):
        return _keys(jnp.einsum("bd,ldm->blm", x, proj, precision=prec) >= 0.0, salts)

    return outer, inner


class Reference:
    """Answers queries over ``points`` as the configuration states.

    ``params`` is the configuration's ``slsh`` group; ``nu`` x ``p`` the
    cell grid. Build work (hashing every point, sorting each table) runs in
    blocks of rows, so that it fits beside whatever else the process holds.
    """

    BLOCK = 1 << 12

    def __init__(self, points: np.ndarray, seed: int, params: dict, nu: int,
                 p: int, precision: str = "float32"):
        if params.get("multiprobe", 0) != 0:
            raise ValueError("the reference covers multiprobe=0 only")
        if precision not in ("float32", "bfloat16"):
            raise ValueError(f"unknown precision {precision!r}")
        self.prm = params
        self.low = precision == "bfloat16"
        self.nu, self.p = nu, p
        n, d = points.shape
        if n % nu or params["L_out"] % p:
            raise ValueError("points and tables must divide across the grid")
        self.n_loc = n // nu
        self.l_loc = params["L_out"] // p
        self.fam = draw_family(seed, d, params)
        self.x = _bf16(points) if self.low else np.asarray(points, np.float32)
        self.thrs = _bf16(self.fam.thrs) if self.low else self.fam.thrs
        self.proj = _bf16(self.fam.proj) if self.low else self.fam.proj
        # every point's inner keys, read for the heavy buckets' populations
        self.inner = self._inner_keys(self.x)
        alpha_n = max(int(params["alpha"] * self.n_loc), 1)
        # per (node, table): stable order of local indices by key, the
        # sorted keys, and the heavy-bucket registry {key: (start, size)}
        self.order, self.sorted, self.heavy = {}, {}, {}
        for i in range(nu):
            order, sk = self._sorted_tables(self.x[i * self.n_loc : (i + 1) * self.n_loc])
            for t in range(params["L_out"]):
                starts = np.flatnonzero(np.r_[True, sk[t, 1:] != sk[t, :-1]])
                sizes = np.diff(np.r_[starts, sk.shape[1]])
                big = np.flatnonzero(sizes > alpha_n)
                # largest first; among equal sizes the smaller key first
                big = big[np.argsort(-sizes[big], kind="stable")][: params["h_max"]]
                self.order[i, t], self.sorted[i, t] = order[t], sk[t]
                self.heavy[i, t] = {
                    int(sk[t, starts[s]]): (int(starts[s]), int(sizes[s])) for s in big
                }

    def _blocks(self, fn, x: np.ndarray, *args):
        import jax.numpy as jnp

        return jnp.concatenate([fn(x[lo : lo + self.BLOCK], *args)
                                for lo in range(0, x.shape[0], self.BLOCK)])

    def _outer_keys(self, x: np.ndarray):
        """(B, d) -> (B, L_out) outer bucket keys, on the accelerator."""
        f = self.fam
        return self._blocks(_hashers(self.low)[0], x, f.dims, self.thrs, f.salts)

    def _inner_keys(self, x: np.ndarray) -> np.ndarray:
        """(B, d) -> (B, L_in) inner bucket keys, on the accelerator."""
        return np.asarray(self._blocks(_hashers(self.low)[1], x, self.proj,
                                       self.fam.inner_salts))

    def _sorted_tables(self, x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """One node's tables: (order, sorted keys), each (L_out, n_loc),
        the order stable among equal keys."""
        import jax.numpy as jnp

        keys = self._outer_keys(x).T
        order = jnp.argsort(keys, axis=1, stable=True)
        return np.asarray(order), np.asarray(jnp.take_along_axis(keys, order, axis=1))

    def _population(self, i: int, t: int, start: int, size: int):
        """Local indices (ascending) and inner keys of a heavy bucket's
        stratified population: its first ``p_max`` points by index."""
        pop = self.order[i, t][start : start + min(size, self.prm["p_max"])]
        return pop, self.inner[i * self.n_loc + pop]

    def _distances(self, q: np.ndarray, rows: np.ndarray) -> np.ndarray:
        dist = np.abs(self.x[rows].astype(np.float64) - q.astype(np.float64)).sum(-1)
        return _bf16(dist.astype(np.float32)).astype(np.float64) if self.low else dist

    def query(self, queries: np.ndarray, merged: int | None = None) -> dict:
        """Answers for (Q, d) queries.

        Returns ``knn_dist`` / ``knn_idx`` (Q, k) (inf / -1 where fewer than
        k candidates), ``merged_dist`` / ``merged_idx`` (Q, ``merged``), the
        merge continued past k for telling near ties apart, and
        ``comparisons`` / ``compaction_overflow`` (nu, p, Q).
        """
        prm, k = self.prm, self.prm["k"]
        merged = merged or 2 * k
        qx = _bf16(queries) if self.low else np.asarray(queries, np.float32)
        q_n = qx.shape[0]
        okeys = np.asarray(self._outer_keys(qx))
        ikeys = self._inner_keys(qx)
        comps = np.zeros((self.nu, self.p, q_n), np.int64)
        over = np.zeros((self.nu, self.p, q_n), np.int64)
        md = np.full((q_n, merged), np.inf)
        mi = np.full((q_n, merged), -1, np.int64)
        c_comp = prm["c_comp"] if prm["c_comp"] > 0 else None
        # every query's candidates in every (node, table), one table at a time
        cand = [[[] for _ in range(self.nu * self.p)] for _ in range(q_n)]
        for (i, t), sk in self.sorted.items():
            cell = i * self.p + t // self.l_loc
            order, heavy = self.order[i, t], self.heavy[i, t]
            lo = np.searchsorted(sk, okeys[:, t], "left").tolist()
            hi = np.searchsorted(sk, okeys[:, t], "right").tolist()
            in_heavy: dict = {}
            for r, key in enumerate(okeys[:, t].tolist()):
                hv = heavy.get(key)
                if hv is None:
                    cand[r][cell].append(order[lo[r] : min(hi[r], lo[r] + prm["c_max"])])
                else:
                    in_heavy.setdefault(hv, []).append(r)
            for hv, rs in in_heavy.items():
                # each inner table: the first c_in of the population that
                # share the query's inner key
                pop, pk = self._population(i, t, *hv)
                same = pk[None] == ikeys[rs][:, None]  # (R, P, L_in)
                same &= np.cumsum(same, axis=1, dtype=np.int32) <= prm["c_in"]
                for r, hit in zip(rs, same.any(axis=2)):
                    cand[r][cell].append(pop[hit])
        for r in range(q_n):
            lists = []
            for i in range(self.nu):
                for j in range(self.p):
                    c = cand[r][i * self.p + j]
                    u = np.unique(np.concatenate(c)) if c else np.zeros(0, np.int64)
                    comps[i, j, r] = u.shape[0]
                    if c_comp is not None:
                        over[i, j, r] = max(u.shape[0] - c_comp, 0)
                        u = u[:c_comp]
                    rows = i * self.n_loc + u
                    dist = self._distances(qx[r], rows)
                    top = np.lexsort((rows, dist))[:k]
                    lists.append((dist[top], rows[top]))
            d_all = np.concatenate([d for d, _ in lists])
            i_all = np.concatenate([g for _, g in lists])
            # cell order is the concatenation order; a stable sort by
            # distance keeps the earlier cell first among equal distances,
            # and a point met again later in that order is the same neighbour
            by = np.argsort(d_all, kind="stable")
            _, first = np.unique(i_all[by], return_index=True)
            pick = by[np.sort(first)][:merged]
            md[r, : pick.shape[0]] = d_all[pick]
            mi[r, : pick.shape[0]] = i_all[pick]
        return {
            "knn_dist": md[:, :k], "knn_idx": mi[:, :k],
            "merged_dist": md, "merged_idx": mi,
            "comparisons": comps, "compaction_overflow": over,
        }
