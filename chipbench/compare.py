"""The comparison that decides ``correct``.

After the window, a sample of the requests the front end answered is drawn
from the seed (the longest request first, then others until ``check_rows``
rows), with each request's answer as its ticket carries it and the
counters of the micro-batch it rode in. The reference answers the same
rows, and each number below is held to its limit from the configuration:

* ``dist_gap``: the widest gap between a served neighbour distance and the
  reference's at the same rank, relative to the reference (floor 1), with
  ``1e9`` where one side has a neighbour and the other has none;
* ``idx_miss``: ranks whose served neighbour differs from the reference's,
  not counting a served neighbour that the reference places within ``TIE``
  of the distance at that rank (an equally near answer is not a wrong one);
* ``counter_miss``: (cell, row) pairs whose ``comparisons`` or
  ``compaction_overflow`` counter differs;
* ``unanswered``: requests of the window that were not answered.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from chipbench import reference

TIE = 1e-5  # relative distance within which two neighbours are equally near
NO_ANSWER = 1e9


@dataclasses.dataclass
class Sample:
    queries: np.ndarray  # (R, d)
    knn_idx: np.ndarray  # (R, k) as served
    knn_dist: np.ndarray
    comparisons: np.ndarray  # (nu, p, R) of the batches the rows rode in
    compaction_overflow: np.ndarray
    requests: int


def sample(run) -> Sample:
    """Draw the requests to check from the seed and pull what the program
    answered for them off the device."""
    rides = [(t, b, lo, hi) for b in run.batches for t, lo, hi in b.spans]
    if not rides:
        raise RuntimeError("no request was answered in the window")
    rng = np.random.default_rng(np.random.SeedSequence([run.seed, 2]))
    order = list(rng.permutation(len(rides)))
    longest = max(range(len(rides)), key=lambda i: rides[i][0].n_queries)
    order.remove(longest)
    picked, rows = [], 0
    for i in [longest] + order:
        if rows >= run.mix["check_rows"]:
            break
        picked.append(rides[i])
        rows += rides[i][0].n_queries
    comps, over = [], []
    for t, b, lo, hi in picked:
        comps.append(np.asarray(b.result.comparisons)[:, :, lo:hi])
        over.append(np.asarray(b.result.compaction_overflow)[:, :, lo:hi])
    return Sample(
        np.concatenate([t.queries for t, *_ in picked]),
        np.concatenate([t.knn_idx for t, *_ in picked]).astype(np.int64),
        np.concatenate([t.knn_dist for t, *_ in picked]).astype(np.float64),
        np.concatenate(comps, axis=2), np.concatenate(over, axis=2), len(picked),
    )


def answers(run, points: np.ndarray, queries: np.ndarray,
            precision: str = "float32") -> dict:
    """The reference's answers to ``queries`` over the cell's data."""
    dep = run.config["deployment"]
    ref = reference.Reference(points, run.seed, run.config["slsh"], dep["nu"],
                              dep["p"], precision)
    return ref.query(queries)


def numbers(got: dict, want: dict) -> dict:
    """The compared numbers of ``got`` (served answers) against ``want``
    (the reference's, with its merge continued past k)."""
    gd, wd = got["knn_dist"], want["knn_dist"]
    fin_g, fin_w = np.isfinite(gd), np.isfinite(wd)
    both = fin_g & fin_w
    rel = np.abs(gd - wd) / np.maximum(np.abs(wd), 1.0)
    gap = float(rel[both].max()) if both.any() else 0.0
    if (fin_g != fin_w).any():
        gap = NO_ANSWER
    gi, wi = got["knn_idx"], want["knn_idx"]
    md, mi = want["merged_dist"], want["merged_idx"]
    miss = 0
    for r, c in zip(*np.nonzero(gi != wi)):
        near = np.abs(md[r] - wd[r, c]) <= TIE * max(abs(wd[r, c]), 1.0)
        if not (near & (mi[r] == gi[r, c])).any():
            miss += 1
    counters = (got["comparisons"] != want["comparisons"]) | (
        got["compaction_overflow"] != want["compaction_overflow"])
    return {"dist_gap": gap, "idx_miss": int(miss), "counter_miss": int(counters.sum())}


def repeated_rows(knn_idx: np.ndarray) -> int:
    """Rows of an answer that name one point twice among their neighbours."""
    return sum(len(set(r[r >= 0].tolist())) < int((r >= 0).sum()) for r in knn_idx)


def judge(config: dict, values: dict) -> dict:
    """Each number beside its limit from the configuration."""
    limits = config["limits"]
    return {k: {"value": v, "limit": limits[k]} for k, v in values.items()}


def passed(compared: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in compared.values())
