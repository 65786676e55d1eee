"""One general traffic generator, driven by a mix's parameter file.

A mix (``chipbench/traffic/<name>.json``) gives:

* ``loop``: ``"open"`` (requests due on a schedule, whatever the server
  does) or ``"closed"`` (``clients`` callers, each with one request
  outstanding);
* ``rate_per_s`` (open loop): the offered request rate. Arrivals are
  Poisson: exponential gaps, scaled so that exactly ``round(rate *
  seconds)`` requests fall inside the window;
* ``rows``: rows per request as ``[lo, hi, weight]`` segments, each
  uniform over ``lo..hi``;
* ``tenants`` / ``zipf_s``: tenant count and the Zipf exponent of their
  shares;
* ``ladder``, ``deadline_s``: the front end's micro-batch rungs and the
  request deadline (``null`` = none);
* ``pool_rows``: held-out query rows drawn from the stream;
* ``check_rows``: rows of finished requests compared with the reference.
* ``basis``: where the mix's parameters come from, in words (not read).

Every seed gets the same multiset of request sizes, tenants and gaps,
drawn from ``shape_seed``; ``--seed`` only permutes them and picks the
query rows, so that seeds change the order of the work and not its amount.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Request:
    due_s: float  # open loop: offset from the window's start
    rows: np.ndarray  # (r, d) float32 query rows
    tenant: str


def _sizes(mix: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    segs = np.asarray(mix["rows"], np.float64)
    w = segs[:, 2] / segs[:, 2].sum()
    seg = rng.choice(len(segs), size=n, p=w)
    lo, hi = segs[seg, 0].astype(int), segs[seg, 1].astype(int)
    return lo + (rng.random(n) * (hi - lo + 1)).astype(int)


def _tenants(mix: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    ranks = np.arange(1, mix["tenants"] + 1, dtype=np.float64)
    share = ranks ** -float(mix["zipf_s"])
    return rng.choice(mix["tenants"], size=n, p=share / share.sum())


def requests(mix: dict, seed: int, seconds: float, pool: np.ndarray) -> list[Request]:
    """The cell's requests, in the order they are sent.

    Open loop: the whole window's schedule. Closed loop: a sequence long
    enough for the window, which the clients take in turn.
    """
    shape = np.random.default_rng(int(mix.get("shape_seed", 0)))
    if mix["loop"] == "open":
        n = max(int(round(float(mix["rate_per_s"]) * seconds)), 1)
        gaps = shape.exponential(1.0, n)
        gaps *= seconds / gaps.sum()
    elif mix["loop"] == "closed":
        n = int(mix["sequence"])
        gaps = np.zeros(n)
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    sizes = _sizes(mix, shape, n)
    tenants = _tenants(mix, shape, n)
    order = np.random.default_rng(np.random.SeedSequence([seed, 1]))
    gaps = gaps[order.permutation(n)]
    sizes = sizes[order.permutation(n)]
    tenants = tenants[order.permutation(n)]
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    out, lo = [], 0
    for i in range(n):
        idx = (lo + np.arange(sizes[i])) % pool.shape[0]
        out.append(Request(float(due[i]), pool[idx], f"t{tenants[i]}"))
        lo += int(sizes[i])
    return out


def percentile(values, q: float) -> float:
    """The ``q``-th percentile of every value, linearly interpolated
    between order statistics (numpy's default rule)."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        raise ValueError("percentile of no values")
    pos = (v.size - 1) * q / 100.0
    lo = int(np.floor(pos))
    hi = min(lo + 1, v.size - 1)
    return float(v[lo] + (v[hi] - v[lo]) * (pos - lo))
