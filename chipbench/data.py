"""Seeded synthetic AHE window stream (the benchmark's own copy).

A copy of the block-seeded generator of ``repro.data.windows``
(``SyntheticWindowSpec`` / ``synth_window_block``): each row is a d=30
vector of per-subwindow mean arterial pressure, a per-window baseline plus
subwindow noise, and a minority of windows ramping down toward a
hypotensive tail. Block ``j`` always draws from ``SeedSequence([seed, j])``
over the whole fixed block, so row ``r`` depends only on ``(seed, r)``.
Kept here so that no change to the program can move the benchmark's data.
"""
from __future__ import annotations

import numpy as np

GEN_BLOCK = 4096
AHE_THRESHOLD_MMHG = 60.0

# the shape of the stream, as the program's scale harness defines it
BASELINE_LO, BASELINE_HI = 68.0, 95.0
NOISE_MMHG = 2.0
DIP_FRAC = 0.08
DIP_LO, DIP_HI = 15.0, 40.0


def block(seed: int, j: int, d: int) -> np.ndarray:
    """Full generation block ``j`` of the stream: (GEN_BLOCK, d) float32."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, j]))
    b = GEN_BLOCK
    baseline = rng.uniform(BASELINE_LO, BASELINE_HI, size=(b, 1))
    noise = rng.normal(0.0, NOISE_MMHG, size=(b, d))
    dip = rng.random(b) < DIP_FRAC
    depth = rng.uniform(DIP_LO, DIP_HI, size=b)
    ramp = np.linspace(0.0, 1.0, d) ** 2
    pts = baseline + noise - (dip * depth)[:, None] * ramp[None, :]
    return np.clip(pts, 20.0, 180.0).astype(np.float32)


def rows(seed: int, lo: int, hi: int, d: int) -> np.ndarray:
    """Rows ``[lo, hi)`` of the stream for ``seed``: (hi - lo, d) float32."""
    if not 0 <= lo <= hi:
        raise ValueError(f"bad row range [{lo}, {hi})")
    out = np.empty((hi - lo, d), np.float32)
    pos = lo
    while pos < hi:
        j = pos // GEN_BLOCK
        a = pos - j * GEN_BLOCK
        b = min(hi - j * GEN_BLOCK, GEN_BLOCK)
        out[pos - lo : pos - lo + (b - a)] = block(seed, j, d)[a:b]
        pos += b - a
    return out


def dataset(seed: int, n: int, n_pool: int, d: int) -> tuple[np.ndarray, np.ndarray]:
    """(points (n, d), query pool (n_pool, d)): the first ``n`` rows are
    indexed, the next ``n_pool`` rows are held out as queries."""
    both = rows(seed, 0, n + n_pool, d)
    return both[:n], both[n:]
