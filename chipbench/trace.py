"""Reduction of a profiler trace to device time, idle gaps and op totals.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes, through
``jax.profiler.ProfileData``, into a :class:`Trace`: for each chip the
operations that ran on it (from the device plane's ``XLA Ops`` line), and
the harness's own host spans (``bench.window``, ``bench.pump``,
``bench.submit``, ``bench.wait``), all in nanoseconds on the profile's
clock. Everything after that is plain interval arithmetic on the
:class:`Trace`, so that tests can hand it synthetic traces. An operation
belongs to a span when its midpoint lies inside it.

    python chipbench/trace.py <dir with .xplane.pb>   # print what a trace holds
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
import sys

import numpy as np

HOST_SPANS = ("bench.window", "bench.pump", "bench.submit", "bench.wait")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"^%(all-reduce|all-gather|collective-permute|all-to-all|reduce-scatter"
    r"|send|recv)[-.a-z0-9]*\s"
)
#: control flow whose span holds the operations of its body
CONTAINER = re.compile(r"^%(while|conditional|call)[.\s]")


#: what the readers ask of each operation, matched once as the trace loads
MATCH = {
    "collective": COLLECTIVE,
    # the Mosaic calls are named after their jitted wrappers, as a TPU v5e
    # trace shows them: "%hash_pack_pallas.18 = u32[8,16] custom-call(...)"
    "hash_pack": re.compile(r"^%(hash_pack(_margins)?|bitsample_gather(_margins)?)_pallas"),
    "l1_topk": re.compile(r"^%l1_pallas"),
}
NAME_CHARS = 160  # of an operation's name kept for the breakdown


@dataclasses.dataclass
class Ops:
    """The operations of one chip, ordered by start (ns), with one flag per
    entry of ``MATCH`` (whether the full name matched it)."""

    names: list[str]
    start: np.ndarray
    end: np.ndarray
    flags: dict[str, np.ndarray]

    @classmethod
    def of(cls, rows) -> "Ops":
        """From ``(name, start_ns, end_ns)`` rows."""
        rows = sorted(rows, key=lambda r: r[1])
        return cls(
            [r[0][:NAME_CHARS] for r in rows],
            np.asarray([r[1] for r in rows], np.float64),
            np.asarray([r[2] for r in rows], np.float64),
            {k: np.fromiter((bool(p.search(r[0])) for r in rows), bool, len(rows))
             for k, p in MATCH.items()},
        )


@dataclasses.dataclass
class Trace:
    chips: list[Ops]
    host: dict[str, list[tuple[float, float]]]  # span name -> intervals (ns)

    @property
    def window(self) -> tuple[float, float]:
        """The measured window: the ``bench.window`` span."""
        spans = self.host.get("bench.window") or []
        if len(spans) != 1:
            raise ValueError(f"expected one bench.window span, found {len(spans)}")
        return spans[0]


def find_xplane(logdir: str) -> str:
    found = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` into a :class:`Trace`."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    chips: dict[int, Ops] = {}
    host: dict[str, list[tuple[float, float]]] = collections.defaultdict(list)
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            rows = [
                (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                for line in plane.lines if line.name == OPS_LINE
                for ev in line.events
            ]
            chips[int(m.group(1))] = Ops.of(rows)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS:
                        host[ev.name].append((ev.start_ns, ev.start_ns + ev.duration_ns))
    return Trace([chips[c] for c in sorted(chips)], dict(host))


# ------------------------------------------------------- interval arithmetic


def union(intervals) -> list[tuple[float, float]]:
    """Merged, ordered intervals covering the same points."""
    iv = np.asarray(sorted(intervals), np.float64).reshape(-1, 2)
    if iv.shape[0] == 0:
        return []
    reach = np.maximum.accumulate(iv[:, 1])
    new = np.r_[True, iv[1:, 0] > reach[:-1]]
    first = np.flatnonzero(new)
    last = np.r_[first[1:] - 1, iv.shape[0] - 1]
    return list(zip(iv[first, 0].tolist(), reach[last].tolist()))


def overlap(a: list[tuple[float, float]], b: list[tuple[float, float]]) -> float:
    """Length of the intersection of two merged interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def busy_ns(ops: Ops, spans) -> float:
    """Time inside ``spans`` in which some operation ran on the chip."""
    return overlap(union(zip(ops.start, ops.end)), union(spans))


def op_ns(ops: Ops, spans, flag: str) -> tuple[float, int]:
    """(summed duration, count) of the operations flagged ``flag`` whose
    midpoint lies inside ``spans``."""
    keep = ops.flags[flag] & _inside(ops, spans)
    return float((ops.end - ops.start)[keep].sum()), int(keep.sum())


def _inside(ops: Ops, spans) -> np.ndarray:
    """Which operations have their midpoint inside ``spans``."""
    merged = union(spans)
    if not merged:
        return np.zeros(ops.start.shape, bool)
    lo = np.asarray([a for a, _ in merged])
    hi = np.asarray([b for _, b in merged])
    mid = 0.5 * (ops.start + ops.end)
    k = np.searchsorted(lo, mid, side="right") - 1
    return (k >= 0) & (mid < hi[np.maximum(k, 0)])


def per_span_ns(ops: Ops, spans: list[tuple[float, float]],
                keep: np.ndarray) -> np.ndarray:
    """Summed duration of the kept operations in each of ``spans`` (ordered
    and disjoint), by midpoint."""
    lo = np.asarray([a for a, _ in spans], np.float64)
    hi = np.asarray([b for _, b in spans], np.float64)
    out = np.zeros(len(spans))
    if not len(spans):
        return out
    mid = 0.5 * (ops.start + ops.end)
    k = np.searchsorted(lo, mid, side="right") - 1
    ok = keep & (k >= 0) & (mid < hi[np.maximum(k, 0)])
    np.add.at(out, k[ok], (ops.end - ops.start)[ok])
    return out


def top_ops(trace: Trace, spans, n: int = 10) -> list[list]:
    """The ``n`` operation names with the most device time inside
    ``spans`` (by midpoint), summed over chips, loops and calls left out
    (their bodies' operations count): ``[[name, seconds], ...]``."""
    acc: collections.Counter = collections.Counter()
    for ops in trace.chips:
        dur = ops.end - ops.start
        for i in np.flatnonzero(_inside(ops, spans)):
            if not CONTAINER.match(ops.names[i]):
                acc[ops.names[i]] += dur[i]
    return [[name, float(ns) * 1e-9] for name, ns in acc.most_common(n)]


def idle_gaps(trace: Trace, n: int = 10) -> list[list]:
    """The ``n`` longest stretches of the window in which no chip ran an
    operation, each named by the host span that holds its midpoint:
    ``[[label, seconds], ...]``."""
    w0, w1 = trace.window
    busy = union(
        (max(a, w0), min(b, w1))
        for ops in trace.chips for a, b in zip(ops.start, ops.end)
        if b > w0 and a < w1
    )
    gaps, cur = [], w0
    for a, b in busy:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if w1 > cur:
        gaps.append((cur, w1))
    labelled = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = 0.5 * (a + b)
        label = "outside any span"
        for name in ("bench.pump", "bench.submit", "bench.wait"):
            if any(s <= mid < e for s, e in trace.host.get(name, ())):
                label = name
                break
        labelled.append([label, (b - a) * 1e-9])
    return labelled


def main(argv: list[str]) -> None:
    path = find_xplane(argv[0]) if os.path.isdir(argv[0]) else argv[0]
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"plane {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            kinds = collections.Counter(
                re.sub(r"[._]?\d+$", "", e.name.split(" ", 1)[0]) for e in events
            )
            print(f"  line {line.name!r}: {len(events)} events;"
                  f" by kind {kinds.most_common(25)}")
            seen = set()
            for e in events:
                kind = e.name.split(" ", 1)[0]
                if ("custom" in e.name or "kernel" in e.name
                        or any(p.search(e.name) for p in MATCH.values())):
                    key = re.sub(r"\d+", "", kind)
                    if key not in seen and len(seen) < 8:
                        seen.add(key)
                        print(f"    sample {e.duration_ns}ns: {e.name[:1500]}")


if __name__ == "__main__":
    main(sys.argv[1:])
