"""Arithmetic shared by the metric readers in ``metrics/``.

Each reader is a file ``metrics/<metric>.py`` with ``read(run)``, which
returns the metric's value or None where the run holds nothing to read
(no trace, no such kernel in it): the harness then leaves the metric out.
"""
from __future__ import annotations

import numpy as np

from chipbench import roofline, traffic
from chipbench import trace as tr


def latencies_ms(run) -> list[float]:
    """Latency of every answered request of the window, in ms."""
    return [1e3 * t.latency_s for t in run.tickets if t.status == "done"]


def latency_percentile(run, q: float):
    lat = latencies_ms(run)
    return traffic.percentile(lat, q) if lat else None


def pump_spans(run) -> list[tuple[float, float]]:
    """The traced window's ``bench.pump`` spans, in order; the k-th is the
    k-th of :func:`traced_batches`."""
    w0, w1 = run.trace.window
    return sorted(s for s in run.trace.host.get("bench.pump", []) if w0 <= s[0] < w1)


def traced_batches(run) -> list:
    return [b for b in run.batches if b.traced]


def busy_per_chip_ns(run, spans) -> float:
    """Device-busy time inside ``spans``, averaged over the chips."""
    chips = run.trace.chips
    if not chips:
        return 0.0
    return sum(tr.busy_ns(ops, spans) for ops in chips) / len(chips)


def device_ms_per_batch(run):
    if run.trace is None or not run.trace.chips:
        return None
    spans = pump_spans(run)
    if not spans:
        return None
    return 1e-6 * busy_per_chip_ns(run, [run.trace.window]) / len(spans)


def kernel_roofline(run, kernel: str):
    """Least time the batches' calls of ``kernel`` need, over the time the
    trace shows them taking, in %; None where the trace shows no call."""
    if run.trace is None or not run.trace.chips:
        return None
    spans = pump_spans(run)
    took = np.zeros(len(spans))
    for ops in run.trace.chips:
        took += tr.per_span_ns(ops, spans, ops.flags[kernel])
    calls = roofline.CALLS[kernel]
    spent = least = 0.0
    for ns, batch in zip(took, traced_batches(run)):
        if ns <= 0:
            continue
        spent += ns * 1e-9
        t, _ = roofline.least_time(calls(run.config, batch.bucket, run.chips),
                                   run.device_kind)
        least += t * run.chips
    if spent <= 0:
        return None
    return 100.0 * least / spent
