"""Share of the time inside the harness's ``bench.pump`` spans in which no
operation ran on the device, in % (chips averaged). Time with an empty
queue is not counted."""
from chipbench import readers
from chipbench import trace as tr


def read(run):
    if run.trace is None or not run.trace.chips:
        return None
    spans = tr.union(readers.pump_spans(run))
    total = sum(b - a for a, b in spans)
    if total <= 0:
        return None
    return 100.0 * (1.0 - readers.busy_per_chip_ns(run, spans) / total)
