"""Share of the whole traced window in which no operation ran on the
device, in % (chips averaged)."""
from chipbench import readers


def read(run):
    if run.trace is None or not run.trace.chips:
        return None
    w0, w1 = run.trace.window
    return 100.0 * (1.0 - readers.busy_per_chip_ns(run, [(w0, w1)]) / (w1 - w0))
