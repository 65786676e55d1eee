"""95th percentile of the latency of every answered request of the window,
from when it was due (its open-loop schedule time) to when its answer was
final."""
from chipbench import readers


def read(run):
    return readers.latency_percentile(run, 95)
