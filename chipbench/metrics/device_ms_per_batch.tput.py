"""Device-busy time in the traced window over the micro-batches the
window executed, in ms (chips averaged)."""
from chipbench import readers


def read(run):
    return readers.device_ms_per_batch(run)
