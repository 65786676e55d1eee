"""The L1 distance / top-k kernel's share of its roofline, in %: the least
time its calls need (``roofline.l1_topk_calls``: memory-bound, the
candidate rows read once) over the device time the trace shows."""
from chipbench import readers


def read(run):
    return readers.kernel_roofline(run, "l1_topk")
