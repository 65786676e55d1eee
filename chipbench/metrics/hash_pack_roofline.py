"""The hash kernels' share of their roofline, in %: the least time their
calls need (``roofline.hash_pack_calls``: memory-bound at these shapes)
over the device time the trace shows them taking."""
from chipbench import readers


def read(run):
    return readers.kernel_roofline(run, "hash_pack")
