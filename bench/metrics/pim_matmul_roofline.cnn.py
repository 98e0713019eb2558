"""``pim_matmul``'s share of its roofline over the image window: for
each conv's im2col GEMM (rows = output pixels of the batch) and the
dense head, the larger of 2*M*K*N over the int8 peak and its bytes at
the configured bit widths over HBM bandwidth, summed over the batches
dispatched, over the kernel's summed device time."""
from harness import counts


def read(run):
    return counts.roofline_share(run, "pim_matmul")
