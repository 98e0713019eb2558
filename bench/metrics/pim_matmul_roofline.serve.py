"""``pim_matmul``'s share of its roofline over the serving window: for
each logical call (a decode step drives every slot's row, a prefill the
padded prompt) the larger of 2*M*K*N over the int8 peak and its bytes at
the configured bit widths over HBM bandwidth, summed, over the kernel's
summed device time."""
from harness import counts


def read(run):
    return counts.roofline_share(run, "pim_matmul")
