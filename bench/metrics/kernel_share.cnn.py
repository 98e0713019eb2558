"""Share of the device's busy time spent in the ``pim_matmul`` kernel;
the rest is the executor's work around it in XLA: im2col, activation
quantization, padding, pooling, ReLU and residual adds."""


def read(run):
    t = run.trace.kernel_s.get("pim_matmul") if run.trace else None
    if not t or run.trace.busy_s <= 0:
        return None
    return 100.0 * t / run.trace.busy_s
