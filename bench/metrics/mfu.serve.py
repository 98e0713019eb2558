"""The whole serving step's share of the chip's int8 peak: integer
operations of the programmed projections at true lengths (real prompt
tokens and real decoded tokens; no padding, no empty slots, one pass per
logical multiply whatever the nibble planes) over the window, divided by
the peak times the chips."""


def read(run):
    window = run.trace.window_s if run.trace else run.window_s
    if not run.true_int_ops or window <= 0:
        return None
    rate = run.true_int_ops / window
    return 100.0 * rate / (run.peaks["int8_ops_per_s"] * run.chips)
