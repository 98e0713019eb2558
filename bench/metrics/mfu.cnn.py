"""The whole forward's share of the chip's int8 peak: 2 * MACs of every
conv and dense layer per image (one pass per logical multiply, no
padding), times the images completed in the window, over the traced
window, divided by the peak times the chips."""


def read(run):
    window = run.trace.window_s if run.trace else run.window_s
    if not run.true_int_ops or window <= 0:
        return None
    rate = run.true_int_ops / window
    return 100.0 * rate / (run.peaks["int8_ops_per_s"] * run.chips)
