"""The whole step's share of the chip's peak: the compulsory HBM bytes of
the traced window's dispatches at the peak bandwidth, over the window's
length.  This stencil is bound by memory and no float32 FLOP peak of the
chip is published, so the peak is the HBM bandwidth.  It bounds every
kernel's roofline share from below whatever kernel serves the step."""


def read(ctx):
    if ctx["peak"] is None:
        return None
    lo, hi = ctx["trace"].window()
    if hi <= lo or not ctx["dispatches"]:
        return None
    least_s = (ctx["compulsory_bytes"] * ctx["dispatches"]
               / ctx["peak"]["hbm_bytes_per_s"])
    return 100.0 * least_s / ((hi - lo) * 1e-9)
