"""The stencil kernels' share of their roofline: the least time any
implementation could take over the traced window (the compulsory HBM
bytes of its dispatches at the chip's peak bandwidth) over the device
time of stencil compute, per chip, averaged."""

from perfbench import trace as tr


def read(ctx):
    if ctx["peak"] is None:
        return None
    t = [tr.length(v) for v in
         tr.per_device(ctx["trace"], {"compute"}).values()]
    if not t or not all(t) or not ctx["dispatches"]:
        return None
    least_s = (ctx["compulsory_bytes"] * ctx["dispatches"]
               / ctx["peak"]["hbm_bytes_per_s"])
    return 100.0 * sum(least_s / (x * 1e-9) for x in t) / len(t)
