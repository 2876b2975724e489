"""1 - (union of the device-op intervals / traced window), averaged over
the chips."""

from perfbench import trace as tr


def read(ctx):
    lo, hi = ctx["trace"].window()
    busy = [tr.length(v) for v in tr.per_device(ctx["trace"]).values()]
    if not busy or hi <= lo:
        return None
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))
