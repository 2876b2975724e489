"""Device time of stencil compute (every leaf op that is not a collective
and not a host transfer) in the traced window, per step, averaged over
the chips."""

from perfbench import trace as tr


def read(ctx):
    t = [tr.length(v) for v in
         tr.per_device(ctx["trace"], {"compute"}).values()]
    if not t or not any(t) or not ctx["steps"]:
        return None
    return sum(t) / len(t) * 1e-6 / ctx["steps"]
