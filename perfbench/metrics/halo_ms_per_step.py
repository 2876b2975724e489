"""Device time of the collectives (the halo exchange: their start and
done ops, the done's time being the wait for the transfer) in the traced
window, per step, averaged over the chips.  Nothing to read where no
collective ran."""

from perfbench import trace as tr


def read(ctx):
    t = [tr.length(v) for v in
         tr.per_device(ctx["trace"], {"collective"}).values()]
    if not t or not any(t) or not ctx["steps"]:
        return None
    return sum(t) / len(t) * 1e-6 / ctx["steps"]
