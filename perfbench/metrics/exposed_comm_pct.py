"""Share of the collectives' in-flight time (start to done, from the
trace's "Async XLA Ops" line) during which no compute op runs on that
device, averaged over the devices whose trace has that line (the
profiler writes it for the first chip).  Nothing to read where no
asynchronous collective ran."""

from perfbench import trace as tr


def read(ctx):
    flying = tr.per_device(ctx["trace"], {"collective"}, line="inflight")
    comp = tr.per_device(ctx["trace"], {"compute"})
    shares = []
    for dev, c in flying.items():
        total = tr.length(c)
        if total:
            shares.append(tr.length(tr.subtract(c, comp.get(dev, [])))
                          / total)
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
