"""Mean host time of one ladder dispatch that compiled nothing (the
program's `igg_dispatch_host_seconds` histogram: admission walk, the SPMD
wrapper's key and lookup, the enqueue), in ms, over the run's dispatches
so far.  Nothing to read where the program keeps no such counter."""

PREFIX = "igg_dispatch_host_seconds{"


def read(ctx):
    from igg import telemetry

    hist = [v for k, v in telemetry.snapshot().items()
            if k.startswith(PREFIX)]
    n = sum(h["count"] for h in hist)
    return sum(h["sum"] for h in hist) / n * 1e3 if n else None
