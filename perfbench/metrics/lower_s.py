"""Seconds the program spent tracing to jaxprs and lowering to MLIR (Pallas
kernel tracing and Mosaic lowering included) inside its own dispatches,
before the window: `igg_compile_seconds{stage="trace"}` plus
`{stage="lower"}`.  Compiles of the benchmark's own programs are not
booked there.  Nothing to read where the program keeps no such counter."""

KEYS = ('igg_compile_seconds{stage="trace"}',
        'igg_compile_seconds{stage="lower"}')


def read(ctx):
    from igg import telemetry

    snap = telemetry.snapshot()
    got = [snap[k]["sum"] for k in KEYS if k in snap]
    return sum(got) if got else None
