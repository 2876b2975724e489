"""Host clock from the first call of the built step to its completion,
inside set-up: tracing, lowering, compiling (or the cache lookup) and one
dispatch of the factory and SPMD wrapper."""


def read(ctx):
    return ctx["first_dispatch_s"]
