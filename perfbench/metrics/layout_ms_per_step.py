"""Device time of the stencil layer's ops that are not Mosaic kernels
(leaf ops whose innermost program scope is `igg.stencil`: lane pads,
central-window slices, the per-dispatch coefficient, copies around the
kernels) in the traced window, per step, averaged over the chips.
Nothing to read where the program names no scopes."""

from perfbench import scopes


def read(ctx):
    return scopes.ms_per_step(
        ctx, lambda scope, kernel, kind: scope == "igg.stencil"
        and kernel is None and kind == "compute")
