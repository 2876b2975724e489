"""Device time of the halo engine's own compute (leaf ops under the
program's `igg.halo` scope that are not collectives: the slab and plane
slices, transposes, stacking, the concatenation into the extended window,
the open-edge fixes) in the traced window, per step, averaged over the
chips.  Nothing to read where the program names no scopes or no halo op
ran."""

from perfbench import scopes


def read(ctx):
    return scopes.ms_per_step(
        ctx, lambda scope, kernel, kind: scope == "igg.halo"
        and kind == "compute")
