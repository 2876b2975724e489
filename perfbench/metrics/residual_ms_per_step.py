"""Device time of the solver's convergence checks (leaf ops under the
program's `igg.residual` scope: the residual norms and their global
reduction) in the traced window, per step, averaged over the chips.
Nothing to read where the program names no such scope or no check ran."""

from perfbench import scopes


def read(ctx):
    return scopes.ms_per_step(
        ctx, lambda scope, kernel, kind: scope == "igg.residual")
