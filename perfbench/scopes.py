"""The program's own names for the traced device ops.

`igg.profiling.op_scopes()` maps each instruction of the programs the
program compiled (`igg.sharded`) to its innermost `igg.*` scope
(`igg.stencil`, `igg.halo`) and, for a Mosaic kernel, its `pallas_call`
name.  Its keys are the instruction text as the profiler names a device op
(`%name = shape opcode(operand shapes and names)`); a trace event matches
the key it starts with, or, where the profiler cut the event's name short,
the key that starts with it.  Ops of the benchmark's own programs (the
in-flight probe, the snapshot copy) are in no program the map covers, and
belong to no scope.

A program without `op_scopes` (an igg before it) gives no map, and the
readers that need one report nothing.
"""

from __future__ import annotations

import re

from perfbench import trace as tr

_INDEX_NOTE = re.compile(r"/\*index=\d+\*/")


class Scopes:
    """`op_scopes()` indexed by instruction name for trace lookups."""

    def __init__(self, op_scopes):
        self._by_name, self._seen = {}, {}
        for key, value in op_scopes.items():
            self._by_name.setdefault(tr.split(key)[0], []).append(
                (_INDEX_NOTE.sub("", key), tuple(value)))

    def lookup(self, event_name):
        """The `(scope, kernel)` of a trace event's op, or None where no
        program the map covers has it."""
        if event_name not in self._seen:
            name = _INDEX_NOTE.sub("", event_name)
            self._seen[event_name] = next(
                (value for key, value in self._by_name.get(
                    tr.split(name)[0], ())
                 if name.startswith(key) or key.startswith(name)), None)
        return self._seen[event_name]


def load():
    """The program's scope map, or None where it has none."""
    from igg import profiling

    op_scopes = getattr(profiling, "op_scopes", None)
    got = op_scopes() if op_scopes is not None else None
    return Scopes(got) if got else None


def ms_per_step(ctx, want):
    """Device time of the leaf ops in the traced window whose
    `(scope, kernel)` and op kind satisfy `want(scope, kernel, kind)`,
    per step, averaged over the cell's chips; None where nothing
    matched or the program names no scopes."""
    scopes = load()
    if scopes is None or not ctx["steps"]:
        return None
    trace = ctx["trace"]
    lo, hi = trace.window()
    total = 0
    for ev in trace.ops.values():
        picked = []
        for name, s, e, kind in ev:
            got = scopes.lookup(name)
            if got is not None and want(got[0], got[1], kind):
                picked.append((s, e))
        total += tr.length(tr.clip(picked, lo, hi))
    if not total:
        return None
    return total / ctx["chips"] * 1e-6 / ctx["steps"]
