"""From a profiler trace to the intervals the per-layer readers use.

Device events are classified by op kind, not by kernel name, so a refactor
of the kernels does not break the reduction:

- "collective": the HLO collectives (collective-permute, all-reduce,
  all-gather, reduce-scatter, all-to-all, send/recv and their -start/-done
  halves): the halo exchange.
- "host": transfers between host and device (infeed, outfeed, host
  send/recv).
- "compute": every other device op: the stencil kernels, fusions, copies.

Only leaf ops count: an op that contains others on the same line (a
`while` loop around its body, a call) is a container, and its time is its
children's.  The "Async XLA Ops" line, where the profiler writes it, holds
each asynchronous collective from its start to its done: the time its
transfer was in flight.

Host spans are the benchmark's own `jax.profiler.TraceAnnotation`s, named
`perfbench.<name>` (window, dispatch, sync, frame).
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

SPAN_PREFIX = "perfbench."
COLLECTIVES = {
    "collective-permute", "collective-permute-start",
    "collective-permute-done", "all-reduce", "all-reduce-start",
    "all-reduce-done", "all-gather", "all-gather-start", "all-gather-done",
    "reduce-scatter", "all-to-all", "ragged-all-to-all",
    "collective-broadcast", "send", "send-done", "recv", "recv-done"}
HOST_TRANSFERS = {"infeed", "outfeed"}
_DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:(\d+)$")
_OPS_LINE = "XLA Ops"
_ASYNC_LINE = "Async XLA Ops"


def split(name: str):
    """(instruction, opcode) of an XLA Ops event, whose name is the HLO
    text `%name = <shape> opcode(operands), attributes`."""
    inst, eq, rest = name.partition(" = ")
    inst = inst.lstrip("%")
    if not eq:
        return inst, inst.split(".")[0]
    rest = rest.lstrip()
    if rest.startswith("("):        # a tuple shape: skip to its end
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.partition(" ")[2]
    return inst, rest.lstrip().partition("(")[0]


def kind(name: str) -> str:
    """The op kind of an XLA Ops event: "collective", "host" or
    "compute"."""
    op = split(name)[1]
    if op in HOST_TRANSFERS or (op in COLLECTIVES
                                and "is_host_transfer=true" in name):
        return "host"
    if op in COLLECTIVES:
        return "collective"
    return "compute"


def leaves(events):
    """The events of one line that contain no other event of it."""
    events = sorted(events, key=lambda e: (e[1], -e[2]))
    container = [False] * len(events)
    stack = []
    for i, (_, s, e, _) in enumerate(events):
        while stack and events[stack[-1]][2] <= s:
            stack.pop()
        if stack and events[stack[-1]][2] >= e:
            container[stack[-1]] = True
        stack.append(i)
    return [ev for ev, c in zip(events, container) if not c]


@dataclasses.dataclass
class Trace:
    """`ops[device]`: the leaf ops of the device's "XLA Ops" line, each
    (name, start_ns, end_ns, kind); `inflight[device]`: the asynchronous
    ops of its "Async XLA Ops" line, start to done, in the same form
    (empty where the profiler wrote none); `spans`: (name, start_ns,
    end_ns) of the benchmark's own host spans, prefix stripped.  All on
    the profiler's one clock."""
    ops: dict
    spans: list
    inflight: dict = dataclasses.field(default_factory=dict)

    def window(self):
        """(start, end) of the measured window's host span."""
        w = [s for s in self.spans if s[0] == "window"]
        if len(w) != 1:
            raise ValueError(f"expected one window span, found {len(w)}")
        return w[0][1], w[0][2]

    @classmethod
    def from_json(cls, d):
        """From `{"ops": {device: [[name, start, end, kind], ...]},
        "inflight": {...}, "spans": [[name, start, end], ...]}` (the form
        of the recorded mesh trace under `perfbench/testdata/`)."""
        def dev(m):
            return {int(k): [tuple(e) for e in v] for k, v in m.items()}
        return cls(dev(d["ops"]), [tuple(s) for s in d["spans"]],
                   dev(d.get("inflight", {})))


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {logdir}, "
                                f"found {len(paths)}")
    return paths[0]


def load(path: str) -> Trace:
    """Read an `.xplane.pb` (or the one under a log directory)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    pd = ProfileData.from_file(path)
    ops, inflight, spans = {}, {}, []
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (_OPS_LINE, _ASYNC_LINE):
                into = ops if line.name == _OPS_LINE else inflight
                into.setdefault(int(m.group(1)), []).extend(
                    (e.name, int(e.start_ns), int(e.end_ns), kind(e.name))
                    for e in line.events)
            elif not m:
                spans.extend(
                    (e.name[len(SPAN_PREFIX):], int(e.start_ns),
                     int(e.end_ns))
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    return Trace({d: leaves(v) for d, v in ops.items()},
                 sorted(spans, key=lambda s: s[1]),
                 {d: v for d, v in inflight.items() if v})


# --------------------------------------------------------------------------
# Interval arithmetic (half-open [start, end) in ns)
# --------------------------------------------------------------------------

def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def length(intervals):
    return sum(b - a for a, b in union(intervals))


def subtract(intervals, minus):
    """Parts of `intervals` not covered by `minus` (both any lists)."""
    out, cut = [], union(minus)
    for a, b in union(intervals):
        for c, d in cut:
            if d <= a or c >= b:
                continue
            if c > a:
                out.append((a, c))
            a = max(a, d)
            if a >= b:
                break
        if a < b:
            out.append((a, b))
    return out


def gaps(intervals, lo, hi):
    """Idle intervals of [lo, hi) not covered by `intervals`."""
    return subtract([(lo, hi)], intervals)


# --------------------------------------------------------------------------
# Per-device sums in the window
# --------------------------------------------------------------------------

def per_device(trace: Trace, kinds=None, line="ops"):
    """{device: [(start, end), ...]} of the ops (or the in-flight async
    ops) of the given kinds, clipped to the window."""
    lo, hi = trace.window()
    return {dev: clip([(s, e) for _, s, e, k in ev
                       if kinds is None or k in kinds], lo, hi)
            for dev, ev in getattr(trace, line).items()}


def breakdown(trace: Trace, top=10):
    """The device ops that took most time (seconds per device, summed over
    the window) and the longest idle gaps, each labelled by what the host
    was doing: the benchmark's span that overlaps it most, a dispatch or
    a frame before a sync (the host waits in sync through most of the
    window)."""
    lo, hi = trace.window()
    n = max(len(trace.ops), 1)
    by_name = {}
    for ev in trace.ops.values():
        for name, s, e, _ in ev:
            a, b = max(s, lo), min(e, hi)
            if b > a:
                inst, op = split(name)
                key = f"{inst} ({op})"
                by_name[key] = by_name.get(key, 0) + (b - a)
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    host = [s for s in trace.spans if s[0] != "window"]
    idle = []
    for ev in trace.ops.values():
        for a, b in gaps([(s, e) for _, s, e, _ in ev], lo, hi):
            best, label = (0, 0), "host"
            for name, s, e in host:
                ov = (name != "sync", min(b, e) - max(a, s))
                if ov[1] > 0 and ov > best:
                    best, label = ov, name
            idle.append((label, b - a))
    idle.sort(key=lambda g: -g[1])
    return {"device_ops": [[k, v / n * 1e-9] for k, v in ops],
            "idle_gaps": [[k, v * 1e-9] for k, v in idle[:top]]}
