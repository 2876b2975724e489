"""Profiling hooks.

The reference's observability is `tic()/toc()` only (SURVEY §5,
`/root/reference/src/tools.jl:228-234`); on TPU the idiomatic extra is an XLA
profiler trace viewable in TensorBoard/Perfetto (per-op device timelines,
collective overlap, HBM traffic).  Host-side spans recorded through
:mod:`igg.telemetry` are mirrored onto the same device timeline via
`jax.profiler.TraceAnnotation`, so a trace captured here lines up with the
unified event stream.

The program names its own work at three layer boundaries:

- **Device scopes.**  Every op of a program :func:`igg.sharded` compiles
  runs under the `jax.named_scope` :data:`STENCIL`; the halo engine's
  slab and plane cutting, sending and stitching under :data:`HALO`.  Each
  Mosaic kernel carries a stable `pallas_call` name.  :func:`op_scopes`
  maps each compiled instruction, as the profiler names its device op, to
  its innermost `igg.*` scope and kernel name.
- **Host spans.**  `igg.dispatch` (a ladder dispatch), `igg.admit` (its
  admission walk), `igg.sharded` (the SPMD wrapper's key, lookup and
  enqueue) and `igg.compile` (a wrapper cache miss): profiler annotations
  only, never a bus record, so they cost an inactive `TraceAnnotation`
  when no trace runs.
- **Counters.**  `igg_compile_seconds{stage}` (trace, lower, compile) from
  one `jax.monitoring` listener, booked only for events on a thread inside
  a :class:`program_frame`; `igg_dispatch_host_seconds{family}` and
  `igg_sharded_compiles_total{fn}` at the frames' call sites.
"""

from __future__ import annotations

import contextlib
import functools
import pathlib
import re
import threading
from typing import Dict, List, NamedTuple, Optional

from .shared import GridError

STENCIL = "igg.stencil"     # every op of a program igg.sharded compiles
HALO = "igg.halo"           # the halo engine's cut, send and stitch
SCOPES = (STENCIL, HALO)

# Re-entrancy guard: `jax.profiler.start_trace` raises mid-flight when a
# trace is already active, which used to surface as an opaque runtime error
# AFTER the enclosing trace was silently broken.  One trace at a time,
# stated upfront.
_lock = threading.Lock()
_active_logdir = None


@contextlib.contextmanager
def trace(logdir: str = "/tmp/igg_trace"):
    """Capture a device trace of the enclosed block:

        with igg.profiling.trace("/tmp/trace"):
            for _ in range(10):
                T = step(T, Cp)

    Open the result with TensorBoard's profile plugin or ui.perfetto.dev.
    The log directory is created (parents included) if missing; nesting a
    second `trace()` inside an active one raises :class:`igg.GridError`
    immediately instead of corrupting the in-flight capture.  Entry and
    exit are recorded on the unified event bus (`trace_started` /
    `trace_stopped`, :mod:`igg.telemetry`).
    """
    import jax

    from . import telemetry as _telemetry

    global _active_logdir
    with _lock:
        if _active_logdir is not None:
            raise GridError(
                f"igg.profiling.trace: a trace is already active "
                f"(logdir {_active_logdir!r}) — traces do not nest; close "
                f"the enclosing trace first.")
        _active_logdir = str(logdir)
    try:
        # A missing parent used to crash start_trace deep inside the
        # profiler plugin; create the whole path upfront.
        pathlib.Path(logdir).mkdir(parents=True, exist_ok=True)
        jax.profiler.start_trace(logdir)
    except BaseException:
        with _lock:
            _active_logdir = None
        raise
    _telemetry.emit("trace_started", logdir=str(logdir))
    try:
        yield logdir
    finally:
        try:
            jax.profiler.stop_trace()
        finally:
            with _lock:
                _active_logdir = None
            _telemetry.emit("trace_stopped", logdir=str(logdir))


def annotate(name: str, **metadata):
    """Named region that shows up on the profiler timeline (wraps
    `jax.profiler.TraceAnnotation`; `metadata` becomes the event's
    arguments, and `set_metadata(**kw)` on the entered annotation adds
    more).  Costs about a microsecond when no profiler runs.
    :func:`igg.telemetry.span` builds on the same annotation and ALSO
    records the region on the host-side event bus — prefer it for rare
    events when you want both."""
    import jax

    return jax.profiler.TraceAnnotation(name, **metadata)


def scoped(name: str):
    """Decorator: trace the function under `jax.named_scope(name)`, so
    every op it emits carries `name` in its compiled `op_name`."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            import jax

            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return wrapper
    return deco


# ---------------------------------------------------------------------------
# Compile stages booked to igg's own dispatches
# ---------------------------------------------------------------------------

_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_tls = threading.local()
_listening = False


def _on_span(event, start, end, **_):
    spans = getattr(_tls, "spans", None)
    if spans is not None:
        stage = _STAGES.get(event)
        if stage is not None:
            spans.append((stage, start, end))


def _listen() -> None:
    global _listening
    if not _listening:
        import jax

        with _lock:
            if not _listening:
                jax.monitoring.register_event_time_span_listener(_on_span)
                _listening = True


def _covered(intervals) -> float:
    """Seconds covered by the union of `(start, end)` intervals (JAX's
    trace events nest: an inner `jit` traced inside an outer one)."""
    total, hi = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b > hi:
            total += b - max(a, hi)
            hi = b
    return total


class program_frame:
    """Marks igg's own dispatch work on this thread (a ladder dispatch,
    an `igg.sharded` call).  JAX compile events (tracing, lowering to
    MLIR — Pallas/Mosaic lowering included — and the backend compile or
    persistent-cache fetch) that fire inside are booked, when the
    outermost frame closes, to `igg_compile_seconds{stage}` as the
    seconds each stage covered; compiles of programs outside any frame
    are not.  `compiled` tells, after exit, whether any fired inside this
    frame."""

    __slots__ = ("_start", "_outer", "compiled")

    def __enter__(self):
        spans = getattr(_tls, "spans", None)
        self._outer = spans is None
        if self._outer:
            _listen()
            spans = _tls.spans = []
        self._start = len(spans)
        self.compiled = False
        return self

    def __exit__(self, *exc):
        spans = _tls.spans
        self.compiled = len(spans) > self._start
        if self._outer:
            _tls.spans = None
            if spans:
                from . import telemetry

                for stage in ("trace", "lower", "compile"):
                    got = [(a, b) for s, a, b in spans if s == stage]
                    if got:
                        telemetry.histogram("igg_compile_seconds",
                                            stage=stage).observe(
                                                _covered(got))
        return False


# ---------------------------------------------------------------------------
# Device ops -> igg scopes
# ---------------------------------------------------------------------------

class OpScope(NamedTuple):
    """What :func:`op_scopes` knows of one compiled instruction: its
    innermost `igg.*` scope (None outside them) and, for a Mosaic custom
    call, its kernel's `pallas_call` name (None otherwise)."""
    scope: Optional[str]
    kernel: Optional[str]


class Instruction(NamedTuple):
    """One instruction of a compiled program's text: `key` is the text the
    profiler names its device op by (`%name = shape opcode(operand shapes
    and names)`), `op_name` its metadata (None where the compiler inserted
    it), `scope`/`kernel` as in :class:`OpScope`."""
    name: str
    opcode: str
    key: str
    op_name: Optional[str]
    scope: Optional[str]
    kernel: Optional[str]


_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%(\S+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%(\S+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE = re.compile(r"igg\.(?:stencil|halo)\b")
_NOT_RUN = re.compile(r"\b(?:calls|to_apply)=%([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")


def _balanced(text: str, i: int) -> int:
    """Index one past the parenthesis that closes the one at `text[i]`
    (the text's end when it never closes)."""
    depth = 0
    for j in range(i, len(text)):
        depth += (text[j] == "(") - (text[j] == ")")
        if depth == 0:
            return j + 1
    return len(text)


def _split_instruction(rest: str):
    """(shape, opcode, operand text, attributes) of an instruction's
    right-hand side."""
    if rest.startswith("("):
        end = _balanced(rest, 0)
        shape, tail = rest[:end], rest[end:].lstrip()
    else:
        shape, _, tail = rest.partition(" ")
    opcode, _, after = tail.partition("(")
    end = _balanced("(" + after, 0) - 1    # one past the closing ")"
    return shape, opcode, after[:end - 1], after[end:]


def instructions(text: str) -> List[Instruction]:
    """Every instruction of a compiled program's text (`as_text()`) that
    runs as a device op — fused computations and reduction bodies are
    left out — with its trace key, scope and kernel name.

    An instruction belongs to the innermost `igg.*` scope in its
    `op_name`.  One without an `op_name` (a copy or copy-start/done the
    compiler inserted for a loop-carried, donated or prefetched buffer)
    takes its operand's scope, and failing that :data:`STENCIL`, the scope
    the whole program was compiled under."""
    comps: Dict[str, list] = {}
    not_run = set()
    comp = None
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m and comp is not None:
            name, rest = m.groups()
            shape, opcode, operands, attrs = _split_instruction(rest)
            md = _OP_NAME.search(attrs)
            comps[comp].append((name, shape, opcode, operands,
                                md.group(1) if md else None, attrs))
            if opcode == "fusion" or "to_apply=" in attrs:
                not_run.update(_NOT_RUN.findall(attrs))
            continue
        c = _COMPUTATION.match(line)
        if c:
            comp = c.group(1)
            comps[comp] = []
    out = []
    for cname, rows in comps.items():
        if cname in not_run:
            continue
        by_name = {r[0]: r for r in rows}
        scopes: Dict[str, Optional[str]] = {}

        def scope_of(name):
            # Set before the operand walk, so the walk always ends.
            if name not in scopes:
                row = by_name.get(name)
                hits = _SCOPE.findall(row[4]) if row and row[4] else []
                scopes[name] = hits[-1] if hits else None
                if row and row[4] is None:
                    for o in _OPERAND.findall(row[3]):
                        if scope_of(o) is not None:
                            scopes[name] = scopes[o]
                            break
            return scopes[name]

        for name, shape, opcode, operands, op_name, attrs in rows:
            names = _OPERAND.findall(operands)
            shown = (", ".join(f"{by_name[o][1]} %{o}" if o in by_name
                               else f"%{o}" for o in names)
                     if names else operands)
            scope = scope_of(name)
            if scope is None and op_name is None:
                scope = STENCIL
            kernel = None
            if opcode == "custom-call" and \
                    'custom_call_target="tpu_custom_call"' in attrs:
                kernel = re.sub(r"\.\d+$", "", name)
            out.append(Instruction(name, opcode,
                                   f"%{name} = {shape} {opcode}({shown})",
                                   op_name, scope, kernel))
    return out


def op_scopes(*programs) -> Dict[str, OpScope]:
    """`{key: OpScope(scope, kernel)}` for every device op of the given
    compiled programs (`jax.stages.Compiled`), by default of the programs
    :func:`igg.sharded` has compiled in this process and still caches.
    `key` is the instruction's text as the profiler prints a device op's
    name, up to the end of its operand list (`%name = shape opcode(shape
    %operand, ...)`), so the same instruction name in another program (a
    caller's own `jit`) does not match.  Ops of other programs are absent.

    The default texts come from each cached program's executable,
    re-lowered from the argument avals kept at its cache miss; that hits
    JAX's in-memory caches and books nothing to `igg_compile_seconds`.
    Call it outside measured windows."""
    from . import parallel

    texts = ([p.as_text() for p in programs] if programs
             else parallel.compiled_texts())
    return {ins.key: OpScope(ins.scope, ins.kernel)
            for text in texts for ins in instructions(text)}
