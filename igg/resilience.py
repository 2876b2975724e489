"""Resilient run loop — device-side NaN watchdog, checkpoint ring with
rollback-and-retry, and preemption handling.

The reference's headline workloads are multi-day pseudo-transient runs on
large device counts (`/root/reference/README.md:5-9`), yet it has no failure
handling: a NaN blowup, a preempted pod slice, or a truncated checkpoint
silently wastes the whole run.  Long-running TPU simulation frameworks treat
periodic checkpointing and health monitoring as first-class subsystems (the
TensorFlow-TPU CFD framework of arXiv:2108.11076 runs exactly this
gather-checkpoint-monitor cadence); :func:`run_resilient` owns that loop so
examples don't reinvent it:

- **Watchdog** — every `watch_every` steps one cheap fused device-side
  health probe runs over the watched fields: a single psum'd non-finite
  count per field, compiled once through :func:`igg.sharded` (one pass over
  each field, replicated scalar out).  The resulting per-field counts stay
  ON DEVICE and are fetched *asynchronously*: the loop polls
  `jax.Array.is_ready()` and only materializes a probe once the runtime has
  completed it, so on TPU the hot loop never host-syncs (a bounded pending
  queue — `max_pending_probes` — caps dispatch depth; detection therefore
  lags injection by at most one watch window plus the pending depth).

- **Checkpoint ring** — every `checkpoint_every` steps the state is written
  as a generation via :mod:`igg.checkpoint`, keeping the newest `ring`
  generations.  By default (`sharded=True`) a generation is a sharded
  DIRECTORY `{prefix}_<step>/`: every process writes only its own O(local)
  blocks (`shard_<rank>.npz`, per-shard CRC32s) and process 0 seals it with
  a manifest-written-last atomic commit — no process ever assembles the
  global array, and a generation restores elastically onto a different
  `dims`/device count (`igg.load_checkpoint(..., redistribute=True)`).
  `sharded=False` keeps the legacy flat `{prefix}_<step>.npz` files.
  Cadence generations are written ASYNCHRONOUSLY (`async_checkpoint=True`):
  the state's device buffers are snapshotted by reference and handed to a
  background writer thread (the :class:`igg.vis.BackgroundRenderer` shape,
  bounded queue = bounded pinned snapshots) which polls `is_ready()` before
  fetching, so the compiled hot loop never stalls on a device→host
  transfer or a filesystem write; the writer is DRAINED before any
  rollback scan, before the final preemption generation, and at end of
  run.  A failed background write degrades the ring depth by one and emits
  a 'checkpoint_failed' event instead of killing the run.  Async holds
  references to the snapshotted buffers until written — a `step_fn` that
  DONATES its input buffers would invalidate them, so donation is
  DETECTED: each dispatch is probed (pre-step buffers deleted afterwards
  ⇒ donating; donation is runtime-dependent, so probing continues until
  first observed) and the writer's worker/submit check snapshot buffers
  for deletion; either way cadence generations degrade to synchronous
  writes with a one-time structured warning instead of crashing or
  silently losing generations (at most the one generation already in
  flight when donation first strikes is lost, with a diagnosis).
  :func:`igg.latest_checkpoint` scans newest-first
  and skips corrupt/truncated/uncommitted generations, so one damaged by a
  crash or preemption mid-write degrades the rollback depth by one instead
  of killing the run.

- **Rollback and retry** — when a probe reports a non-finite count (or the
  user's `divergence_fn` fires), the loop rolls back to the newest
  generation that is older than the failing probe AND verifies healthy
  (checksum + all-finite: a generation written between the blowup and its
  detection is structurally perfect but poisoned), applies the
  `recovery_policy` callback (e.g. damp `dt` and rebuild the step), and
  replays.  The retry budget (`max_retries`) bounds the loop; exhaustion
  raises :class:`ResilienceError`.  A deterministic retry replays
  bit-exactly (`tests/test_resilience.py`).

- **Preemption** — SIGTERM (the standard pod-preemption warning) sets a
  flag checked between dispatches; the loop writes a final atomic
  generation and returns with `preempted=True`.  A relaunched job passes
  `resume=True` to continue from the newest healthy generation.

Every detection and recovery path is provable in CI through the
deterministic fault injectors of :mod:`igg.chaos` (NaN at step k, halo-plane
corruption, checkpoint truncation/bit-flip, simulated preemption) on the
8-device CPU mesh.  Overhead contract: at 128^3 with `watch_every=50` the
watchdog adds < 2% over the bare step loop
(`benchmarks/resilience_overhead.py`, asserted in CI).
"""

from __future__ import annotations

import contextlib
import dataclasses
import pathlib
import signal
import threading
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from . import shared
from . import telemetry as _telemetry
from .shared import GridError

__all__ = ["run_resilient", "RunResult", "Event", "ResilienceError",
           "request_preemption", "preemption_requested", "clear_preemption"]


class ResilienceError(GridError):
    """Unrecoverable failure of the resilient loop: retry budget exhausted,
    or no healthy checkpoint generation to roll back to.  Carries the run's
    event history up to the failure as `.events` (the same
    :class:`Event` list a successful run returns in `RunResult.events`),
    so a postmortem sees every detection, rollback, and degradation that
    led here — not just the final message.  When a telemetry sink is
    configured, the run loop's auto-dump hook fills `.dump_paths` with
    the flight-recorder dump file(s) it wrote on the way out, and the
    message NAMES them — the operator's first postmortem artifact is in
    the exception, not hunted for."""

    def __init__(self, message: str, events: Sequence["Event"] = ()):
        super().__init__(message)
        self.events: List[Event] = list(events)
        self.dump_paths: List[pathlib.Path] = []

    def __str__(self) -> str:
        base = super().__str__()
        if self.dump_paths:
            paths = ", ".join(str(p) for p in self.dump_paths)
            return f"{base}  [flight recorder dumped to: {paths}]"
        return base


# Process-wide preemption flag.  threading.Event so a SIGTERM delivered on
# the main thread is visible to a loop running anywhere, and so
# igg.chaos can simulate preemption deterministically.
_preempt = threading.Event()
# Monotone request counter next to the flag: a consumer that CLEARS the
# flag after handling its own request (the igg.heal repack path in
# igg.fleet) compares the count against the one its request produced —
# an ADDITIONAL request (an operator SIGTERM racing the heal action)
# raises the count further and must not be swallowed by the clear.
_preempt_requests = 0


class PreemptionCell:
    """A scoped preemption channel: one flag + monotone request count, for
    ONE job's run loop instead of the whole process.  The scheduler tier
    (:mod:`igg.serve`) gives each concurrent job a cell and installs it in
    the job's worker thread via :func:`preemption_scope`, so a priority
    preempt (or a fenced-device shrink) reaches exactly one job while its
    neighbors run on.  Thread-safe: the scheduler requests from its own
    thread, the run loop polls from the worker's."""

    def __init__(self) -> None:
        self._ev = threading.Event()
        self._count = 0
        self._lock = threading.Lock()

    def request(self) -> None:
        with self._lock:
            self._count += 1
        self._ev.set()

    def clear(self) -> None:
        self._ev.clear()

    def requested(self) -> bool:
        return self._ev.is_set()

    def requests(self) -> int:
        with self._lock:
            return self._count


_preempt_tls = threading.local()


def _preempt_cell() -> Optional[PreemptionCell]:
    return getattr(_preempt_tls, "cell", None)


@contextlib.contextmanager
def preemption_scope(cell: PreemptionCell):
    """Route this thread's ambient preemption verbs through `cell`:
    :func:`request_preemption` raised FROM this thread (a chaos injector,
    the heal engine's bus handler) lands on the cell, the poll verbs read
    the cell OR the process flag (a process-wide request — an operator
    SIGTERM — still reaches every scoped loop), and
    :func:`clear_preemption` clears only the cell (the owner rule: a
    scoped consumer must never swallow a process-wide shutdown)."""
    prev = _preempt_cell()
    _preempt_tls.cell = cell
    try:
        yield cell
    finally:
        _preempt_tls.cell = prev


def request_preemption(signum=None, frame=None) -> None:
    """Ask the running :func:`run_resilient` loop to checkpoint and exit at
    the next dispatch boundary.  Signature doubles as a signal handler
    (`run_resilient` installs it for SIGTERM by default).  Inside a
    :func:`preemption_scope` the request lands on the scope's cell."""
    global _preempt_requests
    cell = _preempt_cell()
    if cell is not None:
        cell.request()
        return
    _preempt_requests += 1
    _preempt.set()


def preemption_requests() -> int:
    """Monotone count of :func:`request_preemption` calls visible to this
    thread (never reset by :func:`clear_preemption`): the process-wide
    count plus — inside a :func:`preemption_scope` — the cell's own."""
    cell = _preempt_cell()
    return _preempt_requests + (cell.requests() if cell is not None else 0)


def preemption_requested() -> bool:
    cell = _preempt_cell()
    return _preempt.is_set() or (cell is not None and cell.requested())


def clear_preemption() -> None:
    cell = _preempt_cell()
    if cell is not None:
        cell.clear()
        return
    _preempt.clear()


@dataclasses.dataclass(frozen=True)
class Event:
    """One observable incident of the loop (also passed to `on_event`):
    `kind` is one of 'resume', 'checkpoint' (detail `background: True` when
    the generation was committed by the async writer), 'checkpoint_failed'
    (a background write failed — one generation of ring depth lost),
    'nan_detected', 'divergence',
    'integrity_violation' (a finite-but-wrong verdict from the
    igg.integrity layer — an invariant drifted past tolerance or a
    shadow re-execution disagreed; detail names the invariant/field,
    drift, and the attributed suspect rank/device),
    'integrity_resolved' (the violation's rollback landed on a verified
    generation — the statusd readiness reason clears),
    'rollback', 'tier_degraded' (the recovery ladder demoted the kernel
    tier that served the failing dispatch — a recurrence at the same step
    is the signature of a deterministic kernel blowup; detail: tier,
    reason), 'preempt', or a chaos injector's 'chaos_*'; `step` is the
    step count the event is anchored to (for 'nan_detected' the PROBE step
    — injection happened inside that watch window); `detail` carries
    kind-specific payload (per-field counts, paths, ...)."""
    kind: str
    step: int
    detail: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class RunResult:
    """What :func:`run_resilient` returns: the final `state`, how many
    `steps_done` (== `n_steps` unless preempted), the `retries` consumed,
    whether the run was `preempted` (checkpoint on disk; relaunch with
    `resume=True`), the `events` log, and the `checkpoint` path of the
    generation holding the returned state — the last one written, or the
    one rolled back to (None if checkpointing was off)."""
    state: Dict
    steps_done: int
    retries: int
    preempted: bool
    events: List[Event]
    checkpoint: Optional[pathlib.Path]


def _make_probe():
    """Compiled device-side health probe over grid fields: ONE
    fused pass per field computing its non-finite count, psum'd over every
    mesh axis so the stacked `(n_fields,)` result is device-invariant and
    replicated (no gather, no per-device output).  Counts are f32 — only
    zero/nonzero is decided on, and f32 psum avoids the x64-dependent int
    width."""
    from jax.sharding import PartitionSpec

    from .parallel import psum_grid, sharded

    @sharded(out_specs=PartitionSpec())
    def probe(*arrays):
        import jax.numpy as jnp

        counts = []
        for a in arrays:
            if jnp.issubdtype(a.dtype, jnp.inexact):
                c = jnp.sum((~jnp.isfinite(a)).astype(jnp.float32))
            else:
                c = jnp.zeros((), jnp.float32)
            counts.append(psum_grid(c))
        return jnp.stack(counts)

    return probe


# Fault-injection seam (igg.chaos.collective_stall): a predicate applied
# to every `is_ready` poll — the single readiness primitive the watchdog's
# async probe fetches, the comm decomposition probes, and the stall
# heartbeat all consult — so a hung collective (a probe that never
# becomes ready) is injectable deterministically.  Host-level (consulted
# at poll time, never traced), so arming needs no cache clearing.
_CHAOS_FETCH_TAP = None

# Fault-injection seam (igg.chaos.silent_corruption): a state transform
# applied at every dispatch boundary of the run loops — the host-level
# stand-in for silent data corruption (an HBM bit-flip, a flaky chip's
# finite-but-wrong answer) landing in live state between dispatches.
# Host-level and one-shot inside the injector, so arming needs no cache
# clearing and a rolled-back replay passes the same step clean.
_CHAOS_STATE_TAP = None


def _is_ready(x) -> bool:
    tap = _CHAOS_FETCH_TAP
    if tap is not None and not tap(x):
        return False
    try:
        return x.is_ready()
    except AttributeError:   # non-jax value: nothing pending
        return True


def _buffer_ready(x) -> bool:
    """Raw buffer readiness, NOT routed through the chaos probe-fetch
    seam: the async checkpoint writer polls plain per-device snapshot
    buffers, not a collective-readiness channel — an injected
    collective stall must stall the watchdog's verdict stream (and fire
    the heartbeat), never deadlock a background generation write whose
    data is actually there."""
    try:
        return x.is_ready()
    except AttributeError:
        return True


def _is_deleted(x) -> bool:
    """Whether a snapshot buffer has been invalidated (donated to a later
    dispatch) — the async-checkpoint hazard the writer detects."""
    try:
        return bool(x.is_deleted())
    except AttributeError:   # non-jax value: cannot be donated
        return False


class _AsyncCheckpointWriter:
    """Background checkpoint writer — the :class:`igg.vis.BackgroundRenderer`
    shape applied to the resilience ring's cadence generations.

    `submit(step, fields, last_good)` snapshots the state dict BY REFERENCE
    (no device→host transfer on the caller's thread) and enqueues it; the
    worker thread first polls `is_ready()` on every buffer (the watchdog's
    asynchronous-fetch pattern — fetching early would host-sync the device
    stream the hot loop is still feeding), then runs the save function.
    The bounded queue (`maxsize`) is the pinned-snapshot bound: at most
    `maxsize` generations' device buffers are kept alive awaiting write,
    and a submit beyond it backpressures instead of accumulating memory.

    Completions and failures are handed back on the CALLER's thread:
    :meth:`poll` (non-blocking, per loop iteration) and :meth:`drain`
    (blocking — the synchronization point before any rollback scan, the
    final preemption generation, and end of run) both return
    `([(step, path, background)], [(step, error)])` — failures carry the
    step of the generation that failed to write (not whatever step the
    caller happens to be at when it polls), so the 'checkpoint_failed'
    event names the actual lost ring slot.  A failed write surfaces as an
    error — one generation of ring depth lost — never as an exception on
    the hot loop.  The save function must not involve device collectives
    (:func:`igg.save_checkpoint_sharded` is filesystem-coordinated, so it
    qualifies).

    DONATION GUARD (the documented async hazard, closed in round 11):
    snapshots are held by reference, so a step that donates its input
    buffers invalidates them before the worker can fetch.  The worker
    detects a deleted snapshot buffer (`is_deleted`) and fails that
    generation with a donation diagnosis; from then on — or immediately,
    when the caller pre-announces via :meth:`note_donation`, or when a
    submit arrives with already-deleted buffers — `submit` degrades to a
    SYNCHRONOUS write on the caller's thread (where the buffers are
    alive), with a one-time structured warning: generations stop being
    lost instead of failing one by one.  Completions carry
    `background=False` for these sync-degraded writes."""

    def __init__(self, save_fn, *, maxsize: int = 2):
        from .vis import BackgroundRenderer

        self._save_fn = save_fn
        self._done: deque = deque()    # (step, path), appended by the worker
        self._failed: deque = deque()  # (step, exception), ditto
        self._donation_seen = False    # a snapshot buffer was invalidated
        self._warned_donation = False
        self._r = BackgroundRenderer(self._consume, maxsize=maxsize,
                                     name="igg-ckpt-writer")

    def _consume(self, batch) -> None:
        import time

        step, fields, last_good = batch
        try:
            while True:
                if any(_is_deleted(a) for a in fields.values()):
                    # The documented donation hazard struck: a later
                    # dispatch donated (invalidated) the snapshot's
                    # buffers before this write could fetch them.  Flag it
                    # so `submit` degrades every subsequent generation to
                    # a synchronous write instead of losing them one by
                    # one — and fail THIS generation with a diagnosis
                    # instead of a raw runtime error (or silent garbage).
                    self._donation_seen = True
                    raise RuntimeError(
                        "snapshot buffers were deleted (donated to a "
                        "later dispatch) before the background write "
                        "fetched them — step_fn donates its inputs; "
                        "subsequent generations degrade to synchronous "
                        "writes")
                if all(_buffer_ready(a) for a in fields.values()):
                    break
                time.sleep(0.002)
            path = self._save_fn(step, fields, last_good)
        except BaseException as e:
            self._failed.append((step, e))
            return
        self._done.append((step, path, True))

    def _warn_donation(self) -> None:
        if self._warned_donation:
            return
        self._warned_donation = True
        import warnings

        warnings.warn(
            "igg.run_resilient: step_fn DONATES its input buffers, so "
            "asynchronous checkpoint snapshots (held by reference) are "
            "invalidated before the background writer can fetch them; "
            "cadence generations now degrade to synchronous writes for "
            "the rest of the run (use donate=False steps to keep async "
            "writes).  (Warned once per run.)", stacklevel=3)

    def note_donation(self) -> None:
        """Tell the writer the caller's step donates its buffers (detected
        before any generation was submitted): every submit degrades to a
        synchronous write, zero generations lost."""
        self._donation_seen = True

    def submit(self, step: int, fields: Dict, last_good: int) -> None:
        snap = dict(fields)
        deleted_now = any(_is_deleted(a) for a in snap.values())
        if self._donation_seen or deleted_now:
            # Donation detected — at submit time (the buffers handed in
            # are already invalid: nothing can be written) or by the
            # worker on an earlier generation.  Degrade to a synchronous
            # write on the caller's thread, where the buffers are alive.
            self._donation_seen = True
            self._warn_donation()
            if deleted_now:
                self._failed.append((step, RuntimeError(
                    "state buffers were already deleted (donated) at "
                    "submit time — nothing valid to checkpoint")))
                return
            try:
                path = self._save_fn(step, snap, last_good)
            except BaseException as e:
                self._failed.append((step, e))
                return
            self._done.append((step, path, False))   # sync-degraded write
            return
        self._r.submit((step, snap, last_good))

    def _results(self):
        done, errs = [], []
        while self._done:
            done.append(self._done.popleft())
        while self._failed:
            errs.append(self._failed.popleft())
        return done, errs

    def poll(self):
        """Completions/failures so far; never blocks."""
        return self._results()

    def drain(self):
        """Block until every submitted generation is written (or failed),
        then return the completions/failures."""
        self._r.drain()
        return self._results()

    def close(self) -> None:
        self._r.close()


def run_resilient(step_fn: Callable[[Dict], Dict], state: Dict, n_steps: int,
                  *,
                  watch_every: int = 50,
                  watch_fields: Optional[Sequence[str]] = None,
                  divergence_fn: Optional[Callable[[Dict], bool]] = None,
                  checkpoint_dir=None,
                  checkpoint_every: int = 0,
                  ring: int = 3,
                  prefix: str = "ckpt",
                  max_retries: int = 3,
                  recovery_policy: Optional[Callable] = None,
                  resume: bool = False,
                  steps_per_call: int = 1,
                  max_pending_probes: int = 4,
                  sharded: bool = True,
                  async_checkpoint: bool = True,
                  install_sigterm: bool = True,
                  on_event: Optional[Callable[[Event], None]] = None,
                  telemetry=None,
                  serve=None,
                  comm=None,
                  heal=None,
                  integrity=None,
                  chaos=None) -> RunResult:
    """Drive `state = step_fn(state)` for `n_steps` steps with a device-side
    NaN/Inf watchdog, a rolling checkpoint ring, rollback-and-retry, and
    preemption handling (module docstring for the full contract).

    - `state`: dict of named block-stacked grid arrays (the
      :func:`igg.save_checkpoint` field model); `step_fn` maps it to the
      next state dict (same keys).  When `step_fn` advances more than one
      step per call (the TPU idiom: `n_inner` steps per compiled dispatch,
      cf. `igg.models.diffusion3d.make_multi_step`), say so with
      `steps_per_call` — all cadences count STEPS and must be multiples
      of it.
    - `watch_every`: probe cadence in steps (0 disables the watchdog).
      `watch_fields` names the fields to probe (default: every
      floating/complex field).  `divergence_fn(state) -> bool` is an
      optional user predicate evaluated host-side at the same cadence
      (it may sync; keep it cheap or run it on device and let the bool
      fetch sync).
    - `checkpoint_every` > 0 enables the ring under `checkpoint_dir` (a
      generation is also written at entry so a rollback target always
      exists, and on preemption).  `ring` generations are kept.
      `sharded=True` (default) writes generation DIRECTORIES
      `{prefix}_<step>/` in the O(local) per-shard format of
      :func:`igg.save_checkpoint_sharded`; `sharded=False` writes legacy
      flat `{prefix}_<step>.npz` files.  `async_checkpoint=True` (default;
      sharded only) hands cadence generations to a background writer
      thread so the hot loop never stalls on the write — entry, rollback,
      and preemption generations stay synchronous, and the writer is
      drained before every rollback scan and before the final preemption
      generation (module docstring for the full contract, including the
      no-donation caveat).
    - On detection, the loop rolls back to the newest generation older
      than the failing probe that passes
      `igg.verify_checkpoint(check_finite=True)`, then calls
      `recovery_policy(attempt, state, event)` which may return a new
      state dict, a `(state, step_fn)` pair (e.g. a rebuilt step with a
      damped `dt`), or None to retry unchanged.  `max_retries` bounds the
      total rollbacks; exhaustion raises :class:`ResilienceError`, as does
      a detection with no healthy generation (or no ring configured).
    - `resume=True` first scans `checkpoint_dir` for the newest healthy
      generation and continues from its step.
    - `telemetry`: unified observability (:mod:`igg.telemetry`) — None
      (default: on only when `IGG_TELEMETRY_DIR` is set), a directory
      path, a :class:`igg.telemetry.Telemetry` session, or False (off).
      Every run event additionally flows onto the process event bus
      regardless (flight recorder + any attached session);
      `RunResult.events` stays the per-run filtered view.  With a session
      attached the run also emits per-window `step_stats` records
      piggybacked on the watchdog's async fetches (zero extra host
      syncs), exports metrics snapshots, and auto-dumps the flight
      recorder on `ResilienceError`/preemption/unhandled escapes.
    - `serve`: the live ops endpoint (:mod:`igg.statusd`) — None
      (default: on only when ``IGG_STATUSD_PORT`` is set non-zero), an
      int TCP port (0 = ephemeral), True (env port, else ephemeral), a
      shared :class:`igg.statusd.StatusServer`, or False (off).  The
      endpoint serves `/metrics`, `/healthz`, `/status`, and `/events`
      from its own threads for the run's duration (an already-started
      shared server is left running); readiness flips false on an
      active collective-stall episode, all-members-quarantined, a heal
      escalation, or excessive watchdog fetch lag
      (docs/observability.md, "Live endpoint").
    - `comm`: an :class:`igg.comm.StepDecomposition` monitor — per-window
      step-time decomposition probes (compute-only / compute+exchange /
      hidden-overlap) dispatched at the watch cadence and observed through
      the same non-blocking `is_ready` channel the watchdog uses (zero
      additional host syncs; requires `watch_every` > 0; single-controller
      only — warned off on multi-process runs).  Independently of `comm`,
      every async probe fetch is registered with a collective-stall
      heartbeat (`igg.comm.StallWatchdog`, `IGG_COMM_STALL_TIMEOUT`
      seconds, default 120, 0 disables): a probe that never becomes ready
      emits a `collective_stall` event, a `stall_r<rank>.json` report,
      and a flight-recorder dump instead of hanging silently
      (docs/observability.md, "Stall detection").
    - `heal`: the self-healing control plane (:mod:`igg.heal`) — None
      (default: on only when ``IGG_HEAL=1``), True (env-policy engine),
      an :class:`igg.heal.HealPolicy`, an :class:`igg.heal.HealEngine`,
      or False (off).  The engine subscribes to the event bus for the
      run and closes the detection→action loops at dispatch boundaries:
      a ``collective_stall`` verdict or sustained watchdog-window
      inflation seals a final generation and elastically RE-TILES the
      run onto the surviving devices at newly planned ``dims`` (the
      live grid is re-initialized — single-controller only, warned off
      on multi-process runs; requires the checkpoint ring); a
      ``cost_model_drift`` event invalidates the affected
      :mod:`igg.perf` entries and re-calibrates.  Budget, cool-down,
      and escalation (action → demote →
      :class:`igg.heal.HealEscalation`) per the policy; every decision
      is a typed ``heal_*`` bus record.  With no fault present the
      engine costs the hot loop one deque check per iteration — zero
      host syncs (the PR-7 sentinel runs with it enabled).
    - `integrity`: the numeric-integrity layer (:mod:`igg.integrity`) —
      None (default: on only when ``IGG_INTEGRITY=1``), True
      (env-config), an :class:`igg.integrity.IntegrityConfig`, or False
      (off).  Family-declared invariant probes and shadow re-execution
      spot checks are FUSED into the watchdog probe (one concatenated
      vector, the same single async fetch per watch window — zero
      additional host syncs; requires `watch_every` > 0), finite-but-
      wrong state raises ``integrity_violation`` with per-rank device
      attribution, checkpoint generations are stamped with the
      invariants' references, and the rollback/resume scans PREFER the
      newest DEEP-verified generation
      (``igg.verify_checkpoint(deep=True)``) — closing the
      finite-but-poisoned window `check_finite` cannot.  An
      ``integrity_violation`` recurring at the same step after a clean
      rollback demotes the serving tier (the deterministic-miscompile
      rung), and with `heal=` attached the violation additionally plans
      a fence-the-suspect-device elastic re-tile
      (docs/resilience.md, "Silent data corruption").
    - `chaos`: an :class:`igg.chaos.ChaosPlan` for deterministic fault
      injection (CI/testing only).

    Returns a :class:`RunResult`.  Multi-controller runs: every process
    executes the same loop (probes are replicated, checkpoints collective);
    the preemption signal must reach every process, the standard behavior
    of pod schedulers (docs/multihost.md).
    """
    import jax

    from . import checkpoint as ckpt

    shared.check_initialized()
    if not isinstance(state, dict) or not state:
        raise GridError("run_resilient: state must be a non-empty dict of "
                        "named grid fields (the save_checkpoint model).")
    if steps_per_call < 1:
        raise GridError("run_resilient: steps_per_call must be >= 1.")
    for name, value in (("n_steps", n_steps), ("watch_every", watch_every),
                        ("checkpoint_every", checkpoint_every)):
        if value and value % steps_per_call != 0:
            raise GridError(
                f"run_resilient: {name}={value} is not a multiple of "
                f"steps_per_call={steps_per_call}; cadences count steps and "
                f"must align with dispatch boundaries.")
    if checkpoint_every and checkpoint_dir is None:
        raise GridError("run_resilient: checkpoint_every > 0 requires "
                        "checkpoint_dir.")
    if divergence_fn is not None and not watch_every:
        raise GridError("run_resilient: divergence_fn is evaluated at the "
                        "watch cadence; set watch_every > 0.")
    if resume and checkpoint_dir is None:
        raise GridError("run_resilient: resume=True requires "
                        "checkpoint_dir (silently restarting from step 0 "
                        "would recompute the whole run).")
    if ring < 1:
        raise GridError("run_resilient: ring must be >= 1.")

    import jax.numpy as jnp

    state = dict(state)
    cdir = pathlib.Path(checkpoint_dir) if checkpoint_dir is not None else None
    # jnp.issubdtype, not np: extension float dtypes (bfloat16, float8_*)
    # are numpy kind 'V' and would silently fall out of the default watch
    # set under np.issubdtype.
    watch = list(watch_fields) if watch_fields is not None else [
        n for n, a in state.items()
        if jnp.issubdtype(getattr(a, "dtype", np.float64), jnp.inexact)]
    missing = [n for n in watch if n not in state]
    if missing:
        raise GridError(f"run_resilient: watch_fields {missing} not in "
                        f"state {sorted(state)}.")

    events: List[Event] = []

    def _emit(kind, step, _bus=True, **detail) -> Event:
        ev = Event(kind, step, detail)
        events.append(ev)
        # The unified bus (igg.telemetry): same record, timestamped and
        # rank-tagged — `events` stays the per-run filtered view.
        # `_bus=False` keeps an event in the per-run view only, for kinds
        # whose authoritative bus record another subsystem just emitted.
        if _bus:
            _telemetry.emit(kind, step=step, run="resilient", **detail)
        if on_event is not None:
            on_event(ev)
        return ev

    # Multi-controller: generation verification reads every shard once
    # GLOBALLY (round-robin + AND-combined verdicts) instead of once per
    # process; all processes reach these calls at the same iteration (see
    # `deterministic_only` below), so the collective is safe.
    dist_verify = jax.process_count() > 1

    # Unified telemetry (igg.telemetry): attach the session BEFORE the
    # resume scan so the run's earliest events reach the JSONL sink too.
    tel = _telemetry.as_session(telemetry)
    tel_owns = tel is not None and not tel.attached
    if tel_owns:
        tel.attach()
    _telemetry.emit("run_started", run="resilient", n_steps=n_steps,
                    watch_every=watch_every, steps_per_call=steps_per_call)
    # Perf-ledger context (igg.perf): window rates are attributed to the
    # serving kernel tier — ladder bookkeeping on the watchdog's existing
    # fetch timestamps, zero additional host syncs.
    from . import perf as _perf

    stats = _telemetry.StepStats(
        "resilient",
        perf=(_perf.sample_context(state[watch[0]])
              if watch and _perf.enabled() else None))
    m_steps = _telemetry.counter("igg_steps_total", run="resilient")
    m_rollbacks = _telemetry.counter("igg_rollbacks_total", run="resilient")

    # Communication observability (igg.comm): the collective-stall
    # heartbeat watches every async probe fetch (a hung collective then
    # becomes a structured artifact instead of a silent hang), and an
    # optional StepDecomposition monitor rides the watch cadence.
    from . import comm as _comm

    stall = (_comm.make_stall_watchdog("resilient")
             if (watch and watch_every) else None)
    # Self-healing control plane (igg.heal): the engine subscribes to
    # the bus for the run's duration; actions are executed at dispatch
    # boundaries below.  Single-controller only — a mid-run grid
    # re-initialization cannot be coordinated through a desynchronized
    # multi-process collective stream (the comm-monitor precedent).
    from . import heal as _heal

    heal_eng = _heal.as_engine(heal, run="resilient")
    if heal_eng is not None and jax.process_count() > 1:
        import warnings

        warnings.warn(
            "igg.run_resilient: heal= is single-controller only (an "
            "elastic re-tile re-initializes the live grid, which cannot "
            "be coordinated mid-run across controller processes); "
            "disabled for this run.", stacklevel=2)
        heal_eng = None
    # Numeric-integrity layer (igg.integrity): invariant probes + shadow
    # re-execution checks fused into the watchdog probe, deep-verified
    # rollback.  Config coercion + validation here (before the statusd
    # server binds); the Monitor itself is built in the pre-loop try
    # below, after a resume has settled the state it validates against.
    from . import integrity as _integrity

    int_cfg = _integrity.as_config(integrity)
    if int_cfg is not None and not (watch and watch_every):
        raise GridError(
            "run_resilient: the integrity= probes ride the watch cadence; "
            "set watch_every > 0 (with watched fields).")
    deep_pref = int_cfg is not None and int_cfg.resolved_deep()
    mon: Optional[_integrity.Monitor] = None
    comm_mon = None
    if comm is not None:
        if not (hasattr(comm, "maybe_dispatch") and hasattr(comm, "poll")):
            raise GridError(
                f"run_resilient: comm={comm!r}: expected an "
                f"igg.comm.StepDecomposition monitor (or None).")
        if not (watch and watch_every):
            raise GridError(
                "run_resilient: the comm= decomposition probes ride the "
                "watch cadence; set watch_every > 0 (with watched "
                "fields).")
        if jax.process_count() > 1:
            import warnings

            warnings.warn(
                "igg.run_resilient: comm= step-decomposition probes are "
                "single-controller only (their dispatch cadence depends "
                "on local readiness timing, which would desynchronize "
                "multi-process collective streams); disabled for this "
                "run.", stacklevel=2)
        else:
            comm_mon = comm

    # Live ops endpoint (igg.statusd), started AFTER the heal=/comm=
    # argument validations above: a GridError there must not leak a
    # bound HTTP server (nor may a bind failure — a real runtime
    # condition when the port is taken — leak the attached session).
    # The endpoint still covers the whole run: the health tracker
    # backfills run_started from the flight ring on attach, and the
    # pre-loop except + the main finally both stop an owned server.
    from . import statusd as _statusd

    try:
        srv = _statusd.as_server(serve)
        srv_owns = srv is not None and not srv.started
        if srv_owns:
            srv.start()
    except BaseException:
        if tel_owns:
            tel.detach()
        raise

    # Subscribe AFTER the argument validations above: a GridError there
    # must not leak the engine into the process-global subscriber list
    # (the pre-loop except and the main finally both detach).
    if heal_eng is not None:
        heal_eng.attach()

    steps_done = 0
    resumed_step = None
    try:
        if resume and cdir is not None:
            found = None
            if deep_pref:
                # Verified resume: prefer the newest DEEP-verified
                # generation (recomputed integrity stamps + invariant
                # references); unstamped/poisoned generations fall through
                # to the plain finite scan below.
                found = ckpt.latest_checkpoint(
                    cdir, prefix, check_finite=True,
                    distributed=dist_verify, deep=True)
            if found is None:
                found = ckpt.latest_checkpoint(
                    cdir, prefix, check_finite=True,
                    distributed=dist_verify)
            if found is not None:
                # redistribute=True makes the resume ELASTIC: a generation
                # written under a different dims/device count is re-tiled
                # onto the live decomposition (on a matching geometry it is
                # the plain 1:1 restore — redistribute only engages on
                # mismatch).
                state = ckpt.load_checkpoint(found, redistribute=True)
                steps_done = resumed_step = ckpt.checkpoint_step(found) or 0
                if steps_done % steps_per_call != 0:
                    raise GridError(
                        f"run_resilient(resume=True): generation "
                        f"{found.name} "
                        f"is at step {steps_done}, not a multiple of "
                        f"steps_per_call={steps_per_call} — the resumed "
                        f"walk "
                        f"would miss every watch/checkpoint boundary and "
                        f"overshoot n_steps.  Resume with the "
                        f"steps_per_call "
                        f"the checkpoint was written under.")
                _emit("resume", steps_done, path=str(found))
        probe = _make_probe() if (watch and watch_every) else None
        if int_cfg is not None:
            # Built AFTER the resume scan: the monitor validates its
            # invariants against (and snapshots) the state actually run.
            mon = _integrity.Monitor(int_cfg, state, watch, watch_every,
                                     steps_per_call)
    except BaseException as e:
        # A pre-loop failure must not leak the run-owned session into the
        # process-global sink list (nor the heal engine's subscription,
        # nor the integrity monitor's checkpoint-stamp context).
        paths = _telemetry._auto_dump(f"run_resilient: "
                                      f"{type(e).__name__}: {e}")
        if isinstance(e, ResilienceError):
            e.dump_paths.extend(p for p in paths if p not in e.dump_paths)
        if mon is not None:
            mon.close()
        if heal_eng is not None:
            heal_eng.detach()
        if srv_owns:
            srv.stop()
        if tel_owns:
            tel.detach()
        raise
    pending: deque = deque()   # (probe_step, device-resident (nf,) counts)
    retries = 0
    last_fail = None           # (kind, step) of the previous rollback cause
    # Demotion scope: only ladder families that dispatch AFTER this stamp
    # belong to this run — a healthy tier some unrelated earlier factory
    # warmed must never be quarantined by this run's recovery.
    from . import degrade as _degrade
    run_stamp = _degrade.dispatch_stamp()
    preempted = False
    last_ckpt: Optional[pathlib.Path] = None
    last_ckpt_step = -1
    use_async = bool(async_checkpoint and sharded and checkpoint_every)
    writer: Optional[_AsyncCheckpointWriter] = None   # created on first use
    # Steps whose on-disk generation is known to hold THIS run's state (a
    # leftover file from a previous run in the same directory does not
    # qualify); invalidated on rollback, where the replay may diverge from
    # the first attempt (recovery_policy may have changed the step).
    synced = set()
    if resumed_step is not None:
        synced.add(resumed_step)
    # Newest step whose health is established: probe-confirmed, loaded from
    # a finite-verified generation, or the caller's initial state.  The
    # generation at (or newest below) this step is exempt from ring pruning:
    # with checkpoint_every << watch_every, several unconfirmed — possibly
    # poisoned — generations can land before the first probe is fetched,
    # and plain newest-R pruning would rotate the only healthy rollback
    # target out of the ring.
    last_good = steps_done

    def _generations():
        """This ring's generation files, `[(step, path)]` sorted by step
        (the strict match shared with `latest_checkpoint` — a sibling ring
        under a longer prefix is never pruned or rolled back into)."""
        return ckpt.list_generations(cdir, prefix) if cdir is not None else []

    def _gen_path(step) -> pathlib.Path:
        """`{prefix}_<step>/` sharded generation directory, or the legacy
        flat `{prefix}_<step>.npz` under `sharded=False`."""
        return cdir / (f"{prefix}_{step:09d}" if sharded
                       else f"{prefix}_{step:09d}.npz")

    def _prune(good_until: int) -> None:
        """Keep the newest `ring` generations plus the newest
        health-established one (`good_until` — see `last_good`)."""
        if jax.process_index() != 0:
            return
        ckpt.prune_generations(cdir, prefix, ring, good_until)

    def _write_gen(step, fields, good_until) -> pathlib.Path:
        """Write one generation and prune the ring — runs on the caller's
        thread for sync generations and on the writer thread for async
        ones (the sharded save is filesystem-coordinated: no device
        collectives, so it is thread-safe)."""
        p = _gen_path(step)
        with _telemetry.span("checkpoint.generation", step=step,
                             path=str(p)):
            if sharded:
                ckpt.save_checkpoint_sharded(p, **fields)
            else:
                ckpt.save_checkpoint(p, **fields)
        _prune(good_until)
        return p

    def _record_gen(step, p, background=False) -> None:
        nonlocal last_ckpt, last_ckpt_step
        synced.add(step)
        if step >= last_ckpt_step:
            last_ckpt, last_ckpt_step = p, step
        detail = {"path": str(p)}
        if background:
            detail["background"] = True
        _emit("checkpoint", step, **detail)

    def _merge_writer(drain: bool = False) -> None:
        """Collect background-write completions/failures onto the main
        thread (bookkeeping + events).  `drain=True` blocks until the
        writer queue is empty — the synchronization point before every
        rollback scan, the final preemption generation, and end of run."""
        if writer is None:
            return
        if drain:
            with _telemetry.span("checkpoint.drain", step=steps_done):
                done, errs = writer.drain()
        else:
            done, errs = writer.poll()
        for step_w, p, background in done:
            _record_gen(step_w, p, background=background)
        for step_w, e in errs:
            # One ring generation lost; the run continues.
            _emit("checkpoint_failed", step_w,
                  error=f"{type(e).__name__}: {e}")

    # Set when the first dispatch proves step_fn donates its input buffers
    # (the pre-step state is deleted afterwards): async snapshots would be
    # invalidated before the writer fetches them, so cadence generations
    # degrade to synchronous writes — detected BEFORE the first async
    # submit, zero generations lost (the writer's own submit-time guard
    # covers direct users of _AsyncCheckpointWriter).
    donating = False

    def _save_gen(step, sync: bool = True) -> None:
        nonlocal writer
        if not sync and use_async and not donating:
            if writer is None:
                writer = _AsyncCheckpointWriter(_write_gen)
            writer.submit(step, state, last_good)
            return
        _record_gen(step, _write_gen(step, state, last_good))

    # Multi-controller: every process must take the rollback branch at the
    # SAME iteration or their subsequent collective streams diverge.  The
    # opportunistic is_ready() fetch is per-process timing — skip it there
    # and fetch only at the deterministic points (pending depth exceeding
    # max_pending_probes, and the drain at end of run), both pure
    # functions of the step count.  Probe VALUES are full-mesh psums, so
    # once fetched all processes agree on the verdict.
    deterministic_only = jax.process_count() > 1

    def _poll_probes(drain: bool = False) -> Optional[Event]:
        """Fetch completed probes oldest-first (forced once the pending
        depth exceeds `max_pending_probes`, or on `drain`); returns the
        failure event of the first non-finite probe, else None."""
        nonlocal last_good
        while pending:
            step_p, counts, tag = pending[0]
            if (not drain and len(pending) <= max_pending_probes
                    and (deterministic_only or not _is_ready(counts))):
                return None
            pending.popleft()
            host = np.asarray(counts)
            if stall is not None:
                stall.fetched(("probe", step_p), step_p)
            viol = None
            if mon is not None:
                nf, viol = mon.decode(host, tag, step_p)
                bad = {n: int(c) for n, c in zip(watch, nf) if c != 0}
            else:
                bad = {n: int(c) for n, c in zip(watch, host) if c != 0}
            if bad:
                # Younger pending probes are post-failure noise.
                pending.clear()
                if stall is not None:
                    stall.clear()
                return _emit("nan_detected", step_p, counts=bad)
            if viol is not None:
                # Finite-but-wrong state (an invariant drifted past its
                # tolerance, or a shadow re-execution disagreed): the
                # silent-data-corruption verdict — per-rank partials
                # attribute the suspect device, the rollback below
                # prefers a DEEP-verified generation, and an attached
                # heal engine plans fence + elastic re-tile off this
                # bus record.
                pending.clear()
                if stall is not None:
                    stall.clear()
                return _emit("integrity_violation", step_p, **viol)
            last_good = max(last_good, step_p)
            # Step stats piggyback on THIS fetch (igg.telemetry): the
            # probe was already materialized for the verdict, so the rate
            # telemetry costs a host timestamp — zero additional syncs.
            stats.fetched(step_p, steps_done)
        return None

    def _dispatch_probe() -> None:
        """One watchdog probe dispatch, registered with the stall
        heartbeat (the in-flight record a hung collective is reported
        against).  With integrity enabled the monitor's FUSED probe
        serves instead — non-finite counts, invariant partials, and (on
        the check cadence) the shadow re-execution diffs in ONE vector,
        so the loop still fetches exactly one array per window."""
        if mon is not None:
            counts, tag = mon.dispatch(state, steps_done, step_fn)
        else:
            counts, tag = probe(*[state[n] for n in watch]), None
        pending.append((steps_done, counts, tag))
        if stall is not None:
            stall.watch(("probe", steps_done), steps_done,
                        "watchdog probe (psum over mesh axes)", counts)

    def _rollback(ev: Event) -> None:
        nonlocal state, steps_done, retries, step_fn, final_probe_done, \
            last_good, last_ckpt, last_ckpt_step, last_fail
        from . import degrade

        final_probe_done = False   # the replay's tail window re-probes
        # Tier-demotion rung (igg.degrade): the SAME failure recurring at
        # the SAME step after a bit-exact rollback is the signature of a
        # deterministic kernel blowup (a miscompiled fast tier), not a
        # transient — damping dt or replaying cannot fix it.  Quarantine
        # the tier(s) that served the failing dispatch so the replay runs
        # the next rung, and do NOT burn a retry on it: the demotion IS
        # the recovery action (each tier demotes at most once, so this
        # cannot loop).  First occurrences and recurrences with no fast
        # tier left fall through to the plain retry budget.
        demoted: List[str] = []
        if last_fail == (ev.kind, ev.step):
            demoted = degrade.demote_active(
                reason="nan_recurrence",
                error_text=f"{ev.kind} recurred at step {ev.step} after a "
                           f"bit-exact rollback",
                since=run_stamp)
            for tname in demoted:
                # degrade.quarantine (inside demote_active) just emitted
                # the authoritative tier_degraded bus record — this one is
                # the per-run view's step-anchored copy only.
                _emit("tier_degraded", ev.step, _bus=False, tier=tname,
                      reason="nan_recurrence")
            if demoted and mon is not None:
                # The demoted tier's physics was wrong, so integrity
                # references anchored on its trajectory would flag the
                # now-correct replay forever — re-anchor on the healthy
                # rung's values.
                mon.reset_reference()
        last_fail = (ev.kind, ev.step)
        if not demoted:
            retries += 1
        if retries > max_retries:
            raise ResilienceError(
                f"run_resilient: {ev.kind} at step {ev.step} "
                f"({ev.detail or ''}) and the retry budget "
                f"(max_retries={max_retries}) is exhausted.", events)
        if cdir is None:
            raise ResilienceError(
                f"run_resilient: {ev.kind} at step {ev.step} but no "
                f"checkpoint_dir is configured — nothing to roll back to.  "
                f"Enable the ring (checkpoint_every/checkpoint_dir) for "
                f"rollback-and-retry.", events)
        # The generation scan must see every in-flight background write
        # settled (committed or failed) — a half-staged directory is not a
        # rollback candidate, and the newest healthy generation may still
        # be in the writer queue.  Multi-controller: barrier after the
        # drain, so no process scans while another's writer is still
        # committing or pruning (every process reaches this rollback at
        # the same iteration — see `deterministic_only`).
        _merge_writer(drain=True)
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices("igg_rollback_scan")
        # A generation written between the blowup and its detection is
        # structurally valid but poisoned; check_finite rejects it.  The
        # scan is the agreed-step probe protocol of `latest_checkpoint`:
        # on a multi-controller run every process executes the same
        # collectives in the same order even if their directory listings
        # transiently diverge (NFS attribute caches).
        found = None
        if mon is not None and mon.deep_verify:
            # VERIFIED-generation rollback: a finite-but-POISONED
            # generation (saved from silently-corrupted state) passes
            # check_finite but fails the deep stamp's invariant
            # references — prefer the newest generation that deep-
            # verifies, falling back to the plain scan only when none is
            # stamped (mixed pre-/post-round-19 rings stay recoverable).
            found = ckpt.latest_checkpoint(
                cdir, prefix, check_finite=True, max_step=ev.step,
                distributed=jax.process_count() > 1, deep=True)
        if found is None:
            found = ckpt.latest_checkpoint(
                cdir, prefix, check_finite=True, max_step=ev.step,
                distributed=jax.process_count() > 1)
        target = ((ckpt.checkpoint_step(found), found)
                  if found is not None else None)
        if target is None:
            raise ResilienceError(
                f"run_resilient: {ev.kind} at step {ev.step} and no healthy "
                f"checkpoint generation exists under {cdir} to roll back "
                f"to.", events)
        pending.clear()
        if stall is not None:
            stall.clear()
        m_rollbacks.inc()
        with _telemetry.span("resilience.rollback", step=ev.step,
                             target_step=target[0]):
            state = ckpt.load_checkpoint(target[1])
        steps_done = target[0]
        synced.clear()
        synced.add(steps_done)   # the loaded generation IS the state now
        last_good = steps_done   # finite-verified on load
        last_ckpt = target[1]    # result.checkpoint names the LIVE state
        last_ckpt_step = steps_done
        # Generations NEWER than the target belong to the abandoned
        # attempt (finite or not, they are no longer this trajectory —
        # especially once recovery_policy changes the step): a later
        # resume scanning newest-first must never land on them.
        if jax.process_index() == 0:
            for s, p in _generations():
                if s > steps_done:
                    ckpt.remove_generation(p)
        _emit("rollback", steps_done, from_step=ev.step,
              attempt=retries, path=str(target[1]))
        if mon is not None:
            mon.on_rollback(state, steps_done)
            if ev.kind == "integrity_violation":
                # The corruption verdict is no longer live: the state was
                # replaced from a verified generation.  statusd readiness
                # (pinned reason "integrity_violation") recovers on this
                # record.
                _emit("integrity_resolved", steps_done, from_step=ev.step,
                      path=str(target[1]),
                      deep_verified=bool(mon.deep_verify))
        if recovery_policy is not None:
            out = recovery_policy(retries, state, ev)
            if isinstance(out, tuple):
                state, step_fn = out
            elif out is not None:
                state = out

    def _heal_retile(act) -> bool:
        """Loop 1's action (igg.heal): seal a final generation, fence the
        suspect device(s), re-plan `dims` over the survivors, re-init the
        grid, and resume elastically from the sealed generation — the
        PR-4 redistribute restore driven by a detection instead of an
        operator.  Returns True when the loop must `continue` (the state
        and decomposition changed)."""
        nonlocal state, steps_done, last_good, last_ckpt, \
            last_ckpt_step, comm_mon, stats, run_stamp
        if cdir is None:
            # Budget-refunded skip: a retile without a ring is
            # unactionable for the whole run — it must neither escalate
            # nor be re-planned.
            heal_eng.record_skipped("retile", reason="no_checkpoint_ring")
            _telemetry.emit("heal_skipped", step=steps_done,
                            run="resilient", action="retile",
                            why="no checkpoint ring to seal/resume from")
            return False
        from .finalize import finalize_global_grid
        from .init import init_global_grid

        grid = shared.global_grid()
        old_dims, old_ndev = tuple(grid.dims), grid.nprocs
        try:
            devs, new_dims, new_local = heal_eng.plan_retile(
                grid, suspects=act.get("suspects"))
        except GridError as e:
            heal_eng.record_skipped("retile", reason=str(e))
            _telemetry.emit("heal_skipped", step=steps_done,
                            run="resilient", action="retile",
                            why=f"no decomposition fits the survivors: {e}")
            return False
        # Seal the handoff generation: every in-flight background write
        # settled first, then a synchronous write unless a generation at
        # this exact step already holds this state.
        _merge_writer(drain=True)
        if steps_done not in synced:
            _save_gen(steps_done)
        pending.clear()
        if stall is not None:
            stall.clear()
        periods, overlaps = tuple(grid.periods), tuple(grid.overlaps)
        with _telemetry.span("heal.retile", step=steps_done,
                             from_dims=list(old_dims),
                             dims=list(new_dims)):
            finalize_global_grid()
            init_global_grid(
                *new_local, dimx=new_dims[0], dimy=new_dims[1],
                dimz=new_dims[2], periodx=periods[0], periody=periods[1],
                periodz=periods[2], overlapx=overlaps[0],
                overlapy=overlaps[1], overlapz=overlaps[2],
                devices=devs, quiet=True)
            found = None
            if mon is not None and mon.deep_verify:
                # The retile resume honors the verified-generation
                # contract too: an integrity-triggered re-tile must never
                # resume from the very generation the violation poisoned.
                found = ckpt.latest_checkpoint(cdir, prefix,
                                               check_finite=True, deep=True)
            if found is None:
                found = ckpt.latest_checkpoint(cdir, prefix,
                                               check_finite=True)
            if found is None:
                raise ResilienceError(
                    f"igg.heal: elastic re-tile at step {steps_done} found "
                    f"no healthy generation under {cdir} to resume from.",
                    events)
            state = ckpt.load_checkpoint(found, redistribute=True)
            steps_done = ckpt.checkpoint_step(found) or 0
        # Everything compiled on the retiled-away mesh re-traces lazily
        # (igg.sharded keys on the grid epoch); run-scoped bookkeeping is
        # re-anchored here.  finalize cleared the ladder, so the demotion
        # scope stamp restarts too.
        run_stamp = _degrade.dispatch_stamp()
        synced.clear()
        synced.add(steps_done)
        last_good = steps_done
        last_ckpt, last_ckpt_step = found, steps_done
        if mon is not None:
            # The probe re-traces on the new grid epoch; per-rank
            # reference partials re-anchor at the next clean fetch (the
            # global references survive — same field, fewer devices).
            mon.on_retile(state, steps_done)
        stats = _telemetry.StepStats(
            "resilient",
            perf=(_perf.sample_context(state[watch[0]])
                  if watch and _perf.enabled() else None))
        if comm_mon is not None:
            # Its decomposition probe programs hold the dead mesh, and a
            # monitor cannot be rebuilt without the caller's compute fn.
            try:
                comm_mon.finalize(steps_done)
            except Exception:
                pass
            comm_mon = None
            _telemetry.emit("heal_skipped", step=steps_done,
                            run="resilient", action="comm_monitor",
                            why="decomposition probes were built on the "
                                "retiled-away mesh; monitor detached")
        heal_eng.record_done("retile", from_dims=list(old_dims),
                             dims=list(new_dims), devices=len(devs),
                             step=steps_done)
        # The smaller surviving grid is legitimately slower per step —
        # the straggler detector must re-baseline, not compare against
        # the old topology.
        heal_eng.reset_baseline()
        _emit("heal_retile", steps_done, from_dims=list(old_dims),
              from_devices=old_ndev, dims=list(new_dims),
              devices=len(devs), path=str(found),
              reason=act.get("reason"))
        return True

    def _heal_act() -> bool:
        """Execute the heal engine's next planned action at this dispatch
        boundary; True means the loop must `continue` (state changed)."""
        act = heal_eng.pop()
        if act is None:
            return False
        kind = act["action"]
        if kind == "retile":
            return _heal_retile(act)
        if kind == "recalibrate":
            from . import heal as _heal_mod

            fam = act.get("family")
            if fam:
                with _telemetry.span("heal.recalibrate", step=steps_done,
                                     family=fam):
                    sec = _heal_mod.recalibrate(fam, tier=act.get("tier"))
                heal_eng.record_done("recalibrate", family=fam,
                                     measured_s_per_step=sec)
                # `recalibrate` just emitted the authoritative
                # `recalibrated` bus record; this is the per-run view's
                # step-anchored copy only.
                _emit("heal_recalibrate", steps_done, _bus=False,
                      family=fam, measured_s_per_step=sec)
            return False
        if kind == "demote":
            demoted = _degrade.demote_active(
                reason="heal_escalation",
                error_text=f"heal escalation: "
                           f"{act.get('escalated_from')} budget exhausted "
                           f"and the failure signal persists",
                since=run_stamp)
            heal_eng.record_done("demote", tiers=demoted)
            for tname in demoted:
                _emit("tier_degraded", steps_done, _bus=False, tier=tname,
                      reason="heal_escalation")
            return False
        if kind == "fail":
            from . import heal as _heal_mod

            raise _heal_mod.HealEscalation(
                f"igg.heal: the action budget "
                f"(max_actions={heal_eng.policy.max_actions}) is "
                f"exhausted, the escalation ladder is walked, and the "
                f"failure signal ({act.get('escalated_from')}: "
                f"{act.get('signal_reason')}) persists at step "
                f"{steps_done}.", events)
        return False

    installed = False
    old_handler = None
    if install_sigterm:
        try:
            old_handler = signal.signal(signal.SIGTERM, request_preemption)
            installed = True
        except ValueError:
            pass   # not on the main thread: caller owns signal wiring

    try:
        # A fresh run (resume=False) owns its ring: generations left in
        # the directory by a PREVIOUS run are not this run's trajectory,
        # and a later rollback or resume scanning the directory must never
        # land on one (silently wrong results) — clear them.  Gated on the
        # DIRECTORY, not the cadence: a preemption-checkpoint-only config
        # (checkpoint_dir set, checkpoint_every=0) scans the same ring.
        # resume=True is the way to continue from an existing ring.
        if cdir is not None and not resume and jax.process_index() == 0:
            for _, old in _generations():
                ckpt.remove_generation(old)
        # Entry generation, so a rollback target exists from step 0 (a
        # resume that just loaded the generation at this exact step skips
        # the identical rewrite).
        if checkpoint_every and steps_done != resumed_step:
            _save_gen(steps_done)
        if mon is not None:
            # Shadow spot checks: snapshot the entry state (device-
            # resident references, no fetch) so the FIRST watch window is
            # re-executable.
            mon.arm_entry(state, steps_done)

        final_probe_done = False
        donation_probe = bool(use_async)   # probe until donation observed
        while True:
            while steps_done < n_steps:
                if preemption_requested():
                    preempted = True
                    break
                # Self-healing actions execute at dispatch boundaries (a
                # deque check when idle — the heal_overhead contract).
                if heal_eng is not None and heal_eng.has_pending():
                    if _heal_act():
                        continue
                if chaos is not None:
                    state = chaos.apply(state, steps_done, _emit,
                                        span=steps_per_call)
                    if preemption_requested():
                        preempted = True
                        break
                state_tap = _CHAOS_STATE_TAP
                if state_tap is not None:
                    # Silent-corruption seam (igg.chaos.silent_corruption):
                    # a host-level, one-shot finite perturbation at the
                    # dispatch boundary — the fault the NaN watchdog
                    # provably cannot see.
                    state = state_tap(state, steps_done, _emit,
                                      steps_per_call)
                # EVERY field is probed: a step may donate some fields but
                # not the dict's first one (e.g. a pass-through
                # coefficient), and missing the donation would cost a ring
                # generation before the writer's own guard catches up.
                prev = tuple(state.values()) if donation_probe else ()
                state = step_fn(state)
                if donation_probe and any(_is_deleted(x) for x in prev):
                    # Donation is runtime-dependent (a dispatch whose
                    # input buffer is externally referenced — e.g. by a
                    # checkpoint fetch — may copy instead of alias), so
                    # every dispatch is probed until deletion is first
                    # OBSERVED; from then on cadence generations degrade
                    # to synchronous writes.
                    donation_probe = False
                    donating = True
                    if writer is not None:
                        writer.note_donation()
                    if mon is not None:
                        # Shadow snapshots are held by reference too —
                        # same hazard, same degradation (invariant probes
                        # keep running; only the re-execution checks
                        # stop).
                        mon.note_donation()
                    import warnings

                    warnings.warn(
                        "igg.run_resilient: step_fn DONATES its input "
                        "buffers (the pre-step state was invalidated "
                        "by the dispatch); asynchronous checkpoint "
                        "snapshots would be deleted before the "
                        "background writer fetches them — cadence "
                        "generations degrade to synchronous writes "
                        "for this run (use donate=False steps to keep "
                        "async writes).  (Warned once per run.)",
                        stacklevel=2)
                steps_done += steps_per_call
                m_steps.inc(steps_per_call)
                fail = None
                if probe is not None and steps_done % watch_every == 0:
                    _dispatch_probe()
                    if comm_mon is not None:
                        comm_mon.maybe_dispatch(steps_done, stall)
                if (divergence_fn is not None and watch_every
                        and steps_done % watch_every == 0
                        and divergence_fn(state)):
                    fail = _emit("divergence", steps_done)
                if fail is None:
                    fail = _poll_probes()
                if fail is not None:
                    _rollback(fail)
                    continue
                if checkpoint_every and steps_done % checkpoint_every == 0:
                    # Cadence generations go to the background writer (the
                    # hot loop's cost is a reference snapshot + queue put);
                    # entry/rollback/preemption generations stay sync.
                    _save_gen(steps_done, sync=False)
                _merge_writer()   # cheap: a deque pop, no blocking
                if comm_mon is not None:
                    comm_mon.poll(steps_done, stall)   # is_ready only
                if tel is not None:
                    tel.maybe_export_metrics()   # one clock read when idle
            if preempted:
                break
            # End of the run: probe the tail window (if the final step is
            # off-cadence) and drain every pending probe — a failure here
            # still rolls back and replays.
            if (probe is not None and not final_probe_done
                    and steps_done % watch_every != 0):
                final_probe_done = True
                _dispatch_probe()
            fail = _poll_probes(drain=True)
            if fail is None:
                _merge_writer(drain=True)
                break
            _rollback(fail)

        if preempted:
            # A blowup inside the last watch window must not become the
            # final generation: probe the tail, drain, and roll back first
            # (the rollback may raise — then the existing healthy
            # generations stand and the caller sees the real failure).
            if probe is not None and steps_done % watch_every != 0:
                _dispatch_probe()
            fail = _poll_probes(drain=True)
            if fail is not None:
                _rollback(fail)
            # Drain the background writer before the final generation: a
            # cadence write still in flight at this step makes the rewrite
            # redundant, and the final write must never race a background
            # one (SIGTERM grace windows are exactly for this drain).
            _merge_writer(drain=True)
            # Final atomic generation (skipped when a generation at this
            # step — the cadence write, or the one just rolled back to —
            # already holds this state).  Multi-controller: the sharded
            # save is a cross-process rendezvous, so the skip decision
            # must be GLOBALLY consistent — `synced` can diverge (one
            # process's background write failed, or its commit wait timed
            # out after process 0 sealed), and a subset entering the
            # rendezvous alone would hang out the SIGTERM grace window.
            # AND-combine the per-process verdicts: if anyone is missing
            # the generation, everyone rewrites it (overwriting a
            # committed generation is safe — the save replaces it
            # atomically).
            if cdir is not None:
                have = steps_done in synced
                if jax.process_count() > 1:
                    have = ckpt._combine_verdicts(have)
                if not have:
                    _save_gen(steps_done)
            _emit("preempt", steps_done,
                  path=str(last_ckpt) if last_ckpt else None)
            # Post-mortems always have the tail of the story: SIGTERM is
            # one of the flight recorder's auto-dump triggers.
            _telemetry._auto_dump(f"preempt at step {steps_done}")
    except BaseException as e:
        # ResilienceError, the retry-budget exhaustion path, and any
        # unhandled escape: dump the flight recorder wherever a sink is
        # configured, then re-raise — a ResilienceError additionally
        # carries the dump path(s), so the exception message NAMES the
        # operator's first postmortem artifact.
        paths = _telemetry._auto_dump(f"run_resilient: "
                                      f"{type(e).__name__}: {e}")
        if isinstance(e, ResilienceError):
            e.dump_paths.extend(p for p in paths if p not in e.dump_paths)
        raise
    finally:
        if mon is not None:
            mon.close()   # clears the checkpoint-stamp context
        if heal_eng is not None:
            heal_eng.detach()
        if comm_mon is not None:
            try:
                comm_mon.finalize(steps_done)
            except Exception:
                pass   # a broken probe must not mask the run's outcome
        if stall is not None:
            stall.close()
        if writer is not None:
            try:
                _merge_writer(drain=True)
            finally:
                writer.close()
        if installed:
            signal.signal(signal.SIGTERM, old_handler)
        clear_preemption()
        _telemetry.emit("run_finished", step=steps_done, run="resilient",
                        preempted=preempted, retries=retries)
        if srv_owns:
            srv.stop()
        if tel is not None:
            # Owned sessions get their final export inside detach();
            # exporting here too would write two identical back-to-back
            # snapshots.  Shared sessions stay attached, so the run-final
            # snapshot is written explicitly.
            if tel_owns:
                tel.detach()
            else:
                tel.export_metrics()

    return RunResult(state=state, steps_done=steps_done, retries=retries,
                     preempted=preempted, events=events, checkpoint=last_ckpt)
