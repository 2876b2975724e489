"""Verified tiered dispatch — the graceful degradation ladder.

igg serves every model family through a ladder of kernel tiers (trapezoid
chunk → per-step Mosaic → pure-XLA composition, plus the halo engine's
Pallas-writer vs XLA-plan election).  The fast tiers are an OPTIMIZATION,
never a correctness dependency — the reference's own design rule
(`/root/reference/src/update_halo.jl` falls back transparently when
CUDA-aware MPI is absent).  Hand-written admission predicates decide where
a tier *applies*; this module owns what happens when an admitted tier
*fails anyway* — a Mosaic compile error on a new toolchain, or worse, a
miscompiled kernel silently producing wrong physics.  Portable stencil
frameworks treat verified fallback as a first-class subsystem, and TPU
production simulation stacks numerically cross-check kernels against a
reference path (PAPERS.md); this is igg's version of both:

- **Compile-failure capture.**  The first build/trace/compile of a tier is
  guarded: an XLA/Mosaic lowering failure quarantines that tier for the
  process with a structured one-time warning naming the tier and the
  captured error, and dispatch falls to the next rung.  Errors after a
  tier has served successfully are real and propagate.

- **Numeric verify-on-first-use.**  With ``verify="first_use"`` on a model
  factory (or ``IGG_VERIFY_KERNELS=1`` globally), a tier runs ONE dispatch
  on scratch copies of the real arguments against the pure-XLA composition
  truth before it serves real traffic, tolerance-gated per dtype.  A
  mismatch quarantines the tier and dispatch falls back — a wrong answer
  is never served.  The cost is one extra tier dispatch plus one truth
  dispatch per (tier, argument signature), amortized below 1% of a
  1000-step run (``benchmarks/resilience_overhead.py``, asserted in CI).

- **Quarantine is observable and resettable.**  :func:`status` returns
  the quarantined tiers (tier, rung, reason, captured error);
  :func:`events` the `tier_degraded` event log; :func:`active` the tier
  that served each family's last dispatch.  :func:`reset` clears state
  (``igg.finalize_global_grid`` does it with the other caches).

- **Recovery-ladder rung.**  :func:`igg.run_resilient` calls
  :func:`demote_active` when a NaN recurs at the same step after a
  rollback — the signature of a deterministic kernel blowup — so
  miscompile-shaped failures recover by tier demotion with zero
  user-supplied `recovery_policy` code (`tier_degraded` events in the run
  log).

- **Provable.**  :mod:`igg.chaos` injects both failure shapes through the
  `_CHAOS_TIER_TAP` dispatch seam (``kernel_compile_fail``,
  ``kernel_corrupt`` — the `_CHAOS_PLANE_TAP` pattern), so every rung of
  the ladder is demonstrable on the 8-device CPU interpret mesh in CI.

Model families route through :class:`Ladder`
(`igg/models/_dispatch.py:auto_dispatch`); the halo engine's
writer-vs-XLA election consults :data:`HALO_WRITER_TIER` quarantine
directly (`igg/halo.py`).
"""

from __future__ import annotations

import dataclasses
import threading
import time
import warnings
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from . import _env, profiling
from . import telemetry as _telemetry
from .shared import GridError

__all__ = ["Admission", "Quarantine", "Tier", "Ladder", "status", "events",
           "active", "is_quarantined", "quarantine", "reset",
           "demote_active", "HALO_WRITER_TIER"]


# The halo engine's in-place Pallas writer tier (rung 0 of the assembly
# ladder; rung 1 is the XLA masked-select/aligned-DUS plans, the truth).
HALO_WRITER_TIER = "halo.writer"


class Admission:
    """Structured admission verdict: truthy/falsy like the bare bools the
    gates used to return, plus the human-readable reason a tier was
    refused — so ``igg.degrade`` (and a user debugging "why is my run on
    the slow path?") can see *which* gate failed instead of a bare
    False."""

    __slots__ = ("ok", "reason")

    def __init__(self, ok: bool, reason: str = ""):
        self.ok = bool(ok)
        self.reason = reason

    def __bool__(self) -> bool:
        return self.ok

    def __repr__(self) -> str:
        return (f"Admission(ok={self.ok}"
                + (f", reason={self.reason!r}" if self.reason else "") + ")")

    @classmethod
    def yes(cls) -> "Admission":
        return cls(True)

    @classmethod
    def no(cls, reason: str) -> "Admission":
        return cls(False, reason)


@dataclasses.dataclass(frozen=True)
class Quarantine:
    """One quarantined tier: which rung it sat on, why it was pulled
    ('compile_failed', 'verify_mismatch', 'nan_recurrence'), and the
    captured error text (the Mosaic/XLA lowering failure, the numeric
    mismatch magnitudes, or the recurrence description)."""
    tier: str
    rung: int
    reason: str
    error: Optional[str] = None


# Process-wide ladder state.  Quarantine is keyed by tier NAME so every
# ladder instance of a family (factories are cheap and recreated freely)
# shares one verdict; the lock guards mutation from the resilient loop's
# threads (async writers poll on the caller's thread, but demotion can race
# a concurrent dispatch in principle).
_lock = threading.Lock()
_QUARANTINE: Dict[str, Quarantine] = {}
_ACTIVE: Dict[str, str] = {}            # family -> tier serving last dispatch
_ACTIVE_STAMP: Dict[str, int] = {}      # family -> dispatch counter at that
_DISPATCHES = 0                         # monotone dispatch counter
_SERVED: set = set()                    # tier names that have served, keyed
#   process-wide like quarantine: a recreated factory must not re-treat a
#   proven tier's first transient runtime error as a compile failure.
_VERIFIED: set = set()                  # (tier name, argument signature)
_ADMISSION_LOG: Dict[str, str] = {}     # tier -> last structured skip reason
_EVENTS: List[dict] = []                # tier_degraded event log
_warned: set = set()                    # tiers already warned about

# Fault-injection seam (igg.chaos.kernel_compile_fail / kernel_corrupt —
# the `_CHAOS_PLANE_TAP` pattern applied to tier dispatch): a dict
# {"compile_fail": {tier: message}, "corrupt": {tier: magnitude}} consulted
# at the two guard points.  Host-level (never traced into compiled
# programs), so arming/disarming needs no cache clearing.
_CHAOS_TIER_TAP: Optional[dict] = None


class InjectedCompileError(RuntimeError):
    """The chaos stand-in for an XLA/Mosaic lowering failure."""


def _chaos_compile_check(tier: str) -> None:
    tap = _CHAOS_TIER_TAP
    if tap and tier in tap.get("compile_fail", {}):
        raise InjectedCompileError(
            tap["compile_fail"][tier]
            or f"Mosaic lowering failed (chaos-injected) for tier {tier}")


def _chaos_corrupt(tier: str, out):
    """Apply an armed output corruption for `tier` — the deterministic
    stand-in for a miscompiled kernel: every dispatch of the tier perturbs
    one interior element of its first floating output by `magnitude`
    (sharding preserved)."""
    tap = _CHAOS_TIER_TAP
    if not tap or tier not in tap.get("corrupt", {}):
        return out
    magnitude = tap["corrupt"][tier]
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree_util.tree_flatten(out)
    for i, leaf in enumerate(leaves):
        if (hasattr(leaf, "dtype")
                and jnp.issubdtype(leaf.dtype, jnp.inexact)):
            idx = tuple(min(1, s - 1) for s in leaf.shape)
            bad = leaf.at[idx].add(jnp.asarray(magnitude, leaf.dtype))
            sharding = getattr(leaf, "sharding", None)
            if sharding is not None:
                bad = jax.device_put(bad, sharding)
            leaves[i] = bad
            break
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# Quarantine state
# ---------------------------------------------------------------------------

def quarantine(tier: str, rung: int, reason: str,
               error: Optional[BaseException] = None,
               error_text: Optional[str] = None) -> Quarantine:
    """Pull `tier` out of dispatch for the process: records the verdict,
    appends a `tier_degraded` event, and warns ONCE naming the tier and
    the captured error (so a degraded production run is loud exactly once,
    not per step and not never)."""
    text = error_text if error_text is not None else (
        f"{type(error).__name__}: {error}" if error is not None else None)
    q = Quarantine(tier=tier, rung=rung, reason=reason, error=text)
    with _lock:
        _QUARANTINE[tier] = q
        _EVENTS.append({"kind": "tier_degraded", "tier": tier, "rung": rung,
                        "reason": reason, "error": text})
        warn = tier not in _warned
        _warned.add(tier)
    # The unified bus (igg.telemetry): `events()` stays the ladder's own
    # filtered view; the bus record adds timestamps/rank for post-mortems.
    _telemetry.emit("tier_degraded", tier=tier, rung=rung, reason=reason,
                    error=text)
    _telemetry.counter("igg_tier_quarantined_total", tier=tier).inc()
    if warn:
        warnings.warn(
            f"igg.degrade: tier {tier!r} (rung {rung}) quarantined "
            f"({reason}); dispatch falls to the next rung.  Captured: "
            f"{text or '<none>'}.  igg.degrade.status() queries, "
            f"igg.degrade.reset({tier!r}) re-admits.", stacklevel=2)
    if tier == HALO_WRITER_TIER:
        _drop_halo_programs()
    return q


def _drop_halo_programs() -> None:
    """The halo writer election (`igg.halo._writer_dims`) is read at TRACE
    time, so flipping the writer tier's quarantine must drop every
    compiled program that may have baked the old election in (the
    `_CHAOS_PLANE_TAP` convention)."""
    try:
        from . import halo, parallel
    except ImportError:     # interpreter teardown
        return
    halo.free_update_halo_buffers()
    parallel.free_sharded_cache()


def is_quarantined(tier: str) -> bool:
    return tier in _QUARANTINE


def status() -> Dict[str, Quarantine]:
    """The quarantined tiers: `{tier: Quarantine(tier, rung, reason,
    error)}` (empty when every tier is healthy)."""
    return dict(_QUARANTINE)


def events() -> List[dict]:
    """The `tier_degraded` event log, oldest first (each entry: kind,
    tier, rung, reason, error)."""
    return list(_EVENTS)


def active() -> Dict[str, str]:
    """Which tier served each family's most recent dispatch."""
    return dict(_ACTIVE)


def active_records() -> List[Tuple[str, str, int]]:
    """`[(family, serving tier, dispatch stamp)]` — :func:`active` plus
    the monotone dispatch counter at each family's last dispatch, so a
    consumer (the perf ledger's watchdog-window attribution,
    `igg.perf.observe_window`) can tell which families dispatched inside
    a given interval of :func:`dispatch_stamp` snapshots."""
    with _lock:
        return [(f, t, _ACTIVE_STAMP.get(f, 0))
                for f, t in _ACTIVE.items()]


def admission_log() -> Dict[str, str]:
    """The last structured refusal reason per tier (admission gates that
    returned False on the most recent dispatch walk)."""
    return dict(_ADMISSION_LOG)


def reset(tier: Optional[str] = None) -> None:
    """Re-admit `tier` (or, with no argument, clear ALL ladder state:
    quarantine, verification memory, active-tier records, the event log,
    and the one-time-warning memory).  `igg.finalize_global_grid` calls
    the full reset with the other caches."""
    with _lock:
        if tier is not None:
            was = _QUARANTINE.pop(tier, None)
            _warned.discard(tier)
            _SERVED.discard(tier)
            for key in [k for k in _VERIFIED if k[0] == tier]:
                _VERIFIED.discard(key)
            if was is not None and tier == HALO_WRITER_TIER:
                _drop_halo_programs()
            return
        had_writer = HALO_WRITER_TIER in _QUARANTINE
        _QUARANTINE.clear()
        _ACTIVE.clear()
        _ACTIVE_STAMP.clear()
        _SERVED.clear()
        _VERIFIED.clear()
        _ADMISSION_LOG.clear()
        _EVENTS.clear()
        _warned.clear()
        # Drop the family -> newest-ladder map too: a retained ladder holds
        # its _built compiled callables (closures over a possibly-finalized
        # mesh), which would otherwise outlive every cache finalize clears.
        _LADDERS.clear()
    if had_writer:
        _drop_halo_programs()


def dispatch_stamp() -> int:
    """The monotone ladder-dispatch counter: snapshot it before a run and
    pass it to :func:`demote_active` as `since` to scope demotion to the
    families that actually dispatched during that run."""
    return _DISPATCHES


def diagnostic_dispatches():
    """Context manager under which ladder dispatches do NOT update the
    per-family active-tier records (they are snapshotted on entry and
    restored on exit).  For DIAGNOSTIC re-executions — the
    :mod:`igg.integrity` shadow replay runs the family's truth step
    between two serving dispatches, and without this guard the truth
    rung would look like the serving tier to :func:`demote_active`
    (nothing left to demote) and to the perf ledger's watchdog-window
    attribution (diagnostic work booked as serving throughput)."""
    import contextlib

    @contextlib.contextmanager
    def _ctx():
        with _lock:
            act, stamps = dict(_ACTIVE), dict(_ACTIVE_STAMP)
        try:
            yield
        finally:
            with _lock:
                _ACTIVE.clear()
                _ACTIVE.update(act)
                _ACTIVE_STAMP.clear()
                _ACTIVE_STAMP.update(stamps)

    return _ctx()


def demote_active(reason: str = "nan_recurrence",
                  error_text: Optional[str] = None,
                  since: Optional[int] = None) -> List[str]:
    """Quarantine the non-truth tier(s) that served each family's most
    recent dispatch — the resilient loop's recovery rung for
    deterministic kernel blowups (a NaN recurring at the same step after
    a bit-exact rollback).  With `since` (a :func:`dispatch_stamp`
    snapshot), only families that dispatched strictly after that stamp
    are demoted — so a run's recovery never quarantines a healthy tier
    some unrelated earlier factory warmed.  Returns the quarantined tier
    names (empty when every eligible active tier is already the truth
    rung, i.e. there is nothing left to demote)."""
    demoted = []
    for family, tname in list(_ACTIVE.items()):
        if since is not None and _ACTIVE_STAMP.get(family, -1) <= since:
            continue
        ladder = _LADDERS.get(family)
        tier = ladder.tier(tname) if ladder is not None else None
        if tier is None or tier.truth or is_quarantined(tname):
            continue
        quarantine(tname, tier.rung, reason, error_text=error_text)
        demoted.append(tname)
    return demoted


# ---------------------------------------------------------------------------
# The ladder
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Tier:
    """One rung of a family's ladder.

    `build()` lazily returns the serving callable (built at most once per
    ladder); `admit(args)` returns an :class:`Admission`/bool per dispatch
    (None admits always); `truth` marks the pure-XLA composition rung —
    the verification oracle, exempt from quarantine and chaos;
    `required` + `requirement` realize the forced-tier contract
    (`use_pallas=True` / `trapezoid=True`): a required tier that is
    quarantined or refused raises `GridError` instead of silently serving
    a lower rung."""
    name: str
    rung: int
    build: Callable[[], Callable]
    admit: Optional[Callable[[tuple], object]] = None
    truth: bool = False
    required: bool = False
    requirement: Optional[str] = None


class _VerifyMismatch(Exception):
    def __init__(self, detail: str):
        super().__init__(detail)
        self.detail = detail


# Verification tolerances per dtype kind: |tier - truth| <= atol +
# rtol * max|truth| over every output leaf.  The tiers share their
# arithmetic source with the XLA composition (e.g.
# `stokes3d.iteration_core`), so the budget only has to absorb
# Mosaic-vs-XLA instruction ordering (~1 ulp/step, a few steps per
# dispatch) — far below any miscompile, whose corruption is O(field).
_TOLERANCES = {
    2: (2e-2, 1e-2),     # bf16 / f16
    4: (1e-4, 1e-5),     # f32
    8: (1e-9, 1e-12),    # f64
}


def _leaf_mismatch(i, a, b):
    """Reason text when output leaf `i` of tier and truth disagree beyond
    tolerance; None when they agree.  Host-side numpy throughout: the
    comparison is part of the one-time verify cost contract (< 1% of a
    1000-step run, `benchmarks/resilience_overhead.py`), and device-side
    comparison ops would charge a cascade of small one-time XLA compiles
    to it."""
    import jax.numpy as jnp
    import numpy as np

    if getattr(a, "shape", None) != getattr(b, "shape", None) or \
            getattr(a, "dtype", None) != getattr(b, "dtype", None):
        return (f"output {i}: structure {getattr(a, 'shape', a)}/"
                f"{getattr(a, 'dtype', '')} != {getattr(b, 'shape', b)}/"
                f"{getattr(b, 'dtype', '')}")
    if not hasattr(a, "dtype") or not jnp.issubdtype(a.dtype, jnp.inexact):
        if np.array_equal(np.asarray(a), np.asarray(b)):
            return None
        return f"output {i}: exact-dtype values differ"
    # Extension floats (bfloat16, float8_*) are numpy kind 'V'; widen
    # everything so the host comparison is dtype-agnostic and exact enough.
    wide = (np.complex128 if jnp.issubdtype(a.dtype, jnp.complexfloating)
            else np.float64)
    A = np.asarray(a).astype(wide)
    B = np.asarray(b).astype(wide)
    rtol, atol = _TOLERANCES.get(np.dtype(a.dtype).itemsize
                                 if np.dtype(a.dtype).kind != "V" else 2,
                                 (1e-4, 1e-5))
    with np.errstate(invalid="ignore", over="ignore"):
        # The tolerance scale must stay finite: an inf in the truth would
        # make tol=inf (any corruption passes) and a NaN would make it NaN
        # (nothing passes); non-finite cells are instead held to exact
        # agreement (same inf, or NaN on both sides) by the terms below.
        finite_B = np.abs(B)[np.isfinite(B)]
        scale = float(np.max(finite_B)) if finite_B.size else 0.0
        tol = atol + rtol * scale
        diff = np.abs(A - B)
        agree = ((diff <= tol) | (A == B)
                 | (np.isnan(A) & np.isnan(B)))
        nbad = int(np.sum(~agree))
        if nbad == 0:
            return None
        err = float(np.max(np.where(np.isfinite(diff), diff, np.inf)))
    return (f"output {i} ({a.shape}, {a.dtype}): {nbad} cell(s) beyond "
            f"tolerance, max|tier-truth|={err:.3e} vs tol={tol:.3e}")


def _compare_outputs(got, want) -> Optional[str]:
    import jax

    ga = jax.tree_util.tree_leaves(got)
    wa = jax.tree_util.tree_leaves(want)
    if len(ga) != len(wa):
        return f"tier returned {len(ga)} leaves, truth {len(wa)}"
    for i, (a, b) in enumerate(zip(ga, wa)):
        detail = _leaf_mismatch(i, a, b)
        if detail is not None:
            return detail
    return None


# Family -> most recent ladder (for demote_active's name->rung lookup;
# tier NAMES are stable across instances, so the newest registration is
# authoritative).
_LADDERS: Dict[str, "Ladder"] = {}

_VERIFY_MODES = (None, False, True, "first_use")


class Ladder:
    """A family's ordered tier ladder (fast rungs first, the pure-XLA
    truth rung last): walks admission, quarantine, the compile-failure
    capture, and verify-on-first-use per dispatch, serving the first rung
    that survives all four.  The truth rung always serves — it is exempt
    from quarantine and injection, so the ladder can never run out of
    rungs."""

    def __init__(self, family: str, tiers: Sequence[Tier],
                 verify=None):
        if not tiers or not tiers[-1].truth:
            raise GridError(f"Ladder({family!r}): the last tier must be "
                            f"the pure-XLA truth rung.")
        if verify not in _VERIFY_MODES:
            raise GridError(
                f"verify={verify!r}: expected None (IGG_VERIFY_KERNELS "
                f"decides), False (off), or 'first_use'.")
        self.family = family
        self.tiers = list(tiers)
        self.verify = verify
        self._built: Dict[str, Callable] = {}
        _LADDERS[family] = self

    def tier(self, name: str) -> Optional[Tier]:
        for t in self.tiers:
            if t.name == name:
                return t
        return None

    def _verify_enabled(self) -> bool:
        want = (bool(self.verify) if self.verify is not None
                else _env.flag("IGG_VERIFY_KERNELS"))
        if not want:
            return False
        import jax

        if jax.process_count() > 1:
            # The verdict must be process-global or the SPMD programs
            # diverge (one process quarantines, another serves the fast
            # tier), and the host-side comparison sees only addressable
            # shards — same reason the measured assembly election is
            # disabled multi-controller.  Pin tiers explicitly there.
            key = (self.family, "verify_multihost")
            with _lock:
                warn = key not in _warned
                _warned.add(key)
            if warn:
                warnings.warn(
                    f"igg.degrade: verify-on-first-use is disabled on "
                    f"multi-controller runs ({self.family}); pin the tier "
                    f"(use_pallas=False/...) if the fast path is suspect.",
                    stacklevel=3)
            return False
        return want

    def _fn(self, t: Tier) -> Callable:
        fn = self._built.get(t.name)
        if fn is None:
            if not t.truth:
                _chaos_compile_check(t.name)
            fn = t.build()
            self._built[t.name] = fn
        return fn

    def _call(self, t: Tier, fn: Callable, args: tuple):
        out = fn(*args)
        return out if t.truth else _chaos_corrupt(t.name, out)

    @staticmethod
    def _signature(args) -> tuple:
        return tuple((getattr(a, "shape", ()), str(getattr(a, "dtype", a)))
                     for a in args)

    def _verify_first_use(self, t: Tier, fn: Callable, args: tuple) -> None:
        """One tier dispatch against one truth dispatch on scratch copies
        of the real arguments (donation-safe), tolerance-gated per dtype;
        raises `_VerifyMismatch` on disagreement.  Runs at most once per
        (tier, argument signature)."""
        sig = self._signature(args)
        if (t.name, sig) in _VERIFIED:
            return
        import jax
        import numpy as np

        truth_fn = self._fn(self.tiers[-1])

        def scratch():
            # Fresh device copies through a host round-trip: donation-safe
            # without charging a one-time `a + 0` XLA compile per argument
            # shape to the verify cost contract (single-controller only —
            # _verify_enabled gates multihost off — so every shard is
            # addressable).
            out = []
            for a in args:
                if hasattr(a, "dtype"):
                    sharding = getattr(a, "sharding", None)
                    host = np.asarray(a)
                    out.append(jax.device_put(host, sharding)
                               if sharding is not None else host)
                else:
                    out.append(a)
            return tuple(out)
        with _telemetry.span("degrade.verify_first_use", tier=t.name,
                             family=self.family):
            got = self._call(t, fn, scratch())
            want = truth_fn(*scratch())
            detail = _compare_outputs(got, want)
        if detail is not None:
            raise _VerifyMismatch(detail)
        with _lock:
            _VERIFIED.add((t.name, sig))
        self._perf_sample(t, fn, scratch)

    def _perf_sample(self, t: Tier, fn: Callable, scratch) -> None:
        """One WARM timed dispatch into the perf ledger after a tier
        passes verification (the verify dispatch itself paid this
        signature's compile, so its wall time is not a serving-cost
        sample).  One extra dispatch per (tier, signature), inside the
        one-time verify cost contract; ms is per DISPATCH (== per step
        for the per-step factories).  Never allowed to fail a verified
        dispatch — perf bookkeeping is advisory."""
        from . import perf as _perf

        if not _perf.enabled():
            return
        try:
            import time as _time

            import jax

            args = scratch()
            t0 = _time.monotonic()
            out = self._call(t, fn, args)
            jax.block_until_ready(out)
            ms = (_time.monotonic() - t0) * 1e3
            ctx = _perf.sample_context(args[0] if args else None)
            _perf.record(self.family, t.name, ms,
                         source="verify_first_use",
                         local_shape=ctx.get("local_shape", ()),
                         dtype=ctx.get("dtype", "-"),
                         dims=ctx.get("dims"), backend=ctx.get("backend"),
                         device_kind=ctx.get("device_kind"))
        except Exception:   # pragma: no cover - advisory path
            pass

    def _record_active(self, tier_name: str, fn: Callable) -> int:
        """Stamp the dispatch `tier_name` served with `fn`, and count it
        with the counts each dispatch of `fn` performs
        (`igg.parallel.count_per_dispatch`)."""
        global _DISPATCHES
        with _lock:
            _DISPATCHES += 1
            stamp = _DISPATCHES
            _ACTIVE[self.family] = tier_name
            _ACTIVE_STAMP[self.family] = stamp
        _telemetry.counter("igg_tier_dispatch_total", family=self.family,
                           tier=tier_name).inc()
        for name, n in getattr(fn, "per_dispatch", {}).items():
            _telemetry.counter(name, family=self.family,
                               tier=tier_name).inc(n)
        return stamp

    def dispatch(self, *args):
        """Serve one dispatch: the `igg.dispatch` profiler span (family,
        serving tier, dispatch stamp) around the walk; its host seconds
        go to `igg_dispatch_host_seconds{family}` unless it compiled, in
        which case the compile stages went to `igg_compile_seconds`."""
        t0 = time.perf_counter()
        with profiling.annotate("igg.dispatch", family=self.family) as span, \
                profiling.program_frame() as frame:
            out, tier, stamp = self._serve(args)
            span.set_metadata(tier=tier, stamp=stamp)
        if not frame.compiled:
            _telemetry.histogram("igg_dispatch_host_seconds",
                                 family=self.family).observe(
                                     time.perf_counter() - t0)
        return out

    def _admit(self, i: int, args: tuple) -> int:
        """Index of the first tier from `i` on that is neither quarantined
        nor refused by its admission gate (the truth rung always admits);
        a required tier that is either raises."""
        for i in range(i, len(self.tiers)):
            t = self.tiers[i]
            if t.truth:
                return i
            if is_quarantined(t.name):
                if t.required:
                    q = _QUARANTINE[t.name]
                    raise GridError(
                        f"tier {t.name} is required "
                        f"(use_pallas=True/trapezoid=True) but quarantined "
                        f"({q.reason}): {q.error or '<no capture>'}.  "
                        f"igg.degrade.reset({t.name!r}) re-admits it.")
                continue
            adm = t.admit(args) if t.admit is not None else True
            if not adm:
                reason = getattr(adm, "reason", "") or "not admitted"
                _ADMISSION_LOG[t.name] = reason
                if t.required:
                    raise GridError(t.requirement
                                    or f"tier {t.name}: {reason}")
                continue
            return i
        raise GridError(   # unreachable: the last rung is the truth rung
            f"degrade: no tier of {self.family} could serve the dispatch.")

    def _serve(self, args: tuple):
        """(outputs, serving tier, dispatch stamp) of the first admitted
        tier that survives the compile-failure capture and verification."""
        i = 0
        while True:
            with profiling.annotate("igg.admit"):
                i = self._admit(i, args)
            t = self.tiers[i]
            i += 1
            if t.truth:
                fn = self._fn(t)
                out = fn(*args)
                return out, t.name, self._record_active(t.name, fn)
            try:
                fn = self._fn(t)
                if self._verify_enabled():
                    self._verify_first_use(t, fn, args)
                out = self._call(t, fn, args)
            except GridError:
                raise
            except _VerifyMismatch as e:
                quarantine(t.name, t.rung, "verify_mismatch",
                           error_text=e.detail)
                if t.required:
                    raise GridError(
                        f"tier {t.name} is required but failed "
                        f"verify-on-first-use against the XLA composition "
                        f"truth: {e.detail}") from e
                continue
            except Exception as e:
                if t.name in _SERVED:
                    raise     # post-first-success failures are real
                if any(getattr(a, "is_deleted", lambda: False)()
                       for a in args):
                    # The tier donates its inputs: a post-donation runtime
                    # failure has consumed them — the next rung cannot be
                    # dispatched, and the error says nothing about
                    # compilation.  Propagate it unclaimed.
                    raise
                quarantine(t.name, t.rung, "compile_failed", e)
                if t.required:
                    raise GridError(
                        f"tier {t.name} is required but its first "
                        f"compile/dispatch failed: "
                        f"{type(e).__name__}: {e}") from e
                continue
            _SERVED.add(t.name)
            return out, t.name, self._record_active(t.name, fn)
