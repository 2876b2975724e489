"""igg.integrity — the numeric-integrity layer: silent-data-corruption
defense for the resilient run loops.

Every health gate the earlier rounds built is NaN-shaped: the PR-3
watchdog counts non-finites, the rollback scan requires
``check_finite=True``, and ``verify_checkpoint`` is checksum +
all-finite.  A flaky chip or an HBM bit-flip that produces
*finite-but-wrong* values is invisible to all of it and gets faithfully
checkpointed, served, and weak-scaled — the fleet-scale SDC failure mode
the tuning/portability literature warns hand-checked kernels do not
cover (PAPERS 2406.08923, 2309.04671).  This module adds three
mechanisms, all under the zero-host-sync discipline (the PR-7 sentinel
runs with every one of them enabled):

1. **Invariant probes.**  Families declare conserved or bounded
   quantities — shallow-water mass, periodic-diffusion total heat, the
   wave energy bound — through :func:`register_invariants` (the
   ``igg.perf.register_family`` hook pattern, so `igg.stencil` specs
   participate without editing this module).  Each invariant is a
   moment sum over the de-duplicated OWNED cells of its fields
   (``Σ f^m``; m=1 conservation, m=2 energy), computed as per-device
   partial sums scattered into an ``(ndev,)`` vector and psum'd — the
   result is fused into the existing watchdog probe (ONE concatenated
   vector, ONE async ``is_ready()`` fetch per watch window, zero
   additional host syncs).  Drift past the per-invariant tolerance
   emits ``integrity_violation`` carrying the per-rank partial sums, so
   the suspect DEVICE is attributed on the spot (the partial that
   moved).

2. **Shadow re-execution spot checks.**  Every ``check_every`` watch
   windows the loop snapshots the window-entry state (device-resident
   references — no fetch) and, at the window's end, re-dispatches the
   window on the truth step and compares ON DEVICE: per-field
   ``Σ|state - truth|`` partials ride the SAME probe vector (the "wide"
   probe) and are fetched over the same async channel.  This catches
   corruption with no declared invariant; amortized cost is one extra
   window of compute per ``check_every`` windows (≈ 1/check_every).

3. **Verified-generation rollback.**  ``save_checkpoint{,_sharded}``
   stamp per-field owned-cell sums plus the active invariants'
   reference values into the checkpoint manifest;
   ``verify_checkpoint(deep=True)`` recomputes them, and the
   rollback/resume scans PREFER the newest deep-verified generation —
   closing the documented finite-but-poisoned window that
   ``check_finite`` cannot (a generation saved from corrupted-but-
   finite state carries a drifted invariant and is refused).

Wiring: the ``integrity=`` knob on :func:`igg.run_resilient` and
:func:`igg.run_ensemble` (None = on when ``IGG_INTEGRITY=1``; True =
env config; an :class:`IntegrityConfig`; False = off — the
``telemetry=``/``comm=`` pattern).  The heal loop (:mod:`igg.heal`)
closes detection→action: an attributed ``integrity_violation`` plans a
rollback-to-verified plus a fence-the-suspect-device elastic re-tile,
and the same violation recurring at the same step after a clean
rollback demotes the serving tier (the PR-5 deterministic-miscompile
signature, generalized — handled by the run loop's recurrence rung).

Chaos-provable end to end (``igg.chaos.silent_corruption`` /
``poison_checkpoint`` — finite perturbations the NaN watchdog provably
never sees): detection within one check window, rollback onto a
deep-verified generation skipping the poisoned one, fence + re-tile,
bit-exact finish (``tests/test_integrity.py``,
``examples/integrity_run.py``).  Overhead contract: the
``integrity_overhead`` row of ``benchmarks/resilience_overhead.py``
(< 1% over the bare watchdog loop at 128^3 ``watch_every=50``,
``host_syncs_added: 0``).
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import _env
from . import shared
from . import telemetry as _telemetry
from .shared import AXIS_NAMES, NDIMS, GridError

__all__ = ["Invariant", "IntegrityConfig", "register_invariants",
           "invariants_for", "registered_families", "match_invariants",
           "as_config", "Monitor", "DEFAULT_TOL"]

# Relative drift tolerance default (IGG_INTEGRITY_TOL).  The probe
# accumulates in f32 and the deep stamp in f64, so the floor must absorb
# ~1e-6 of cross-precision slack on top of the physical scheme's own
# conservation roundoff; 1e-3 is loose enough for f32 fields over long
# windows and tight enough that any corruption worth detecting (>> one
# ulp of the field) trips it.
DEFAULT_TOL = 1e-3
_TINY = 1e-30


@dataclasses.dataclass(frozen=True)
class Invariant:
    """One family-declared conserved or bounded quantity.

    The quantity is ``value = Σ_fields Σ_owned f^moment`` over the
    de-duplicated global interior (owned cells — overlap copies counted
    once, open-boundary user-owned planes included):

    - ``moment=1``, ``kind="conserved"`` — a conservation law (total
      heat, shallow-water mass): the value must stay within
      ``tol × scale`` of its reference, where ``scale = Σ|f|^moment``
      captured with the reference (robust for zero-mean fields, whose
      plain sum is ~0).
    - ``moment=2``, ``kind="bounded"`` — an energy-type bound (wave
      energy): the value may decay or oscillate but must never GROW
      past ``ref + tol × scale``.

    ``requires_periodic``: the law holds only on fully periodic sharded
    dims (an open boundary leaks the quantity); such invariants are
    auto-skipped on grids with open dims.  ``tol=None`` defers to the
    config/``IGG_INTEGRITY_TOL`` default."""
    name: str
    fields: Tuple[str, ...]
    moment: int = 1
    kind: str = "conserved"           # "conserved" | "bounded"
    tol: Optional[float] = None
    requires_periodic: bool = True

    def __post_init__(self):
        if self.moment not in (1, 2):
            raise GridError(f"Invariant {self.name!r}: moment must be 1 "
                            f"(sum) or 2 (sum of squares).")
        if self.kind not in ("conserved", "bounded"):
            raise GridError(f"Invariant {self.name!r}: kind must be "
                            f"'conserved' or 'bounded'.")
        if not self.fields:
            raise GridError(f"Invariant {self.name!r}: fields is empty.")
        object.__setattr__(self, "fields", tuple(self.fields))


# ---------------------------------------------------------------------------
# The family registry (the igg.perf.register_family hook pattern)
# ---------------------------------------------------------------------------

_REG_LOCK = threading.Lock()
_FAMILIES: Dict[str, Tuple[Invariant, ...]] = {}


def register_invariants(family: str, invariants: Sequence[Invariant]) -> None:
    """Declare `family`'s invariants (replacing any previous
    registration).  Model modules call this at import; `igg.stencil`
    spec families call it next to their ``igg.perf.register_family``
    registration, so spec-defined physics participates in the integrity
    probes without editing this module."""
    invs = tuple(invariants)
    for inv in invs:
        if not isinstance(inv, Invariant):
            raise GridError(f"register_invariants({family!r}): expected "
                            f"Invariant instances, got {type(inv).__name__}.")
    with _REG_LOCK:
        _FAMILIES[family] = invs


def invariants_for(family: str) -> Tuple[Invariant, ...]:
    with _REG_LOCK:
        return _FAMILIES.get(family, ())


def registered_families() -> List[str]:
    with _REG_LOCK:
        return sorted(_FAMILIES)


def match_invariants(state_keys, grid) -> Tuple[Invariant, ...]:
    """The zero-config default: every registered invariant whose fields
    are ALL present in the run's state dict (and whose periodicity
    requirement the live grid meets) is active.  Field names are the
    family's canonical ones ("T", "h"/"hu"/"hv", "P"/"Vx"/"Vy"), so a
    state dict using them opts in automatically; deduplicated by
    invariant name, first registration wins."""
    keys = set(state_keys)
    out: List[Invariant] = []
    seen = set()
    with _REG_LOCK:
        fams = list(_FAMILIES.items())   # registration (insertion) order
    for _, invs in fams:
        for inv in invs:
            if inv.name in seen or not set(inv.fields) <= keys:
                continue
            if inv.requires_periodic and not all(grid.periods):
                continue
            seen.add(inv.name)
            out.append(inv)
    return tuple(out)


# ---------------------------------------------------------------------------
# The integrity= knob
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class IntegrityConfig:
    """Configuration for one run's integrity layer.

    - `invariants`: explicit :class:`Invariant` list, or None for the
      registry auto-match against the state's field names.
    - `check_every`: shadow re-execution cadence in watch WINDOWS
      (default ``IGG_INTEGRITY_CHECK_EVERY``, 4; 0 disables shadows —
      invariant probes alone).  Amortized shadow cost ≈ 1/check_every.
    - `tol`: default relative drift tolerance (per-invariant `tol`
      overrides; default ``IGG_INTEGRITY_TOL``).
    - `shadow_tol`: relative tolerance of the shadow comparison
      (``Σ|state−truth|`` vs ``Σ|state|``; defaults to `tol` — with the
      live step as its own truth the diff is bitwise 0 when healthy).
    - `deep_verify`: rollback/resume scans prefer deep-verified
      generations (default ``IGG_INTEGRITY_DEEP_VERIFY``, on; stamps
      are written regardless).
    - `truth_step_fn`: the shadow re-execution step (e.g. the family's
      pure-XLA truth rung).  None re-dispatches the run's own step —
      which still catches NON-deterministic corruption (a flaky chip
      answers differently on re-execution; a deterministic miscompile
      is the recurrence-demotion rung's job)."""
    invariants: Optional[Sequence[Invariant]] = None
    check_every: Optional[int] = None
    tol: Optional[float] = None
    shadow_tol: Optional[float] = None
    deep_verify: Optional[bool] = None
    truth_step_fn: Optional[Callable] = None

    def resolved_check_every(self) -> int:
        if self.check_every is not None:
            ce = int(self.check_every)
        else:
            ce = int(_env.number("IGG_INTEGRITY_CHECK_EVERY", 4))
        if ce < 0:
            raise GridError("IntegrityConfig: check_every must be >= 0 "
                            "(0 disables shadow checks).")
        return ce

    def resolved_tol(self) -> float:
        tol = (float(self.tol) if self.tol is not None
               else float(_env.number("IGG_INTEGRITY_TOL", DEFAULT_TOL)))
        if tol <= 0:
            raise GridError("IntegrityConfig: tol must be > 0.")
        return tol

    def resolved_deep(self) -> bool:
        if self.deep_verify is not None:
            return bool(self.deep_verify)
        return _env.flag("IGG_INTEGRITY_DEEP_VERIFY", True)


def as_config(integrity) -> Optional[IntegrityConfig]:
    """Coerce the run loops' ``integrity=`` knob: None → a config only
    when ``IGG_INTEGRITY=1``; True → env config; an
    :class:`IntegrityConfig` → itself; False → off even when the env
    knob is set (the ``telemetry=``/``comm=`` pattern)."""
    if integrity is False:
        return None
    if integrity is None:
        if not _env.flag("IGG_INTEGRITY", False):
            return None
        return IntegrityConfig()
    if integrity is True:
        return IntegrityConfig()
    if isinstance(integrity, IntegrityConfig):
        return integrity
    raise GridError(
        f"integrity={integrity!r}: expected None, False, True, or an "
        f"igg.integrity.IntegrityConfig.")


# ---------------------------------------------------------------------------
# Device-side owned-cell reductions (traced inside the probe programs)
# ---------------------------------------------------------------------------

def _owned_weights(a, grid, lead: int = 0):
    """Per-dim ownership weights of a local block `a` (the checkpoint
    dedup algebra, traced): along each sharded dim the block owns its
    first ``s − ol`` cells — the LAST block of a non-periodic dim owns
    all ``s`` (its outer planes are de-duplicated global cells).
    Replicas of a lower-rank field on trailing mesh axes are gated to
    the coords-0 plane (the shard-ownership rule of the sharded
    checkpoint format).  `lead` skips leading non-grid axes (the
    ensemble member axis).  Returns ``(weights, gate)`` — broadcastable
    per-dim 0/1 factors and a scalar replica gate."""
    import jax.numpy as jnp
    from jax import lax

    nd = min(a.ndim - lead, NDIMS)
    ws = []
    for d in range(nd):
        s = int(a.shape[lead + d])
        ol = grid.overlaps[d] + (s - grid.nxyz[d])
        keep = s - max(ol, 0)
        iota = lax.broadcasted_iota(jnp.int32, (s,), 0)
        if grid.periods[d] or grid.dims[d] == 1 and not grid.periods[d]:
            # Periodic: every block owns its first `keep` cells.  A
            # single open block is also static: it IS the last block.
            lim = s if (not grid.periods[d] and grid.dims[d] == 1) else keep
            w = iota < lim
        else:
            idx = lax.axis_index(AXIS_NAMES[d])
            w = iota < jnp.where(idx == grid.dims[d] - 1, s, keep)
        shape = [1] * a.ndim
        shape[lead + d] = s
        ws.append(w.astype(jnp.float32).reshape(shape))
    gate = None
    for d in range(nd, NDIMS):
        if grid.dims[d] > 1:
            g = (lax.axis_index(AXIS_NAMES[d]) == 0).astype(jnp.float32)
            gate = g if gate is None else gate * g
    return ws, gate


def _owned_reduce(a, moment: int, grid, lead: int = 0, absolute=False):
    """Local partial ``Σ_owned f(a)`` (f = x, |x|, or x² per `moment`/
    `absolute`) reduced over the grid dims; with ``lead=1`` the leading
    member axis survives (a per-member vector)."""
    import jax.numpy as jnp

    x = _masked_moment(a, moment, grid, absolute=absolute, lead=lead)
    return jnp.sum(x, axis=tuple(range(lead, a.ndim)))


def _rank_scatter(local, grid):
    """Scatter a local scalar into an ``(ndev,)`` vector at this
    device's cart rank (x fastest — the shard-file numbering) and psum
    over every mesh axis: the replicated per-device partials the
    violation attribution reads."""
    import jax.numpy as jnp
    from jax import lax

    ix, iy, iz = (lax.axis_index(a) for a in AXIS_NAMES)
    dx, dy, _ = grid.dims
    flat = ix + iy * dx + iz * dx * dy
    vec = jnp.zeros((grid.nprocs,), jnp.float32).at[flat].set(local)
    return lax.psum(vec, AXIS_NAMES)


def _masked_moment(a, moment: int, grid, absolute=False, lead: int = 0):
    """Elementwise owned-cell moment term (NOT reduced): ``f(a) · w``
    with f = x, |x|, or x² — the shared input of the packed reductions
    below (the weights broadcast, so XLA fuses the masking into the
    reduce input instead of materializing a mask array)."""
    import jax.numpy as jnp

    x = a.astype(jnp.float32)
    if moment == 2:
        x = x * x
    elif absolute:
        x = jnp.abs(x)
    ws, gate = _owned_weights(a, grid, lead=lead)
    for w in ws:
        x = x * w
    if gate is not None:
        x = x * gate
    return x


def member_invariant_rows(invariants, arrays_by_field, pk_name: str, grid):
    """The ensemble probe's invariant rows (traced): per invariant, a
    per-member (M,) value row and scale row over the member-stacked
    local blocks (leading member axis), psum'd over grid axes under
    grid packing (batch packing's member shards need no collective —
    the count-probe contract)."""
    from .parallel import psum_grid

    rows = []
    for inv in invariants:
        val = sca = 0.0
        for f in inv.fields:
            a = arrays_by_field[f]
            val = val + _owned_reduce(a, inv.moment, grid, lead=1)
            sca = sca + _owned_reduce(a, inv.moment, grid, lead=1,
                                      absolute=True)
        if pk_name == "grid":
            val = psum_grid(val)
            sca = psum_grid(sca)
        rows.append(val)
        rows.append(sca)
    return rows


# ---------------------------------------------------------------------------
# The fused run probe (run_resilient)
# ---------------------------------------------------------------------------

def _moment_map(invariants: Sequence[Invariant]) -> Dict[str, Tuple[int, ...]]:
    """field → sorted moments any invariant needs of it (the probe's
    per-field work list; invariant values are recombined host-side as
    ``Σ_fields partial[f, m]``, matching the checkpoint deep stamps)."""
    moms: Dict[str, set] = {}
    for inv in invariants:
        for f in inv.fields:
            moms.setdefault(f, set()).add(inv.moment)
    return {f: tuple(sorted(ms)) for f, ms in moms.items()}


def _build_probe(watch: Sequence[str], extra: Sequence[str],
                 invariants: Sequence[Invariant], kind: str):
    """ONE compiled probe over the watched fields (+ invariant-only
    `extra` fields, + shadow-truth counterparts when ``kind="wide"``),
    concatenated into ONE replicated f32 vector so the loop's single
    async fetch covers everything (the zero-host-sync contract).

    The cost discipline (the ``integrity_overhead`` < 1% contract): XLA
    does not multi-output-fuse sibling reductions, so every extra
    reduction is a full memory pass over the field.  The steady-state
    probe therefore PACKS each watched field's non-finite count and its
    first owned-moment sum into one ``complex64`` reduction (count in
    the real lane, masked sum in the imaginary lane — one pass), and the
    scale sums (``Σ|f|^m``, the tolerance denominators) are computed
    only by the ``"anchor"`` variant, dispatched once to capture the
    references (and again after a re-tile).  ``"wide"`` is the shadow
    variant: anchor width plus per-watched-field packed
    ``Σ|state−truth|`` / ``Σ|state|`` rows.  A moment-2 sum is its own
    scale (``x² ≥ 0``), so m=2 scale rows are free.

    Per-device partials ride an ``(ndev,)`` scatter+psum per row — the
    violation's device attribution.  Grid geometry is read at TRACE
    time, so `igg.sharded`'s epoch-keyed re-trace keeps the probe valid
    across an elastic re-tile."""
    from jax.sharding import PartitionSpec

    from .parallel import psum_grid, sharded

    watch = tuple(watch)
    extra = tuple(extra)
    invariants = tuple(invariants)
    moms = _moment_map(invariants)
    vs_keys = [(f, m) for f in watch + extra for m in moms.get(f, ())]

    @sharded(out_specs=PartitionSpec())
    def probe(*arrays):
        import jax.numpy as jnp
        from jax import lax

        grid = shared.global_grid()   # trace-time: the live epoch
        n, ne = len(watch), len(extra)
        cur = dict(zip(watch + extra, arrays[:n + ne]))
        truth = (dict(zip(watch, arrays[n + ne:])) if kind == "wide"
                 else {})
        counts = []
        vals = {}
        for name in watch:
            a = cur[name]
            fm = moms.get(name, ())
            if not jnp.issubdtype(a.dtype, jnp.inexact):
                counts.append(psum_grid(jnp.zeros((), jnp.float32)))
                continue
            nf = (~jnp.isfinite(a)).astype(jnp.float32)
            if fm:
                # The packed pass: count + first moment in one reduce.
                z = jnp.sum(lax.complex(nf,
                                        _masked_moment(a, fm[0], grid)))
                counts.append(psum_grid(z.real))
                vals[(name, fm[0])] = z.imag
                for m in fm[1:]:
                    vals[(name, m)] = jnp.sum(_masked_moment(a, m, grid))
            else:
                counts.append(psum_grid(jnp.sum(nf)))
        for name in extra:
            a = cur[name]
            for m in moms.get(name, ()):
                vals[(name, m)] = jnp.sum(_masked_moment(a, m, grid))
        pieces = [jnp.stack(counts)] if counts else []
        for key in vs_keys:
            pieces.append(_rank_scatter(vals[key], grid))
        if kind in ("anchor", "wide"):
            for name, m in vs_keys:
                sc = (vals[(name, m)] if m == 2     # x² is its own |·|
                      else jnp.sum(_masked_moment(cur[name], 1, grid,
                                                  absolute=True)))
                pieces.append(_rank_scatter(sc, grid))
        if kind == "wide":
            for name in watch:
                a, t = cur[name], truth[name]
                d = jnp.abs(a.astype(jnp.float32)
                            - t.astype(jnp.float32))
                z = jnp.sum(lax.complex(
                    _masked_moment(d, 1, grid),
                    _masked_moment(a, 1, grid, absolute=True)))
                pieces.append(_rank_scatter(z.real, grid))
                pieces.append(_rank_scatter(z.imag, grid))
        return jnp.concatenate(pieces)

    return probe


# ---------------------------------------------------------------------------
# The checkpoint stamp context (read by igg.checkpoint at save time)
# ---------------------------------------------------------------------------

_STAMP_LOCK = threading.Lock()
_STAMP: Optional[List[dict]] = None


def _set_stamp_context(entries: Optional[List[dict]]) -> None:
    global _STAMP
    with _STAMP_LOCK:
        _STAMP = list(entries) if entries is not None else None


def stamp_entries() -> Optional[List[dict]]:
    """The active run's invariant stamp entries (None outside an
    integrity-enabled run): ``{name, fields, moment, kind, tol, ref,
    scale}`` dicts the checkpoint layer writes into the deep manifest —
    `ref`/`scale` are the run's reference values (None before the first
    probe anchors them, in which case deep verify checks content only).
    Thread-safe: the async checkpoint writer reads this from its own
    thread."""
    with _STAMP_LOCK:
        return [dict(e) for e in _STAMP] if _STAMP is not None else None


# ---------------------------------------------------------------------------
# The run monitor (owned by run_resilient)
# ---------------------------------------------------------------------------

class Monitor:
    """One run's integrity runtime: builds the fused probes, manages the
    shadow window snapshot, anchors/holds the invariant references,
    decodes fetched probe vectors into verdicts, and exports the stamp
    context for verified-generation rollback.  Pure host bookkeeping
    outside the probe programs — the hot loop's cost is the probe
    dispatch it already paid for the watchdog."""

    def __init__(self, cfg: IntegrityConfig, state: Dict,
                 watch: Sequence[str], watch_every: int,
                 steps_per_call: int, run: str = "resilient"):
        import jax.numpy as jnp

        grid = shared.global_grid()
        self.run = run
        # The FULL watch list, non-float fields included: the probe
        # emits a (zero) count row for them exactly like the plain
        # watchdog probe, so the caller's zip(watch, counts) labels stay
        # aligned (dropping them here would misattribute a NaN verdict
        # to the wrong field name).
        self.watch = list(watch)
        if cfg.invariants is not None:
            invs = tuple(cfg.invariants)
            missing = [i.name for i in invs
                       if not set(i.fields) <= set(state)]
            if missing:
                raise GridError(
                    f"integrity: invariant(s) {missing} name fields not in "
                    f"the run state {sorted(state)}.")
        else:
            invs = match_invariants(state, grid)
        self.invariants = invs
        for inv in invs:
            for f in inv.fields:
                if not jnp.issubdtype(getattr(state[f], "dtype",
                                              np.float64), jnp.inexact):
                    raise GridError(
                        f"integrity: invariant {inv.name!r} field {f!r} "
                        f"has non-floating dtype "
                        f"{getattr(state[f], 'dtype', '?')}.")
        self.tol = cfg.resolved_tol()
        self.shadow_tol = (float(cfg.shadow_tol)
                           if cfg.shadow_tol is not None else self.tol)
        self.check_every = cfg.resolved_check_every()
        self.deep_verify = cfg.resolved_deep()
        self.truth_step_fn = cfg.truth_step_fn
        self.watch_every = int(watch_every)
        self.steps_per_call = int(steps_per_call)
        # Invariant-only fields (declared but unwatched) still feed the
        # probe; the per-(field, moment) layout both probes and the host
        # decode share.
        self.extra = [f for inv in invs for f in inv.fields
                      if f not in self.watch]
        self.extra = list(dict.fromkeys(self.extra))
        self._moms = _moment_map(invs)
        self.vs_keys = [(f, m) for f in list(self.watch) + self.extra
                        for m in self._moms.get(f, ())]
        self._steady = _build_probe(self.watch, self.extra, invs, "steady")
        self._anchor = _build_probe(self.watch, self.extra, invs, "anchor")
        self._wide = (_build_probe(self.watch, self.extra, invs, "wide")
                      if self.check_every else None)
        self._snapshot: Optional[Dict] = None
        self._snapshot_step: Optional[int] = None
        self._shadow_off = False          # donation detected: refs die early
        # References: per-(field, moment) global value/scale sums + the
        # per-rank value partials for attribution; anchored at the first
        # clean fetch of an anchor-width probe, partials re-anchored
        # after a re-tile changes the device count.
        self._ref_vals: Optional[Dict[Tuple, float]] = None
        self._ref_scales: Optional[Dict[Tuple, float]] = None
        self._ref_partials: Optional[Dict[Tuple, np.ndarray]] = None
        self.checks = 0
        self.shadow_checks = 0
        self.violations = 0
        self._m_checks = _telemetry.counter("igg_integrity_checks_total",
                                            run=run)
        self._m_shadow = _telemetry.counter(
            "igg_integrity_shadow_checks_total", run=run)
        self._m_viol = _telemetry.counter(
            "igg_integrity_violations_total", run=run)
        _telemetry.emit(
            "integrity_config", run=run,
            invariants=[i.name for i in invs],
            check_every=self.check_every, tol=self.tol,
            deep_verify=self.deep_verify,
            shadow="truth_step" if cfg.truth_step_fn is not None
                   else ("re_execution" if self.check_every else "off"))
        self._push_stamp()

    # -- stamp context -----------------------------------------------------
    def _inv_ref(self, inv: Invariant):
        """(ref, scale) of one invariant from the per-(field, moment)
        anchors (None before the first anchor fetch)."""
        if self._ref_vals is None:
            return None, None
        ref = sum(self._ref_vals[(f, inv.moment)] for f in inv.fields)
        sca = sum(self._ref_scales[(f, inv.moment)] for f in inv.fields)
        return float(ref), float(sca)

    def _push_stamp(self) -> None:
        entries = []
        for inv in self.invariants:
            ref, sca = self._inv_ref(inv)
            entries.append({
                "name": inv.name, "fields": list(inv.fields),
                "moment": inv.moment, "kind": inv.kind,
                "tol": inv.tol if inv.tol is not None else self.tol,
                "ref": ref, "scale": sca})
        _set_stamp_context(entries)

    def close(self) -> None:
        _set_stamp_context(None)

    # -- shadow snapshot management ----------------------------------------
    def note_donation(self) -> None:
        """The step donates its buffers: window-entry snapshots would be
        invalidated before the re-dispatch — shadows degrade off with a
        structured event (the async-checkpoint donation contract)."""
        if self._shadow_off or not self.check_every:
            return
        self._shadow_off = True
        self._snapshot = self._snapshot_step = None
        _telemetry.emit("integrity_degraded", run=self.run,
                        why="step_fn donates its input buffers; shadow "
                            "re-execution checks disabled for this run "
                            "(invariant probes unaffected)")

    def arm_entry(self, state: Dict, steps_done: int) -> None:
        """Snapshot the run-entry (or post-resume) state so the FIRST
        watch window is shadow-checkable."""
        if self.check_every and not self._shadow_off:
            self._snapshot = dict(state)
            self._snapshot_step = steps_done

    def on_rollback(self, state: Optional[Dict] = None,
                    steps_done: Optional[int] = None) -> None:
        """A rollback moved `steps_done`: the pending snapshot no longer
        fronts a live window — it is RE-ARMED from the restored state,
        so the replay's first window is shadow-covered (a deterministic
        corruption must recur at the SAME probe step for the demotion
        rung to see its signature).  References are KEPT — the
        invariants are properties of the trajectory, and the
        rolled-back-to state is on it."""
        self._snapshot = self._snapshot_step = None
        if (state is not None and self.check_every
                and not self._shadow_off):
            self._snapshot = dict(state)
            self._snapshot_step = steps_done

    def on_retile(self, state: Optional[Dict] = None,
                  steps_done: Optional[int] = None) -> None:
        """An elastic re-tile changed the device count: per-rank
        reference partials are re-anchored at the next clean fetch (the
        global references survive — the field is the same field), and
        the shadow snapshot re-arms on the restored state."""
        self.on_rollback(state, steps_done)
        self._ref_partials = None

    def reset_reference(self) -> None:
        """Forget the anchored references entirely — called when the
        recurrence rung DEMOTES the serving tier: the demoted kernel's
        physics was wrong, so references anchored on its trajectory
        would flag the now-correct replay forever.  The next
        anchor-width probe re-anchors on the healthy tier's values."""
        self._ref_vals = self._ref_scales = self._ref_partials = None
        self._push_stamp()

    # -- dispatch ----------------------------------------------------------
    def dispatch(self, state: Dict, steps_done: int, step_fn):
        """The probe dispatch at a watch boundary: the wide (shadow)
        variant when the just-completed window was snapshotted, the
        anchor variant (scale rows included) while the references are
        unanchored, else the packed steady variant; arms the next
        window's snapshot on the check cadence.  Returns
        ``(device vector, tag)`` — the tag decodes the fetched vector
        (the layout depends on width and device count)."""
        grid = shared.global_grid()
        ndev = grid.nprocs
        fields = list(self.watch) + self.extra
        args = [state[n] for n in fields]
        if (self._snapshot is not None
                and self._snapshot_step == steps_done - self.watch_every):
            from . import degrade as _degrade

            truth_fn = self.truth_step_fn or step_fn
            t = self._snapshot
            # Diagnostic re-execution: the replay must not make the
            # truth rung look like the serving tier (the demotion rung
            # quarantines whatever served the MAIN loop's dispatches).
            with _degrade.diagnostic_dispatches():
                for _ in range(self.watch_every // self.steps_per_call):
                    t = truth_fn(t)
            vec = self._wide(*args, *[t[n] for n in self.watch])
            tag = ("wide", ndev)
            self._snapshot = self._snapshot_step = None
        elif self._ref_vals is None:
            vec, tag = self._anchor(*args), ("anchor", ndev)
        else:
            vec, tag = self._steady(*args), ("steady", ndev)
        if (self.check_every and not self._shadow_off
                and (steps_done // self.watch_every) % self.check_every == 0):
            self._snapshot = dict(state)
            self._snapshot_step = steps_done
        return vec, tag

    # -- decode ------------------------------------------------------------
    def _attribute(self, partials: np.ndarray, ref: Optional[np.ndarray]):
        """Suspect shard rank from per-device partials (the one whose
        partial moved most vs the reference, or holds the most diff);
        None on single-device grids — there is nothing to fence."""
        if partials.size <= 1:
            return None
        delta = np.abs(partials - ref) if (
            ref is not None and ref.shape == partials.shape) else np.abs(
            partials)
        return int(np.argmax(delta))

    def decode(self, host: np.ndarray, tag, step_p: int):
        """Split a fetched probe vector into ``(nonfinite_counts,
        violation-or-None)``.  The first clean anchor-width fetch
        anchors the references; a drifted invariant or an
        over-tolerance shadow diff returns the ``integrity_violation``
        payload (per-rank partials included for device attribution)."""
        kind, ndev = tag
        n_w = len(self.watch)
        counts = host[:n_w]
        off = n_w
        vals: Dict[Tuple, float] = {}
        parts: Dict[Tuple, np.ndarray] = {}
        for key in self.vs_keys:
            p = host[off:off + ndev].astype(np.float64)
            vals[key] = float(p.sum())
            parts[key] = p
            off += ndev
        scales: Optional[Dict[Tuple, float]] = None
        if kind in ("anchor", "wide"):
            scales = {}
            for key in self.vs_keys:
                scales[key] = float(
                    host[off:off + ndev].astype(np.float64).sum())
                off += ndev
        shadow: List[Tuple[float, float, np.ndarray]] = []
        if kind == "wide":
            for _ in self.watch:
                dp = host[off:off + ndev].astype(np.float64)
                sp = host[off + ndev:off + 2 * ndev].astype(np.float64)
                shadow.append((float(dp.sum()), float(sp.sum()), dp))
                off += 2 * ndev
        if counts.sum() != 0:
            # Non-finite state: the NaN watchdog's verdict outranks any
            # drift (the sums are poisoned too).
            return counts, None
        self.checks += 1
        self._m_checks.inc()
        anchored_now = False
        if self._ref_vals is None:
            if scales is None:
                return counts, None   # steady fetch before any anchor
            # Anchor.  The invariant drift of THIS window is trivially
            # zero against itself — but the shadow rows (when wide) are
            # reference-free and still checked below, so corruption
            # inside the very first window is not a blind spot of the
            # anchoring fetch.
            self._ref_vals = dict(vals)
            self._ref_scales = dict(scales)
            self._ref_partials = {k: p.copy() for k, p in parts.items()}
            self._push_stamp()
            anchored_now = True
        if (self._ref_partials is None
                or (self.vs_keys
                    and self._ref_partials[self.vs_keys[0]].size != ndev)):
            # Post-retile: the device count changed; re-anchor the
            # attribution baselines from this (clean-counted) fetch.
            self._ref_partials = {k: p.copy() for k, p in parts.items()}
        for inv in self.invariants if not anchored_now else ():
            value = sum(vals[(f, inv.moment)] for f in inv.fields)
            ref, ref_scale = self._inv_ref(inv)
            tol = inv.tol if inv.tol is not None else self.tol
            drift = value - ref
            bound = tol * max(ref_scale, _TINY)
            bad = (drift > bound if inv.kind == "bounded"
                   else abs(drift) > bound)
            if bad:
                partials = sum(parts[(f, inv.moment)] for f in inv.fields)
                ref_p = (sum(self._ref_partials[(f, inv.moment)]
                             for f in inv.fields)
                         if self._ref_partials is not None else None)
                rank = self._attribute(partials, ref_p)
                return counts, self._violation(
                    step_p, source="invariant", invariant=inv.name,
                    fields=list(inv.fields), value=value, ref=ref,
                    drift=float(drift), tol=tol,
                    scale=float(ref_scale), rank=rank,
                    partials=[float(x) for x in partials])
        if kind == "wide":
            self.shadow_checks += 1
            self._m_shadow.inc()
            for i, name in enumerate(self.watch):
                diff, scale, dp = shadow[i]
                bound = self.shadow_tol * max(scale, _TINY)
                if diff > bound:
                    rank = self._attribute(dp, None)
                    return counts, self._violation(
                        step_p, source="shadow", field=name,
                        diff=float(diff), scale=float(scale),
                        tol=self.shadow_tol, rank=rank,
                        partials=[float(x) for x in dp])
        return counts, None

    def _violation(self, step_p: int, **detail) -> dict:
        self.violations += 1
        self._m_viol.inc()
        grid = shared.global_grid()
        rank = detail.get("rank")
        if rank is not None and rank < grid.nprocs:
            try:
                detail["device"] = str(
                    grid.mesh.devices[grid.cart_coords(rank)])
            except (IndexError, ValueError):
                pass
        return detail


# ---------------------------------------------------------------------------
# Ensemble support: per-member references
# ---------------------------------------------------------------------------

class MemberRefs:
    """The per-member reference/verdict bookkeeping behind
    :func:`igg.run_ensemble`'s integrity rows — decode an
    ``(2·n_inv, M)`` block of per-member (value, scale) rows, anchor
    references per member at the first clean fetch, and name the
    members whose invariant drifted."""

    def __init__(self, invariants: Sequence[Invariant], members: int,
                 tol: float):
        self.invariants = tuple(invariants)
        self.members = members
        self.tol = tol
        self._ref: Optional[np.ndarray] = None       # (n_inv, M) values
        self._ref_scale: Optional[np.ndarray] = None

    def rows(self) -> int:
        return 2 * len(self.invariants)

    def check(self, block: np.ndarray, lanes: np.ndarray) -> dict:
        """`block` is the probe matrix's invariant rows ((2·n_inv, M):
        value, scale per invariant); returns `{member: [names]}` of the
        accountable lanes whose invariants drifted."""
        vals = block[0::2].astype(np.float64)
        scas = block[1::2].astype(np.float64)
        if self._ref is None:
            # First clean fetch anchors — per lane, so a quarantined
            # lane's NaN rows never block the healthy ones.
            self._ref, self._ref_scale = vals.copy(), scas.copy()
            return {}
        fill = ~np.isfinite(self._ref) & np.isfinite(vals)
        if fill.any():
            self._ref[fill] = vals[fill]
            self._ref_scale[fill] = scas[fill]
        bad: Dict[int, List[str]] = {}
        for i, inv in enumerate(self.invariants):
            tol = inv.tol if inv.tol is not None else self.tol
            drift = vals[i] - self._ref[i]
            bound = tol * np.maximum(self._ref_scale[i], _TINY)
            hit = (drift > bound if inv.kind == "bounded"
                   else np.abs(drift) > bound)
            # A non-finite value (or an unanchored reference) is the NaN
            # watchdog's case, not drift.
            hit &= np.isfinite(vals[i]) & np.isfinite(self._ref[i])
            for m in np.nonzero(hit & lanes)[0]:
                bad.setdefault(int(m), []).append(inv.name)
        return bad
