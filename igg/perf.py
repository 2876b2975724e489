"""igg.perf — performance observability: the persistent perf ledger,
live roofline / cost-model-drift gauges, and bench regression gating.

PR 7's :mod:`igg.telemetry` made *incidents* observable; this module does
the same for *performance*.  Three pieces, all flowing through the
telemetry bus (``perf_sample`` records in the flight recorder and every
attached session's JSONL sink, gauges in the metrics registry /
Prometheus exposition):

- **The perf ledger.**  Every measured dispatch becomes a sample keyed
  ``(family, tier, local_shape, dtype, dims, backend, device_kind)`` —
  the same signature axes the compiled-program cache keys on — with
  ms-per-step aggregates (best/mean/last/count, per-source counts).
  Samples arrive from three sources with zero hot-loop host syncs:

  1. *watchdog windows*: the run loops' :class:`igg.telemetry.StepStats`
     meter hands each window's measured ms/step to
     :func:`observe_window`, which attributes it to the kernel tier(s)
     that actually served dispatches inside that window
     (:func:`igg.degrade.active_records` stamps) — piggybacking entirely
     on the watchdog's existing async probe fetches;
  2. *verify-on-first-use*: after a fast tier passes its one-time
     numeric check, :mod:`igg.degrade` times one extra warm dispatch on
     scratch copies and records it (one sample per (tier, signature));
  3. *explicit calibration*: :func:`calibrate` slope-times a step (or a
     named model family's default step) ahead of time and records the
     result — the AOT path benchmarks and the future autotuner drive.

  The ledger persists to a **versioned JSON file**
  (``IGG_PERF_LEDGER``; format ``igg-perf-ledger-v1``) with
  merge-on-write atomic saves, rank-tagged on multi-controller runs,
  and is mergeable across processes/runs (``python -m igg.perf
  show|merge``).  :func:`best` / :func:`query` are the designed entry
  points for the ROADMAP-item-2 autotuner: an on-disk prior of measured
  per-(tier, shape, dtype, topology) timings.

- **Live gauges.**  Each recorded sample updates
  ``igg_achieved_gbps{family,tier}`` and ``igg_pct_hbm_peak`` from the
  family's analytic bytes/step accounting (the
  ``docs/stokes_roofline.md`` / ``pallas_sweep`` traffic models) and a
  per-device-kind HBM-peak table.  :func:`predict` registers the cost
  model's ``compute_s_per_step`` for a family
  (``benchmarks/cost_model_calibration.py`` feeds it); measured samples
  then maintain ``igg_cost_model_rel_error{family}`` and emit a
  ``cost_model_drift`` bus event when the relative error exceeds
  ``IGG_PERF_DRIFT_TOL``.

- **Regression gating.**  ``python -m igg.perf compare <baseline>
  <new> --tol X`` matches benchmark JSONL rows on (metric, config) AND
  the PR-7 provenance header — only rows with the same
  (backend, device_kind, smoke) are compared, so TPU evidence is never
  gated against CPU smoke — and exits nonzero on regressions beyond
  tolerance: a ``"pass": true`` contract row flipping false, a
  lower-is-better value (ms, %, seconds) growing past ``--tol``
  relative, a higher-is-better value (GB/s, steps/s, jobs/hour)
  shrinking past it, or a golden row missing entirely.
  ``benchmarks/run_all.py --compare`` and ``ci.sh`` enforce the
  committed CPU-smoke goldens under ``benchmarks/goldens/``.

Everything here is host-side bookkeeping: no device collectives, no
extra device→host synchronization (the zero-host-syncs sentinel in
``tests/test_telemetry.py`` runs with the ledger enabled).
"""

from __future__ import annotations

import json
import math
import os
import pathlib
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import _env
from . import shared
from . import telemetry as _telemetry
from .shared import GridError

__all__ = [
    "enabled", "ledger_path", "record", "query", "best", "predict",
    "calibrate", "observe_window", "window_state", "sample_context",
    "device_context", "bytes_per_step", "hbm_peak_gbps", "save", "load",
    "merge_ledgers", "reset", "invalidate", "forget_prediction",
    "compare_rows", "compare_paths", "register_family",
    "registered_families", "LEDGER_FORMAT",
]

LEDGER_FORMAT = "igg-perf-ledger-v1"

_lock = threading.RLock()
_LEDGER: Dict[Tuple, Dict] = {}          # key tuple -> aggregate entry
_PREDICTIONS: Dict[str, Dict] = {}       # family -> cost-model prediction
_DRIFT_EMITTED: set = set()              # (family, tier) drift events sent
# What this process has already contributed to each ledger FILE
# ({path: {key: {count, sum_ms, sources}}}): repeated saves to the same
# file must merge only the DELTA since the last save — re-merging the
# full in-memory ledger into a file that already holds its own earlier
# save would double-count every persisted sample.  load() credits a
# file's entries to its baseline for the same reason.
_PERSISTED: Dict[str, Dict[Tuple, Dict]] = {}
_last_save = 0.0
_atexit_registered = False


# ---------------------------------------------------------------------------
# Configuration
# ---------------------------------------------------------------------------

def enabled() -> bool:
    """The master switch: ``IGG_PERF=0`` disables all ledger recording
    (queries and the CLI still work on whatever was loaded)."""
    return _env.flag("IGG_PERF", True)


def ledger_path() -> Optional[pathlib.Path]:
    """The configured on-disk ledger (``IGG_PERF_LEDGER``), rank-tagged on
    multi-controller runs so concurrent processes never fight over one
    file (``ledger.json`` → ``ledger_r3.json`` on rank 3; the rank files
    merge with ``python -m igg.perf merge``).  None when unset — the
    ledger then lives in memory only."""
    raw = _env.text("IGG_PERF_LEDGER")
    if not raw:
        return None
    p = pathlib.Path(raw)
    rank = _telemetry._process()
    if rank:
        p = p.with_name(f"{p.stem}_r{rank}{p.suffix or '.json'}")
    return p


# ---------------------------------------------------------------------------
# Roofline accounting: analytic bytes/step + per-device-kind HBM peaks
# ---------------------------------------------------------------------------

# Full-field HBM accesses per step for the per-step tiers (mosaic / xla),
# the ideal-fusion traffic models of benchmarks/pallas_sweep.py and
# docs/stokes_roofline.md (logical bytes; tile-padding excluded — see the
# roofline doc for the padded v5e numbers):
#   diffusion3d: read T + Cp, write T                      -> 3 accesses
#   stokes3d:    read P,Vx,Vy,Vz,Rho, write P,Vx,Vy,Vz     -> 9 accesses
#   hm3d:        read H,M, write H,M                       -> 4 accesses
#   wave2d:      read P,Vx,Vy, write P,Vx,Vy               -> 6 accesses
_FAMILY_ACCESSES = {"diffusion3d": 3, "stokes3d": 9, "hm3d": 4, "wave2d": 6}

# Round 17: the hard-coded family table above became a REGISTRATION HOOK
# so spec-defined families (igg.stencil) get roofline gauges, drift
# detection, and heal-loop re-calibration without editing this module.
# `register_family(name, accesses=..., steps=...)` supplies the analytic
# accesses count (the stencil analyzer derives it from the read-set) and
# an optional `steps(dtype) -> (state_fn, args)` builder consulted by
# :func:`calibrate`; the four built-ins stay in the tables as the
# fallback, registry entries win.
_FAMILY_REGISTRY: Dict[str, Dict] = {}


def register_family(name: str, *, accesses: Optional[int] = None,
                    steps=None) -> None:
    """Register (or update) a model family with the perf layer:
    `accesses` feeds :func:`bytes_per_step`'s roofline model, `steps`
    (a `(dtype) -> (state_fn, args)` builder on the live grid) makes
    :func:`calibrate`'s named-family convenience — and with it the heal
    loop's drift→recalibrate action — work for the family.  Idempotent;
    `igg.stencil.compile` calls it for every compiled spec."""
    with _lock:
        _FAMILY_REGISTRY[str(name)] = {
            "accesses": int(accesses) if accesses is not None else None,
            "steps": steps,
        }


def registered_families() -> Dict[str, Dict]:
    """The registered-family table (name -> {accesses, steps}); the
    built-in families live in the static fallback tables, not here."""
    with _lock:
        return dict(_FAMILY_REGISTRY)

# Peak HBM bandwidth per chip, GB/s (published per-chip figures; matched
# by substring against the lowercased jax `device_kind`).  The K-step
# trapezoid tiers read/write once per K steps, so the per-step model
# does not apply to them (bytes_per_step returns None there).
_HBM_PEAK_TABLE: Sequence[Tuple[str, float]] = (
    ("v6e", 1640.0), ("v6 lite", 1640.0),
    ("v5p", 2765.0), ("v5e", 819.0), ("v5 lite", 819.0),
    ("v4", 1228.0), ("v3", 900.0), ("v2", 700.0),
)


def bytes_per_step(family: str, tier: Optional[str], local_shape,
                   dtype) -> Optional[int]:
    """Analytic HBM traffic of ONE step of `family`'s per-step tiers on a
    `local_shape` block of `dtype` — logical bytes, the ideal-fusion
    model.  None when no model applies (unknown family, a K-step
    trapezoid tier whose traffic is amortized over K, or no shape)."""
    reg = _FAMILY_REGISTRY.get(family)
    acc = (reg["accesses"] if reg and reg.get("accesses") is not None
           else _FAMILY_ACCESSES.get(family))
    if acc is None or not local_shape:
        return None
    if tier and ("trapezoid" in tier or tier.endswith(".chunk")):
        # Resident K-step chunk tiers read/write HBM once per K steps,
        # so the per-step model does not apply.
        return None
    try:
        itemsize = np.dtype(dtype).itemsize
    except TypeError:
        return None
    cells = 1
    for s in local_shape:
        cells *= int(s)
    return acc * cells * itemsize


def hbm_peak_gbps(device_kind: Optional[str]) -> Optional[float]:
    """Published peak HBM bandwidth (GB/s) for a jax `device_kind`, or
    None when unknown (CPU hosts have no meaningful HBM peak)."""
    if not device_kind:
        return None
    dk = str(device_kind).lower()
    if "tpu" not in dk:
        return None
    for pat, val in _HBM_PEAK_TABLE:
        if pat in dk:
            return val
    return None


# ---------------------------------------------------------------------------
# Sample context (key axes read from live arrays — metadata only, no fetch)
# ---------------------------------------------------------------------------

def device_context() -> Dict:
    """`{backend, device_kind}` of the default device — the environment
    half of the ledger key (the same fields the benchmark provenance
    header stamps, so bench rows and ledger entries are joinable)."""
    import jax

    dev = jax.devices()[0]
    return {"backend": jax.default_backend(),
            "device_kind": getattr(dev, "device_kind", dev.platform)}


def sample_context(array=None) -> Dict:
    """Ledger-key context from a live grid array: its per-device block
    shape (shard metadata — never a device fetch), dtype, the grid's
    `dims`, and the device context.  With `array=None` only the
    grid/device axes are filled."""
    ctx = dict(device_context())
    ctx["dims"] = (tuple(shared.global_grid().dims)
                   if shared.grid_is_initialized() else None)
    if array is not None:
        shards = getattr(array, "addressable_shards", None)
        if shards:
            ctx["local_shape"] = tuple(shards[0].data.shape)
        else:
            ctx["local_shape"] = tuple(getattr(array, "shape", ()))
        ctx["dtype"] = str(getattr(array, "dtype", type(array).__name__))
    return ctx


# ---------------------------------------------------------------------------
# The ledger
# ---------------------------------------------------------------------------

def _key(family, tier, local_shape, dtype, dims, backend, device_kind
         ) -> Tuple:
    return (str(family), str(tier),
            tuple(int(s) for s in (local_shape or ())),
            str(dtype),
            tuple(int(d) for d in dims) if dims else None,
            str(backend) if backend else None,
            str(device_kind) if device_kind else None)


def _key_str(k: Tuple) -> str:
    family, tier, shape, dtype, dims, backend, device_kind = k
    return "|".join([
        family, tier, "x".join(map(str, shape)) or "-", dtype,
        "x".join(map(str, dims)) if dims else "-",
        backend or "-", device_kind or "-"])


def _entry_key(e: Dict) -> Tuple:
    return _key(e["family"], e["tier"], e.get("local_shape") or (),
                e.get("dtype", "-"), e.get("dims"), e.get("backend"),
                e.get("device_kind"))


def record(family: str, tier: str, ms_per_step: float, *,
           local_shape=(), dtype="-", dims=None, backend=None,
           device_kind=None, source: str = "api",
           window_steps: Optional[int] = None) -> Optional[Dict]:
    """Record one measured sample into the ledger: update the keyed
    aggregates, refresh the roofline / cost-model gauges, emit a
    ``perf_sample`` bus record, and (throttled) autosave the on-disk
    ledger.  Pure host bookkeeping — no device work.  Returns the
    updated entry (a copy), or None when recording is disabled
    (``IGG_PERF=0``) or the sample is unusable (non-finite/non-positive
    ms)."""
    if not enabled():
        return None
    try:
        ms = float(ms_per_step)
    except (TypeError, ValueError):
        return None
    if not math.isfinite(ms) or ms <= 0:
        return None
    k = _key(family, tier, local_shape, dtype, dims, backend, device_kind)
    now = time.time()
    with _lock:
        e = _LEDGER.get(k)
        if e is None:
            e = _LEDGER[k] = {
                "family": k[0], "tier": k[1], "local_shape": list(k[2]),
                "dtype": k[3], "dims": list(k[4]) if k[4] else None,
                "backend": k[5], "device_kind": k[6],
                "count": 0, "sum_ms": 0.0, "best_ms": ms, "last_ms": ms,
                "sources": {}, "updated_wall": now,
            }
        e["count"] += 1
        e["sum_ms"] += ms
        e["best_ms"] = min(e["best_ms"], ms)
        e["last_ms"] = ms
        e["mean_ms"] = e["sum_ms"] / e["count"]
        e["sources"][source] = e["sources"].get(source, 0) + 1
        e["updated_wall"] = now
        snapshot = dict(e)

    payload = {"family": k[0], "tier": k[1], "ms_per_step": ms,
               "local_shape": list(k[2]), "dtype": k[3],
               "dims": list(k[4]) if k[4] else None, "backend": k[5],
               "device_kind": k[6], "source": source}
    if window_steps is not None:
        payload["window_steps"] = int(window_steps)

    # Roofline gauges: achieved GB/s from the analytic traffic model,
    # percent of the device kind's HBM peak when one is known.
    nbytes = bytes_per_step(k[0], k[1], k[2], k[3])
    if nbytes:
        gbps = nbytes / (ms * 1e-3) / 1e9
        payload["achieved_gbps"] = gbps
        _telemetry.gauge("igg_achieved_gbps", family=k[0],
                         tier=k[1]).set(gbps)
        peak = hbm_peak_gbps(k[6])
        if peak:
            pct = 100.0 * gbps / peak
            payload["pct_hbm_peak"] = pct
            _telemetry.gauge("igg_pct_hbm_peak", family=k[0],
                             tier=k[1]).set(pct)

    # Cost-model drift: measured beside the registered prediction.
    pred = _PREDICTIONS.get(k[0])
    if pred is not None:
        rel = (pred["s_per_step"] * 1e3 - ms) / ms
        payload["predicted_s_per_step"] = pred["s_per_step"]
        payload["cost_model_rel_error"] = rel
        _telemetry.gauge("igg_cost_model_rel_error", family=k[0]).set(rel)
        tol = _env.number("IGG_PERF_DRIFT_TOL", 0.5)
        if abs(rel) > tol:
            with _lock:
                fresh = (k[0], k[1]) not in _DRIFT_EMITTED
                _DRIFT_EMITTED.add((k[0], k[1]))
            if fresh:
                _telemetry.emit(
                    "cost_model_drift", family=k[0], tier=k[1],
                    rel_error=rel, tol=tol, measured_ms=ms,
                    predicted_s_per_step=pred["s_per_step"],
                    prediction_source=pred.get("source"))

    _telemetry.emit("perf_sample", **payload)
    _maybe_autosave()
    return snapshot


def predict(family: str, compute_s_per_step: float, *,
            source: str = "cost_model", **extra) -> None:
    """Register the cost model's predicted seconds/step for a family
    (``benchmarks/cost_model_calibration.py`` calls this with the AOT
    ``compute_s_per_step``).  Measured samples recorded for the family —
    now or later — maintain the ``igg_cost_model_rel_error{family}``
    gauge and fire a ``cost_model_drift`` bus event (once per
    (family, tier)) past ``IGG_PERF_DRIFT_TOL``."""
    pred = {"s_per_step": float(compute_s_per_step), "source": source,
            **extra}
    with _lock:
        _PREDICTIONS[family] = pred
    _telemetry.emit("cost_model_prediction", family=family,
                    compute_s_per_step=pred["s_per_step"], source=source)
    # A measurement may already exist: surface the drift now, not at the
    # next (possibly never) sample.
    e = best(family)
    if e is not None:
        rel = (pred["s_per_step"] * 1e3 - e["best_ms"]) / e["best_ms"]
        _telemetry.gauge("igg_cost_model_rel_error", family=family).set(rel)


def forget_prediction(family: str) -> None:
    """Unregister `family`'s cost-model prediction (the
    :func:`igg.heal.recalibrate` action drops the stale registration
    FIRST, so the fresh samples it records cannot re-fire
    ``cost_model_drift`` against the very prediction being replaced)."""
    with _lock:
        _PREDICTIONS.pop(family, None)


def query(family: Optional[str] = None, *, tier: Optional[str] = None,
          local_shape=None, dtype=None, dims=None, backend=None,
          device_kind=None) -> List[Dict]:
    """Entries matching every given filter (None = wildcard), best-first.
    Shapes/dims compare as tuples, so lists and tuples both match."""
    def norm(x):
        return tuple(x) if x is not None else None

    want_shape, want_dims = norm(local_shape), norm(dims)
    out = []
    with _lock:
        entries = [dict(e) for e in _LEDGER.values()]
    for e in entries:
        if family is not None and e["family"] != family:
            continue
        if tier is not None and e["tier"] != tier:
            continue
        if want_shape is not None and tuple(e["local_shape"]) != want_shape:
            continue
        if dtype is not None and e["dtype"] != str(dtype):
            continue
        if want_dims is not None and norm(e["dims"]) != want_dims:
            continue
        if backend is not None and e["backend"] != backend:
            continue
        if device_kind is not None and e["device_kind"] != device_kind:
            continue
        out.append(e)
    # Deterministic order with tie-breaking (round 16 — the autotuner's
    # prior must be stable when two tiers measure equal-best): best_ms
    # first, ties broken toward the better-evidenced entry (higher
    # sample count), then the freshest (updated_wall), then tier name.
    out.sort(key=lambda e: (e["best_ms"], -e["count"],
                            -e.get("updated_wall", 0.0), e["tier"]))
    return out


def best(family: str, local_shape=None, **filters) -> Optional[Dict]:
    """The fastest recorded entry for `family` under the given filters —
    the autotuner's entry point: ``best("diffusion3d", (130, 130, 66))``
    answers "which tier served this shape fastest, and how fast"."""
    matches = query(family, local_shape=local_shape, **filters)
    return matches[0] if matches else None


def reset() -> None:
    """Clear the in-memory ledger, predictions, and drift-event memory
    (the on-disk file is untouched; tests call this for isolation)."""
    global _last_save
    with _lock:
        _LEDGER.clear()
        _PREDICTIONS.clear()
        _DRIFT_EMITTED.clear()
        _PERSISTED.clear()
        _FAMILY_REGISTRY.clear()
        _last_save = 0.0


def invalidate(family: str, tier: Optional[str] = None) -> int:
    """Drop every in-memory ledger entry for `family` (optionally one
    `tier`) and re-arm the family's once-per-(family, tier)
    ``cost_model_drift`` events — the :mod:`igg.heal` re-calibration
    loop's first step: a drifted calibration must stop serving
    ``query()/best()`` answers BEFORE fresh samples replace it.  The
    entries are also dropped from the per-file persisted baselines, so a
    later :func:`save` merges the replacement samples into the on-disk
    ledger as new deltas (the file keeps the old aggregates as history —
    merge-on-write is append-only by design).  The family's TUNING-CACHE
    entries are evicted too (:func:`igg.autotune.invalidate` — a ledger
    a drift verdict just emptied must not keep serving the winner it
    once picked; round 16).  Emits one ``perf_invalidated`` bus record;
    returns the number of ledger entries dropped."""
    with _lock:
        keys = [k for k in _LEDGER
                if k[0] == family and (tier is None or k[1] == tier)]
        for k in keys:
            del _LEDGER[k]
        for base in _PERSISTED.values():
            for k in [b for b in base
                      if b[0] == family and (tier is None or b[1] == tier)]:
                del base[k]
        for dk in [d for d in _DRIFT_EMITTED if d[0] == family
                   and (tier is None or d[1] == tier)]:
            _DRIFT_EMITTED.discard(dk)
    try:
        from . import autotune as _autotune

        tune_evicted = _autotune.invalidate(family, tier=tier)
    except Exception:   # pragma: no cover - advisory path
        tune_evicted = 0
    _telemetry.emit("perf_invalidated", family=family, tier=tier,
                    entries=len(keys), tune_evicted=tune_evicted)
    return len(keys)


# ---------------------------------------------------------------------------
# Watchdog-window attribution (zero additional host syncs)
# ---------------------------------------------------------------------------

def window_state() -> Dict:
    """Opaque per-run attribution state for :func:`observe_window`:
    remembers the ladder-dispatch stamp so a window is only attributed
    to families that dispatched DURING it (a tier some unrelated earlier
    factory warmed is never credited with this run's step rate)."""
    from . import degrade

    return {"stamp": degrade.dispatch_stamp()}


def observe_window(run: str, ms_per_step: float, window_steps: int,
                   ctx: Optional[Dict], state: Dict) -> List[Dict]:
    """One watchdog window's measured rate, attributed to the serving
    tier(s): every `(family, tier)` whose ladder dispatch stamp advanced
    since the previous window gets a ledger sample (source
    ``"watchdog"``).  Called by :class:`igg.telemetry.StepStats` on the
    SAME host timestamps it already takes for ``step_stats`` records —
    the attribution reads only host-side ladder state, so the zero
    additional device→host syncs contract of the step-stats meter is
    preserved (sentinel-asserted in ``tests/test_telemetry.py``)."""
    if ctx is None or not enabled():
        return []
    from . import degrade

    prev = state.get("stamp", -1)
    recs = degrade.active_records()
    state["stamp"] = degrade.dispatch_stamp()
    out = []
    for family, tier, stamp in recs:
        if stamp <= prev:
            continue
        e = record(family, tier, ms_per_step, source="watchdog",
                   window_steps=window_steps,
                   local_shape=ctx.get("local_shape", ()),
                   dtype=ctx.get("dtype", "-"), dims=ctx.get("dims"),
                   backend=ctx.get("backend"),
                   device_kind=ctx.get("device_kind"))
        if e is not None:
            out.append(e)
    return out


# ---------------------------------------------------------------------------
# Explicit calibration (the AOT path)
# ---------------------------------------------------------------------------

def _default_family_step(family: str, dtype):
    """(state_fn, args) for a named model family's default step on the
    live grid — the convenience behind ``calibrate("diffusion3d")``.
    `state_fn` maps args to same-structured outputs (the
    `igg.time_steps` contract); pass-through coefficients ride along.
    Registered families (:func:`register_family` — spec-defined physics
    among them) resolve through their registered builder first."""
    reg = _FAMILY_REGISTRY.get(family)
    if reg is not None and reg.get("steps") is not None:
        return reg["steps"](dtype)
    if family == "diffusion3d":
        from .models import diffusion3d as m

        T, Cp = m.init_fields(m.Params(), dtype=dtype)
        step = m.make_step(m.Params(), donate=False)
        return (lambda T, Cp: (step(T, Cp), Cp)), (T, Cp)
    if family == "hm3d":
        from .models import hm3d as m

        fields = m.init_fields(m.Params(), dtype=dtype)
        step = m.make_step(m.Params(), donate=False)
        return (lambda *fs: step(*fs)), tuple(fields)
    if family == "stokes3d":
        from .models import stokes3d as m

        fields = m.init_fields(m.Params(), dtype=dtype)
        it = m.make_iteration(m.Params(), donate=False)
        # The iteration returns (P, Vx, Vy, Vz); Rho rides along (the
        # model run()'s own wrapper shape).
        return (lambda P, Vx, Vy, Vz, Rho:
                it(P, Vx, Vy, Vz, Rho) + (Rho,)), tuple(fields)
    if family == "wave2d":
        from .models import wave2d as m

        fields = m.init_fields(m.Params(), dtype=dtype)
        step = m.make_step(m.Params(), donate=False)
        return (lambda P, Vx, Vy: step(P, Vx, Vy)), tuple(fields)
    raise GridError(
        f"igg.perf.calibrate: unknown family {family!r} (built-ins: "
        f"diffusion3d, hm3d, stokes3d, wave2d; registered: "
        f"{sorted(_FAMILY_REGISTRY) or 'none'}; pass a step callable + "
        f"args for anything else, or register via "
        f"igg.perf.register_family).")


def calibrate(model, args=None, *, family: Optional[str] = None,
              tier: Optional[str] = None, nt: int = 8, warmup: int = 1,
              dtype=np.float32, source: str = "calibrate") -> float:
    """Slope-time a step ahead of serving traffic and record the result.

    `model` is either a step callable (then `args` is its argument tuple
    and `family` is required) or a model-family name
    (``"diffusion3d"`` / ``"stokes3d"`` / ``"hm3d"`` — the family's
    default step is built on the live grid).  The measurement is
    `igg.time_steps` slope timing (two batch sizes, nt and 3·nt —
    constant dispatch latency cancels); the serving `tier` is read from
    :func:`igg.degrade.active` after the timed dispatches unless given.
    Returns the measured seconds per dispatch (and records ms into the
    ledger unless ``IGG_PERF=0``)."""
    import igg

    shared.check_initialized()
    if isinstance(model, str):
        family = family or model
        step_fn, args = _default_family_step(model, dtype)
    else:
        if family is None:
            raise GridError("igg.perf.calibrate: family= is required when "
                            "passing a step callable.")
        step_fn = model
        if args is None:
            raise GridError("igg.perf.calibrate: args= (the step's "
                            "argument tuple) is required when passing a "
                            "step callable.")
    if nt < 1:
        raise GridError("igg.perf.calibrate: nt must be >= 1.")
    args = tuple(args) if isinstance(args, (tuple, list)) else (args,)
    _, sec = igg.time_steps(step_fn, args, n1=nt, n2=3 * nt, warmup=warmup)
    from . import degrade

    served = tier or degrade.active().get(family, f"{family}.xla")
    ctx = sample_context(args[0] if args else None)
    record(family, served, sec * 1e3, source=source,
           local_shape=ctx.get("local_shape", ()),
           dtype=ctx.get("dtype", "-"), dims=ctx.get("dims"),
           backend=ctx.get("backend"), device_kind=ctx.get("device_kind"))
    return sec


# ---------------------------------------------------------------------------
# Persistence: versioned JSON, merge-on-write, cross-run merge
# ---------------------------------------------------------------------------

def _merge_entry(into: Dict, e: Dict) -> None:
    into["count"] += e["count"]
    into["sum_ms"] += e["sum_ms"]
    into["best_ms"] = min(into["best_ms"], e["best_ms"])
    if e.get("updated_wall", 0) >= into.get("updated_wall", 0):
        into["last_ms"] = e["last_ms"]
        into["updated_wall"] = e.get("updated_wall", 0)
    into["mean_ms"] = into["sum_ms"] / max(1, into["count"])
    for s, n in e.get("sources", {}).items():
        into["sources"][s] = into["sources"].get(s, 0) + n


def merge_ledgers(entries_lists: Sequence[Sequence[Dict]]) -> Dict[Tuple,
                                                                   Dict]:
    """Merge entry lists (same-key aggregates combine: counts/sums add,
    best_ms min, last_ms from the newest `updated_wall`)."""
    merged: Dict[Tuple, Dict] = {}
    for entries in entries_lists:
        for e in entries:
            k = _entry_key(e)
            have = merged.get(k)
            if have is None:
                merged[k] = json.loads(json.dumps(e))   # deep copy
            else:
                _merge_entry(have, e)
    return merged


def _read_ledger_file(path) -> List[Dict]:
    path = pathlib.Path(path)
    try:
        doc = json.loads(path.read_text())
    except OSError as e:
        raise GridError(f"igg.perf: cannot read ledger {path}: {e}")
    except json.JSONDecodeError as e:
        raise GridError(f"igg.perf: {path} is not valid JSON ({e}).")
    if not isinstance(doc, dict) or doc.get("format") != LEDGER_FORMAT:
        raise GridError(
            f"igg.perf: {path} is not an {LEDGER_FORMAT} ledger "
            f"(format={doc.get('format') if isinstance(doc, dict) else '?'!r}).")
    return list(doc.get("entries", {}).values())


def _baseline_snapshot(e: Dict) -> Dict:
    return {"count": e["count"], "sum_ms": e["sum_ms"],
            "sources": dict(e.get("sources", {}))}


def _delta_entry(e: Dict, base: Optional[Dict]) -> Optional[Dict]:
    """`e` minus what was already persisted (`base`) — the only part a
    save may merge into a file that holds the earlier save.  None when
    nothing new happened for this key."""
    if base is None:
        return dict(e)
    d_count = e["count"] - base["count"]
    if d_count <= 0:
        return None
    d = dict(e)
    d["count"] = d_count
    d["sum_ms"] = e["sum_ms"] - base["sum_ms"]
    d["mean_ms"] = d["sum_ms"] / d_count
    d["sources"] = {s: n - base["sources"].get(s, 0)
                    for s, n in e.get("sources", {}).items()
                    if n - base["sources"].get(s, 0) > 0}
    return d


def save(path=None) -> Optional[pathlib.Path]:
    """Persist the in-memory ledger: read whatever is on disk, merge in
    this process's DELTA since its last save to that file (never the
    full ledger — the file already holds the earlier saves; see
    `_PERSISTED`), and atomically replace the file (tmp + rename) — so
    concurrent runs lose nothing and repeated saves never double-count.
    `path` defaults to the ``IGG_PERF_LEDGER`` rank path; with neither,
    a no-op returning None."""
    global _last_save
    target = pathlib.Path(path) if path is not None else ledger_path()
    if target is None:
        return None
    pkey = str(target.resolve())   # non-strict: path need not exist yet
    on_disk: List[Dict] = []
    disk_ok = False
    if target.exists():
        try:
            on_disk = _read_ledger_file(target)
            disk_ok = True
        except GridError:
            on_disk = []   # a corrupt ledger is replaced, not fatal
    with _lock:
        _last_save = time.monotonic()
        base = _PERSISTED.get(pkey, {}) if disk_ok else {}
        mine = []
        for k, e in _LEDGER.items():
            d = _delta_entry(e, base.get(k))
            if d is not None:
                mine.append(d)
        new_base = {k: _baseline_snapshot(e) for k, e in _LEDGER.items()}
    merged = merge_ledgers([on_disk, mine])
    doc = {"format": LEDGER_FORMAT, "saved_wall": time.time(),
           "process": _telemetry._process(),
           "entries": {_key_str(k): e for k, e in sorted(merged.items())}}
    try:
        target.parent.mkdir(parents=True, exist_ok=True)
        tmp = target.with_name(target.name + ".tmp")
        tmp.write_text(json.dumps(doc, indent=1, sort_keys=True))
        os.replace(tmp, target)
    except OSError:
        return None   # a full/readonly disk must never kill the run
    with _lock:
        # Committed: everything now in memory is also in the file (a
        # disk that was missing/corrupt started from an empty baseline).
        _PERSISTED[pkey] = new_base
    return target


def load(path=None, *, replace: bool = False) -> int:
    """Load a ledger file into memory (merging with what is there;
    ``replace=True`` clears first).  `path` defaults to the
    ``IGG_PERF_LEDGER`` rank path.  Returns the number of entries now in
    memory.  Raises :class:`GridError` on a missing/invalid/
    wrong-format file."""
    target = pathlib.Path(path) if path is not None else ledger_path()
    if target is None:
        raise GridError("igg.perf.load: no path given and IGG_PERF_LEDGER "
                        "is unset.")
    entries = _read_ledger_file(target)
    with _lock:
        pkey = str(target.resolve())
        if replace:
            # Memory is redefined as exactly this file's contents: every
            # other path's baseline is stale, and this path's baseline IS
            # the loaded set.
            _LEDGER.clear()
            _PERSISTED.clear()
            _PERSISTED[pkey] = {_entry_key(e): _baseline_snapshot(e)
                                for e in entries}
        else:
            # The loaded amounts came FROM this file: credit them to its
            # persisted baseline, or the next save would merge them back
            # in on top of themselves (double-counting).
            base = _PERSISTED.setdefault(pkey, {})
            for e in entries:
                k = _entry_key(e)
                have = base.get(k)
                if have is None:
                    base[k] = _baseline_snapshot(e)
                else:
                    have["count"] += e["count"]
                    have["sum_ms"] += e["sum_ms"]
                    for s, n in e.get("sources", {}).items():
                        have["sources"][s] = have["sources"].get(s, 0) + n
        merged = merge_ledgers([[dict(e) for e in _LEDGER.values()],
                                entries])
        _LEDGER.clear()
        _LEDGER.update(merged)
        return len(_LEDGER)


def _maybe_autosave() -> None:
    """Throttled background persistence: at most one save per
    ``IGG_PERF_SAVE_EVERY`` seconds (default 60), plus one at process
    exit — so a long run's ledger survives a crash without paying a
    file write per sample."""
    global _atexit_registered
    if ledger_path() is None:
        return
    if not _atexit_registered:
        import atexit

        with _lock:
            if not _atexit_registered:
                _atexit_registered = True
                atexit.register(lambda: save())
    every = _env.number("IGG_PERF_SAVE_EVERY", 60.0)
    if time.monotonic() - _last_save >= every:
        save()


# ---------------------------------------------------------------------------
# Regression gating: benchmark-row comparison
# ---------------------------------------------------------------------------

def _load_rows(path) -> List[Dict]:
    """Benchmark JSONL rows from a file or a directory of ``*.jsonl``
    (``*.failed.jsonl`` postmortem salvage excluded); unparsable lines
    are skipped — a gate must survive a truncated artifact."""
    p = pathlib.Path(path)
    if p.is_dir():
        files = sorted(f for f in p.glob("*.jsonl")
                       if not f.name.endswith(".failed.jsonl"))
    else:
        files = [p]
    if not files:
        raise GridError(f"igg.perf compare: no .jsonl files under {p}.")
    rows = []
    for f in files:
        try:
            text = f.read_text()
        except OSError as e:
            raise GridError(f"igg.perf compare: cannot read {f}: {e}")
        for line in text.splitlines():
            line = line.strip()
            if not line:
                continue
            try:
                row = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(row, dict) and "metric" in row:
                rows.append(row)
    return rows


def _row_key(r: Dict) -> Tuple[str, str]:
    return (str(r.get("metric")),
            json.dumps(r.get("config"), sort_keys=True, default=str))


def _row_prov(r: Dict) -> Tuple:
    """The provenance axes rows must share to be comparable: backend,
    device_kind, smoke flag (PR-7 header; rows written before it carry
    None — backfill-tolerant, they only match each other)."""
    prov = r.get("provenance") or {}
    return (prov.get("backend"), prov.get("device_kind"), r.get("smoke"))


def _direction(unit: Optional[str]) -> str:
    """'lower' (ms, %, seconds — smaller is better), 'higher' (GB/s,
    steps/s, jobs/hour, efficiency/overlap fractions — bigger is
    better), or 'abs' (relative-error columns — closer to zero is
    better)."""
    u = (unit or "").lower()
    if "relative error" in u or "rel_error" in u:
        return "abs"
    for tok in ("gb/s", "gbps", "/s", "/sec", "/hour", "/hr", "flop",
                "fraction", "efficiency", "speedup"):
        if tok in u:
            return "higher"
    return "lower"


def compare_rows(baseline: Sequence[Dict], new: Sequence[Dict], *,
                 tol: float = 0.1, allow_missing: bool = False,
                 gate_pass_values: bool = False) -> Dict:
    """Compare two benchmark row sets (the regression gate).

    Rows pair on (metric, canonical config) and are only compared when
    their provenance (backend, device_kind, smoke) matches — so a CPU
    smoke golden can never gate TPU evidence or vice versa.  Verdicts:

    - a row whose golden ``"pass"`` is true and new ``"pass"`` is false
      is ALWAYS a regression (contract rows carry their own tolerance —
      their values are informational unless `gate_pass_values`);
    - value rows regress when they move past `tol` RELATIVE in the bad
      direction for their unit (`_direction`);
    - golden rows with no new counterpart are `missing` — regressions
      unless `allow_missing` (golden rows whose provenance matches no
      new row at all are `out_of_scope`, skipped: a different host);
    - new-only rows are informational.

    Returns ``{regressions, improvements, ok, missing, out_of_scope,
    new_only, lines}`` — `lines` is the human-readable report."""
    base_by_key: Dict[Tuple, Dict] = {}
    for r in baseline:
        base_by_key[_row_key(r)] = r      # last row per key wins
    new_by_key: Dict[Tuple, Dict] = {}
    for r in new:
        new_by_key[_row_key(r)] = r
    new_provs = {_row_prov(r) for r in new}

    regressions, improvements, ok = [], [], []
    missing, out_of_scope = [], []
    lines: List[str] = []

    def fin(row, field="value"):
        v = row.get(field)
        return float(v) if isinstance(v, (int, float)) else None

    for key, b in sorted(base_by_key.items()):
        n = new_by_key.get(key)
        label = f"{key[0]} config={key[1]}"
        if n is None:
            if _row_prov(b) not in new_provs:
                out_of_scope.append(key)
                lines.append(f"SKIP (provenance out of scope) {label}")
            else:
                missing.append(key)
                lines.append(f"MISSING {label}")
            continue
        if _row_prov(b) != _row_prov(n):
            out_of_scope.append(key)
            lines.append(f"SKIP (provenance mismatch "
                         f"{_row_prov(b)} vs {_row_prov(n)}) {label}")
            continue
        verdicts = []
        if isinstance(b.get("pass"), bool):
            if b["pass"] and not n.get("pass"):
                verdicts.append(("regression",
                                 'contract "pass": true -> false'))
            gate_value = gate_pass_values
        else:
            gate_value = True
        bv, nv = fin(b), fin(n)
        if gate_value and bv is not None and nv is not None:
            d = _direction(b.get("unit"))
            if d == "abs":
                drift = abs(nv) - abs(bv)
                if drift > tol:
                    verdicts.append(("regression",
                                     f"|error| {abs(bv):.4g} -> "
                                     f"{abs(nv):.4g} (+{drift:.4g} > "
                                     f"tol {tol:g})"))
            else:
                scale = abs(bv)
                rel = ((nv - bv) / scale if scale
                       else (math.inf if nv > bv else 0.0))
                bad = rel if d == "lower" else -rel
                if bad > tol:
                    arrow = f"{bv:.6g} -> {nv:.6g}"
                    verdicts.append(("regression",
                                     f"value {arrow} ({bad:+.1%} beyond "
                                     f"tol {tol:.0%}, {d}-is-better "
                                     f"unit {b.get('unit')!r})"))
                elif -bad > tol:
                    verdicts.append(("improvement",
                                     f"value {bv:.6g} -> {nv:.6g}"))
        regs = [v for v in verdicts if v[0] == "regression"]
        if regs:
            regressions.append((key, [v[1] for v in regs]))
            for _, why in regs:
                lines.append(f"REGRESSION {label}: {why}")
        elif any(v[0] == "improvement" for v in verdicts):
            improvements.append(key)
            lines.append(f"IMPROVED {label}: "
                         f"{[v[1] for v in verdicts if v[0] == 'improvement'][0]}")
        else:
            ok.append(key)
            lines.append(f"OK {label}")

    new_only = sorted(set(new_by_key) - set(base_by_key))
    failing = len(regressions) + (0 if allow_missing else len(missing))
    lines.append(
        f"compare: {len(ok) + len(improvements) + len(regressions)} "
        f"matched ({len(regressions)} regression(s), "
        f"{len(improvements)} improved), {len(missing)} missing"
        f"{' (allowed)' if allow_missing and missing else ''}, "
        f"{len(out_of_scope)} out-of-scope, {len(new_only)} new-only")
    return {"regressions": regressions, "improvements": improvements,
            "ok": ok, "missing": missing, "out_of_scope": out_of_scope,
            "new_only": new_only, "lines": lines,
            "failed": failing > 0}


def compare_paths(baseline, new, *, tol: float = 0.1,
                  allow_missing: bool = False,
                  gate_pass_values: bool = False) -> Dict:
    """:func:`compare_rows` over files/directories of benchmark JSONL."""
    return compare_rows(_load_rows(baseline), _load_rows(new), tol=tol,
                        allow_missing=allow_missing,
                        gate_pass_values=gate_pass_values)


# ---------------------------------------------------------------------------
# CLI: python -m igg.perf show|merge|compare
# ---------------------------------------------------------------------------

def _format_entries(entries: Sequence[Dict]) -> str:
    import io

    out = io.StringIO()
    header = (f"{'family':<12} {'tier':<24} {'local_shape':<16} "
              f"{'dtype':<9} {'dims':<8} {'backend':<7} "
              f"{'best_ms':>10} {'mean_ms':>10} {'n':>5}  sources")
    out.write(header + "\n")
    for e in sorted(entries, key=lambda e: (e["family"], e["best_ms"])):
        shape = "x".join(map(str, e.get("local_shape") or [])) or "-"
        dims = ("x".join(map(str, e["dims"])) if e.get("dims") else "-")
        srcs = ",".join(f"{s}:{n}"
                        for s, n in sorted(e.get("sources", {}).items()))
        out.write(f"{e['family']:<12} {e['tier']:<24} {shape:<16} "
                  f"{e['dtype']:<9} {dims:<8} {e.get('backend') or '-':<7} "
                  f"{e['best_ms']:>10.4f} {e.get('mean_ms', 0):>10.4f} "
                  f"{e['count']:>5}  {srcs}\n")
    return out.getvalue()


def _main(argv: Sequence[str]) -> int:
    import sys

    usage = (
        "usage: python -m igg.perf show [<ledger.json>] [--family F]\n"
        "           [--tier T]\n"
        "       python -m igg.perf tune [<cache.json>] [--family F]\n"
        "           [--ledger <ledger.json>]\n"
        "       python -m igg.perf merge <out.json> <ledger.json> [...]\n"
        "       python -m igg.perf compare <baseline> <new> [--tol X]\n"
        "           [--allow-missing] [--gate-pass-values]\n"
        "  show     print a ledger (default: $IGG_PERF_LEDGER) as a table,\n"
        "           optionally filtered to one family and/or tier (the\n"
        "           per-signature view the tuning work reads)\n"
        "  tune     print the autotuner's tuning cache (default:\n"
        "           $IGG_TUNE_CACHE) next to the ledger prior each winner\n"
        "           came from\n"
        "  merge    merge ledger files into one (aggregates combine)\n"
        "  compare  regression-gate benchmark JSONL rows/dirs; exit 1 on\n"
        "           regressions (or missing golden rows)")
    argv = list(argv)
    if not argv:
        print(usage, file=sys.stderr)
        return 2
    cmd, rest = argv[0], argv[1:]

    def take_flag(name):
        if name in rest:
            i = rest.index(name)
            val = rest[i + 1]
            del rest[i:i + 2]
            return val
        return None

    try:
        if cmd == "show":
            fam = take_flag("--family")
            tier_f = take_flag("--tier")
            path = rest[0] if rest else ledger_path()
            if path is None:
                print("igg.perf show: no ledger given and IGG_PERF_LEDGER "
                      "is unset.", file=sys.stderr)
                return 2
            entries = _read_ledger_file(path)
            if fam is not None:
                entries = [e for e in entries if e["family"] == fam]
            if tier_f is not None:
                entries = [e for e in entries if e["tier"] == tier_f]
            print(f"# {path} ({len(entries)} entr"
                  f"{'y' if len(entries) == 1 else 'ies'})")
            sys.stdout.write(_format_entries(entries))
            return 0
        if cmd == "tune":
            from . import autotune

            fam = take_flag("--family")
            ledger_arg = take_flag("--ledger")
            path = rest[0] if rest else autotune.cache_path()
            if path is None:
                print("igg.perf tune: no cache given and IGG_TUNE_CACHE "
                      "is unset.", file=sys.stderr)
                return 2
            entries = autotune._read_cache_file(path)
            if fam is not None:
                entries = [e for e in entries if e["family"] == fam]
            lpath = ledger_arg or ledger_path()
            led = []
            if lpath is not None and pathlib.Path(lpath).exists():
                led = _read_ledger_file(lpath)
            print(f"# {path} ({len(entries)} winner"
                  f"{'' if len(entries) == 1 else 's'})"
                  + (f" vs prior {lpath}" if led else " (no ledger prior)"))
            header = (f"{'family':<12} {'local_shape':<14} {'tier':<22} "
                      f"{'K':>3} {'bx':>3} {'vmem':>5} "
                      f"{'ms':>9}  prior (ledger best)")
            print(header)
            for e in sorted(entries, key=lambda e: (e["family"],
                                                    str(e["local_shape"]))):
                shape = "x".join(map(str, e.get("local_shape") or [])) or "-"
                prior = [l for l in led
                         if l["family"] == e["family"]
                         and tuple(l.get("local_shape") or ())
                         == tuple(e.get("local_shape") or ())]
                prior.sort(key=lambda l: l["best_ms"])
                ptxt = (f"{prior[0]['tier']} @ {prior[0]['best_ms']:.4f} ms"
                        if prior else "-")
                print(f"{e['family']:<12} {shape:<14} "
                      f"{e.get('tier') or '-':<22} "
                      f"{e.get('K') or '-':>3} {e.get('bx') or '-':>3} "
                      f"{str(e.get('vmem_mb') or '-'):>5} "
                      f"{(e.get('ms') or 0):>9.4f}  {ptxt}")
            return 0
        if cmd == "merge":
            if len(rest) < 2:
                print(usage, file=sys.stderr)
                return 2
            out, inputs = rest[0], rest[1:]
            merged = merge_ledgers([_read_ledger_file(p) for p in inputs])
            doc = {"format": LEDGER_FORMAT, "saved_wall": time.time(),
                   "process": -1,
                   "entries": {_key_str(k): e
                               for k, e in sorted(merged.items())}}
            outp = pathlib.Path(out)
            outp.parent.mkdir(parents=True, exist_ok=True)
            outp.write_text(json.dumps(doc, indent=1, sort_keys=True))
            print(f"merged {len(merged)} entr"
                  f"{'y' if len(merged) == 1 else 'ies'} from "
                  f"{len(inputs)} ledger(s) -> {out}", file=sys.stderr)
            return 0
        if cmd == "compare":
            tol, allow_missing, gate_pass = 0.1, False, False
            if "--tol" in rest:
                i = rest.index("--tol")
                tol = float(rest[i + 1])
                del rest[i:i + 2]
            if "--allow-missing" in rest:
                allow_missing = True
                rest.remove("--allow-missing")
            if "--gate-pass-values" in rest:
                gate_pass = True
                rest.remove("--gate-pass-values")
            if len(rest) != 2:
                print(usage, file=sys.stderr)
                return 2
            rep = compare_paths(rest[0], rest[1], tol=tol,
                                allow_missing=allow_missing,
                                gate_pass_values=gate_pass)
            for line in rep["lines"]:
                print(line)
            return 1 if rep["failed"] else 0
    except (GridError, ValueError, IndexError) as e:
        print(f"igg.perf {cmd}: {e}", file=sys.stderr)
        return 2
    print(usage, file=sys.stderr)
    return 2


if __name__ == "__main__":   # python -m igg.perf ...
    import sys

    sys.exit(_main(sys.argv[1:]))
