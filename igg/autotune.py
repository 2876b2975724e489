"""igg.autotune — ledger-driven dispatch autotuning with a persistent
tuning cache.

PR 8 built the perf ledger as an autotuner's prior on purpose —
`igg.perf.query()/best()` answer "fastest known (tier, config) for this
(family, shape, dtype, dims, device_kind)" — yet the dispatch parameters
stayed hand-derived constants (K=8 at 128^3, fixed slab heights, a fixed
32→110 MB VMEM budget), and the stencil-tuning literature puts auto-tuned
parameters at 1.5-2x over hand-picked ones (PAPERS: 2406.08923,
2309.04671).  This module closes the loop:

- **The search** (:func:`search`): per compiled-cache signature
  `(family, local_shape, dtype, dims, backend, device_kind)` — the same
  axes the perf ledger keys on — candidate configs over
  `(tier, K, bx, vmem budget)` are measured with warm timed dispatches
  on scratch copies of family-default fields (`igg.time_steps` slope
  timing, donation-safe).  The ledger's :func:`igg.perf.best` is the
  PRIOR: its tier's candidates are measured first, and a candidate whose
  first warm sample exceeds ``IGG_TUNE_CUTOFF`` x the best-so-far is cut
  off without paying the full slope measurement.  Every sample lands in
  the perf ledger (source ``"autotune"``), so the search itself enriches
  the prior.

- **The tuning cache**: winners persist in a versioned JSON file
  (``IGG_TUNE_CACHE``; format ``igg-tune-cache-v1``), keyed like the
  compiled-program cache, with atomic merge-on-write saves (tmp +
  rename, newest ``updated_wall`` wins per key — the perf-ledger
  convention) and rank-tagging on multi-controller runs.  A second
  process pointing at the same cache serves the winner with ZERO search
  dispatches (:func:`search_dispatches` counts them for the contract
  test).

- **The application** (:func:`applied`): the model factories
  (`make_multi_step` / `make_iteration` / `make_step`) accept
  ``tune="auto"/True/False`` (default: the ``IGG_TUNE`` knob).  "auto"
  consults the cache and applies a hit's (tier pin, K, bx, vmem budget)
  wherever the caller left the defaults — a pure host-side dict lookup
  at FACTORY time, zero hot-loop cost (the PR-7 zero-host-syncs sentinel
  runs with tuning enabled); True additionally runs the search on a
  cache miss; False ignores the cache.  Explicit caller arguments always
  win over a cached winner.

- **Staleness** (:func:`invalidate`): :func:`igg.perf.invalidate` — the
  `igg.heal` re-calibration loop's first step on ``cost_model_drift`` —
  also evicts the family's tuning-cache entries (memory AND disk), so a
  drifted machine re-tunes instead of serving a stale winner.  The
  eviction emits a ``tune_invalidated`` bus record.

The VMEM-budget axis rides the shared budget authority
(`igg.ops._vmem.set_cap_override`): the search sweeps the cap for
kernels that consult `vmem_limit`/`chunk_budget`, and an applied winner
re-installs its cap process-wide (budgets are a per-chip property, not a
per-family one).
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
from . import telemetry as _telemetry
from .shared import GridError

__all__ = ["TUNE_FORMAT", "applied", "cache_path", "candidates_for", "get",
           "invalidate", "load", "record_winner", "register_family",
           "registered_families", "reset", "resolve", "save", "search",
           "search_dispatches"]

TUNE_FORMAT = "igg-tune-cache-v1"

_env.register("IGG_TUNE",
              "autotune default: 0 off, 1 search-on-miss, auto (default) "
              "cached winners only")
_env.register("IGG_TUNE_CACHE",
              "path of the on-disk tuning-cache JSON (unset: in-memory "
              "only; rank-tagged on multi-controller runs)")
_env.register("IGG_TUNE_NT",
              "slope-timing batch size per tuning candidate (default 2)")
_env.register("IGG_TUNE_CUTOFF",
              "early-cutoff factor: a candidate whose first warm sample "
              "exceeds this multiple of the best-so-far skips the full "
              "measurement (default 2.0)")

_lock = threading.RLock()
_CACHE: Dict[Tuple, Dict] = {}
_LOADED: set = set()           # cache files already lazily loaded
_SEARCH_DISPATCHES = 0         # timed search dispatches this process

# Round 17: the search's hard-coded family tables (candidates_for /
# _build_candidate) became a registration hook so spec-defined families
# (igg.stencil) are searchable without editing this module.  An entry
# supplies `candidates(grid, *, n_inner, interpret) -> [cand dicts]` and
# `build(cand, *, n_inner, params, interpret) -> (state_fn, args)`; the
# four built-ins stay in the static dispatch as the fallback.
_FAMILY_REGISTRY: Dict[str, Dict] = {}


def register_family(name: str, *, candidates, build) -> None:
    """Register a family's autotune providers (idempotent;
    `igg.stencil.compile` calls it for every compiled spec)."""
    with _lock:
        _FAMILY_REGISTRY[str(name)] = {"candidates": candidates,
                                       "build": build}


def registered_families() -> Dict[str, Dict]:
    with _lock:
        return dict(_FAMILY_REGISTRY)


# ---------------------------------------------------------------------------
# Configuration / keys
# ---------------------------------------------------------------------------

def resolve(tune):
    """The factories' ``tune=`` contract: None defers to ``IGG_TUNE``
    ("0" -> False, "1" -> True, unset/"auto" -> "auto"); otherwise must
    be False, True, or "auto"."""
    if tune is None:
        raw = (_env.text("IGG_TUNE") or "auto").strip().lower()
        if raw in ("0", "false", "off", "no"):
            return False
        if raw in ("1", "true", "on", "yes"):
            return True
        if raw == "auto":
            return "auto"
        raise GridError(f"IGG_TUNE={raw!r}: expected 0, 1, or auto.")
    if tune in (False, True, "auto"):
        return tune
    raise GridError(f"tune={tune!r}: expected None, False, True, or "
                    f"'auto'.")


def cache_path() -> Optional[pathlib.Path]:
    """The configured on-disk tuning cache (``IGG_TUNE_CACHE``),
    rank-tagged on multi-controller runs (the perf-ledger convention).
    None when unset — the cache then lives in memory only."""
    raw = _env.text("IGG_TUNE_CACHE")
    if not raw:
        return None
    p = pathlib.Path(raw)
    rank = _telemetry._process()
    if rank:
        p = p.with_name(f"{p.stem}_r{rank}{p.suffix or '.json'}")
    return p


def search_dispatches() -> int:
    """Timed search dispatches performed by this process — the
    cache-round-trip contract's counter (a second process serving a
    cached winner must keep it at zero)."""
    return _SEARCH_DISPATCHES


def _key(family, local_shape, dtype, dims, backend, device_kind) -> Tuple:
    return (str(family),
            tuple(int(s) for s in (local_shape or ())),
            str(dtype),
            tuple(int(d) for d in dims) if dims else None,
            str(backend) if backend else None,
            str(device_kind) if device_kind else None)


def _key_str(k: Tuple) -> str:
    family, shape, dtype, dims, backend, device_kind = k
    return "|".join([
        family, "x".join(map(str, shape)) or "-", dtype,
        "x".join(map(str, dims)) if dims else "-",
        backend or "-", device_kind or "-"])


def _entry_key(e: Dict) -> Tuple:
    return _key(e["family"], e.get("local_shape") or (),
                e.get("dtype", "float32"), e.get("dims"),
                e.get("backend"), e.get("device_kind"))


def _context(family: str, local_shape=None) -> Dict:
    """Signature axes from the live grid/device — the compiled-cache key
    minus the tier."""
    from . import perf, shared

    ctx = perf.device_context()
    ctx["dims"] = (tuple(shared.global_grid().dims)
                   if shared.grid_is_initialized() else None)
    if local_shape is None and shared.grid_is_initialized():
        grid = shared.global_grid()
        local_shape = (tuple(grid.nxyz[:2]) if family == "wave2d"
                       else tuple(grid.nxyz))
    ctx["local_shape"] = tuple(local_shape or ())
    return ctx


# ---------------------------------------------------------------------------
# The cache
# ---------------------------------------------------------------------------

def _lazy_load() -> None:
    """Merge the configured cache file into memory, once per path (the
    second process's zero-search read path)."""
    target = cache_path()
    if target is None:
        return
    pkey = str(target)
    with _lock:
        if pkey in _LOADED:
            return
        _LOADED.add(pkey)
    if target.exists():
        try:
            load(target)
        except GridError:
            pass   # a corrupt cache is re-tuned, never fatal


def get(family: str, local_shape=None, dtype="float32") -> Optional[Dict]:
    """The cached winner for this signature on the live grid/device, or
    None.  Host-side dict lookup (plus a one-time lazy file load) — no
    device work."""
    _lazy_load()
    ctx = _context(family, local_shape)
    k = _key(family, ctx["local_shape"], dtype, ctx["dims"],
             ctx["backend"], ctx["device_kind"])
    with _lock:
        e = _CACHE.get(k)
        return dict(e) if e else None


def record_winner(family: str, winner: Dict, *, local_shape=None,
                  dtype="float32", source: str = "search",
                  persist: bool = True) -> Dict:
    """Install a winner for this signature (and persist it when a cache
    path is configured)."""
    ctx = _context(family, local_shape)
    k = _key(family, ctx["local_shape"], dtype, ctx["dims"],
             ctx["backend"], ctx["device_kind"])
    e = {"family": family, "local_shape": list(k[1]), "dtype": k[2],
         "dims": list(k[3]) if k[3] else None, "backend": k[4],
         "device_kind": k[5],
         "tier": winner.get("tier"), "K": winner.get("K"),
         "bx": winner.get("bx"), "vmem_mb": winner.get("vmem_mb"),
         "overlap": bool(winner.get("overlap", False)),
         "ms": winner.get("ms"), "source": source,
         "updated_wall": time.time()}
    with _lock:
        _CACHE[k] = e
    _telemetry.emit("autotune_winner", **{kk: vv for kk, vv in e.items()
                                          if kk != "updated_wall"})
    if persist:
        save()
    return dict(e)


def reset() -> None:
    """Clear the in-memory cache and the lazy-load/search-count state
    (the on-disk file is untouched; tests call this for isolation)."""
    global _SEARCH_DISPATCHES
    with _lock:
        _CACHE.clear()
        _LOADED.clear()
        _FAMILY_REGISTRY.clear()
        _SEARCH_DISPATCHES = 0


def invalidate(family: str, tier: Optional[str] = None) -> int:
    """Evict `family`'s tuning-cache entries (optionally only winners
    serving `tier`) from memory AND the on-disk cache — the staleness
    half of the heal loop: :func:`igg.perf.invalidate` calls this, so a
    ``cost_model_drift``-driven invalidation re-tunes instead of serving
    a stale winner.  Returns the number of entries evicted."""
    with _lock:
        keys = [k for k, e in _CACHE.items()
                if k[0] == family and (tier is None or e.get("tier") == tier)]
        for k in keys:
            del _CACHE[k]
    n = len(keys)
    # Durable eviction: merge-on-write would resurrect the entry from
    # disk at the next save, so the file is rewritten without it.
    target = cache_path()
    if target is not None and target.exists():
        try:
            doc = json.loads(target.read_text())
            entries = doc.get("entries", {}) if isinstance(doc, dict) else {}
            kept = {ks: e for ks, e in entries.items()
                    if not (isinstance(e, dict) and e.get("family") == family
                            and (tier is None or e.get("tier") == tier))}
            n = max(n, len(entries) - len(kept))
            doc = {"format": TUNE_FORMAT, "saved_wall": time.time(),
                   "process": _telemetry._process(), "entries": kept}
            tmp = target.with_name(target.name + ".tmp")
            tmp.write_text(json.dumps(doc, indent=1, sort_keys=True))
            os.replace(tmp, target)
        except (OSError, json.JSONDecodeError):
            pass
    if n:
        _telemetry.emit("tune_invalidated", family=family, tier=tier,
                        entries=n)
    return n


def _read_cache_file(path) -> List[Dict]:
    path = pathlib.Path(path)
    try:
        doc = json.loads(path.read_text())
    except OSError as e:
        raise GridError(f"igg.autotune: cannot read cache {path}: {e}")
    except json.JSONDecodeError as e:
        raise GridError(f"igg.autotune: {path} is not valid JSON ({e}).")
    if not isinstance(doc, dict) or doc.get("format") != TUNE_FORMAT:
        raise GridError(
            f"igg.autotune: {path} is not an {TUNE_FORMAT} cache "
            f"(format="
            f"{doc.get('format') if isinstance(doc, dict) else '?'!r}).")
    return [e for e in doc.get("entries", {}).values()
            if isinstance(e, dict)]


def save(path=None) -> Optional[pathlib.Path]:
    """Persist the in-memory cache: read whatever is on disk, merge
    (newest ``updated_wall`` wins per key), atomically replace (tmp +
    rename) — concurrent runs lose nothing.  `path` defaults to the
    ``IGG_TUNE_CACHE`` rank path; with neither, a no-op returning
    None."""
    target = pathlib.Path(path) if path is not None else cache_path()
    if target is None:
        return None
    on_disk: List[Dict] = []
    if target.exists():
        try:
            on_disk = _read_cache_file(target)
        except GridError:
            on_disk = []   # a corrupt cache is replaced, not fatal
    merged: Dict[Tuple, Dict] = {}
    for e in on_disk:
        merged[_entry_key(e)] = e
    with _lock:
        for k, e in _CACHE.items():
            have = merged.get(k)
            if (have is None or e.get("updated_wall", 0)
                    >= have.get("updated_wall", 0)):
                merged[k] = dict(e)
    doc = {"format": TUNE_FORMAT, "saved_wall": time.time(),
           "process": _telemetry._process(),
           "entries": {_key_str(k): e for k, e in sorted(merged.items())}}
    try:
        target.parent.mkdir(parents=True, exist_ok=True)
        tmp = target.with_name(target.name + ".tmp")
        tmp.write_text(json.dumps(doc, indent=1, sort_keys=True))
        os.replace(tmp, target)
    except OSError:
        return None   # a full/readonly disk must never kill the run
    return target


def load(path=None, *, replace: bool = False) -> int:
    """Load a cache file into memory (merging, newest wins;
    ``replace=True`` clears first).  Returns the number of entries now
    in memory."""
    target = pathlib.Path(path) if path is not None else cache_path()
    if target is None:
        raise GridError("igg.autotune.load: no path given and "
                        "IGG_TUNE_CACHE is unset.")
    entries = _read_cache_file(target)
    with _lock:
        if replace:
            _CACHE.clear()
        for e in entries:
            k = _entry_key(e)
            have = _CACHE.get(k)
            if (have is None or e.get("updated_wall", 0)
                    >= have.get("updated_wall", 0)):
                _CACHE[k] = e
        return len(_CACHE)


# ---------------------------------------------------------------------------
# The application (factory-time, zero hot-loop cost)
# ---------------------------------------------------------------------------

def applied(family: str, tune, *, n_inner: int = 8, params=None,
            interpret: bool = False) -> Optional[Dict]:
    """The factories' entry point: resolve the ``tune=`` knob, look up
    the cached winner for this signature, search on miss when
    ``tune=True``, install the winner's VMEM cap, and return the winner
    (None when tuning is off, the grid is uninitialized, or there is no
    winner).  Pure host work at factory-build time.

    The VMEM-cap override is process-global (a chip property), so this
    call NORMALIZES it for the factory being built: a winner carrying a
    cap installs it, and every other outcome — a miss, a vmem-less
    winner, or an explicitly-untuned factory (``tune=False``) — CLEARS
    it back to the hand-derived default, so one family's tuned cap can
    never silently re-budget another family's admission."""
    from . import shared
    from .ops import _vmem

    mode = resolve(tune)
    if mode is False:
        _vmem.set_cap_override(None)
        return None
    if not shared.grid_is_initialized():
        return None
    w = get(family)
    if w is None and mode is True:
        w = search(family, n_inner=n_inner, params=params,
                   interpret=interpret)
    _vmem.set_cap_override(int(w["vmem_mb"]) * 1024 * 1024
                           if w and w.get("vmem_mb") else None)
    return w


# ---------------------------------------------------------------------------
# The search
# ---------------------------------------------------------------------------

# (hide_communication read radius, decomposition rank) per built-in —
# the admission geometry of each family's overlapped XLA candidate.
_OVERLAP_GEOMETRY = {"diffusion3d": (1, 3), "stokes3d": (2, 3),
                     "hm3d": (1, 3), "wave2d": (2, 2)}


def candidates_for(family: str, *, n_inner: int = 8,
                   interpret: bool = False) -> List[Dict]:
    """The (tier, K, bx, vmem) candidate set admissible for `family` on
    the live grid: the truth rung, the per-step fused tier (with a bx
    sweep for diffusion and a VMEM-cap sweep on compiled TPU mode), and
    every admissible chunk depth of the family's K-step tier.  Candidate
    dicts carry the factory kwargs the search applies."""
    from . import perf, shared

    grid = shared.global_grid()
    reg = _FAMILY_REGISTRY.get(family)
    if reg is not None:
        return reg["candidates"](grid, n_inner=n_inner,
                                 interpret=interpret)
    shape = (tuple(grid.nxyz[:2]) if family == "wave2d"
             else tuple(grid.nxyz))
    dtype = np.float32
    tpu = perf.device_context()["backend"] == "tpu"
    vmems = [None] if (interpret or not tpu) else [None, 64]
    out: List[Dict] = [{"tier": f"{family}.xla", "K": None, "bx": None,
                        "vmem_mb": None}]

    def chunk_ks(supported, ks=(4, 8)):
        return [K for K in ks
                if supported(grid, shape, K, n_inner - 1, dtype,
                             interpret=interpret)]

    if family == "diffusion3d":
        from .ops import pallas_supported

        if pallas_supported(grid, type("S", (), {
                "ndim": 3, "shape": shape, "dtype": dtype})()):
            for bx in (4, 8, 16):
                if shape[0] % bx == 0:
                    out.append({"tier": "diffusion3d.mosaic", "K": bx,
                                "bx": bx, "vmem_mb": None})
    elif family == "stokes3d":
        from .ops import stokes_trapezoid_supported

        for v in vmems:
            out.append({"tier": "stokes3d.mosaic", "K": None, "bx": None,
                        "vmem_mb": v})
        for K in chunk_ks(stokes_trapezoid_supported):
            out.append({"tier": "stokes3d.trapezoid", "K": K, "bx": None,
                        "vmem_mb": None})
    elif family == "hm3d":
        from .ops.hm3d_trapezoid import hm3d_trapezoid_supported

        for v in vmems:
            out.append({"tier": "hm3d.mosaic", "K": None, "bx": None,
                        "vmem_mb": v})
        for K in chunk_ks(hm3d_trapezoid_supported):
            out.append({"tier": "hm3d.trapezoid", "K": K, "bx": None,
                        "vmem_mb": None})
    elif family == "wave2d":
        from .ops.wave2d_pallas import wave2d_chunk_supported

        out.append({"tier": "wave2d.mosaic", "K": None, "bx": None,
                    "vmem_mb": None})
        for K in chunk_ks(wave2d_chunk_supported):
            out.append({"tier": "wave2d.chunk", "K": K, "bx": None,
                        "vmem_mb": None})
    else:
        raise GridError(
            f"igg.autotune: unknown family {family!r} (built-ins: "
            f"diffusion3d, stokes3d, hm3d, wave2d; registered: "
            f"{sorted(_FAMILY_REGISTRY) or 'none'} — "
            f"igg.autotune.register_family hooks new ones in).")
    # The overlapped XLA composition (igg.hide_communication) is a
    # first-class candidate on the same axes: admission-gated host-side
    # (radius vs ol-1, single-device mesh — igg.overlap.overlap_admission)
    # so a refused variant never costs a search dispatch.  Chunk/mosaic
    # tiers carry their own overlap semantics and get no variant.
    from .overlap import overlap_admission

    radius, nd = _OVERLAP_GEOMETRY[family]
    if overlap_admission(radius, grid=grid, ndim=nd):
        out.insert(1, {"tier": f"{family}.xla", "K": None, "bx": None,
                       "vmem_mb": None, "overlap": True})
    return out


def _build_candidate(family: str, cand: Dict, n_inner: int, params,
                     interpret: bool):
    """(state_fn, args) for one candidate config: the family factory
    pinned to the candidate's tier/K/bx (``tune=False`` so the search
    never recurses into itself), on family-default f32 fields."""
    reg = _FAMILY_REGISTRY.get(family)
    if reg is not None:
        return reg["build"](cand, n_inner=n_inner, params=params,
                            interpret=interpret)
    tier = cand["tier"]
    fast = not tier.endswith(".xla")
    ov = bool(cand.get("overlap"))
    if family == "diffusion3d":
        from .models import diffusion3d as m

        p = params or m.Params()
        T, Cp = m.init_fields(p, dtype=np.float32)
        step = m.make_multi_step(
            n_inner, p, donate=False, overlap=ov,
            use_pallas=(True if fast else False),
            pallas_interpret=interpret, bx=cand.get("bx"), tune=False)
        return (lambda T, Cp: (step(T, Cp), Cp)), (T, Cp)
    if family == "stokes3d":
        from .models import stokes3d as m

        p = params or m.Params()
        fields = m.init_fields(p, dtype=np.float32)
        it = m.make_iteration(
            p, donate=False, n_inner=n_inner, overlap=ov,
            use_pallas=(True if fast else False), pallas_interpret=interpret,
            trapezoid=(tier.endswith(".trapezoid")), K=cand.get("K"),
            tune=False)
        return (lambda P, Vx, Vy, Vz, Rho:
                it(P, Vx, Vy, Vz, Rho) + (Rho,)), tuple(fields)
    if family == "hm3d":
        from .models import hm3d as m

        p = params or m.Params()
        fields = m.init_fields(p, dtype=np.float32)
        step = m.make_step(
            p, donate=False, n_inner=n_inner, overlap=ov,
            use_pallas=(True if fast else False), pallas_interpret=interpret,
            trapezoid=(tier.endswith(".trapezoid")), K=cand.get("K"),
            tune=False)
        return (lambda Pe, phi: step(Pe, phi)), tuple(fields)
    if family == "wave2d":
        from .models import wave2d as m

        p = params or m.Params()
        fields = m.init_fields(p, dtype=np.float32)
        step = m.make_step(
            p, donate=False, n_inner=n_inner, overlap=ov,
            use_pallas=(True if fast else False), pallas_interpret=interpret,
            chunk=(tier == "wave2d.chunk"), K=cand.get("K"),
            tune=False)
        return (lambda P, Vx, Vy: step(P, Vx, Vy)), tuple(fields)
    raise GridError(f"igg.autotune: unknown family {family!r}.")


def _cand_label(cand: Dict) -> str:
    bits = [cand["tier"]]
    if cand.get("overlap"):
        bits.append("overlap")
    if cand.get("K"):
        bits.append(f"K={cand['K']}")
    if cand.get("bx"):
        bits.append(f"bx={cand['bx']}")
    if cand.get("vmem_mb"):
        bits.append(f"vmem={cand['vmem_mb']}MB")
    return "[" + ",".join(bits) + "]"


def search(family: str, *, n_inner: int = 8, params=None,
           interpret: bool = False, nt: Optional[int] = None,
           candidates: Optional[Sequence[Dict]] = None,
           cutoff: Optional[float] = None,
           source: str = "autotune") -> Optional[Dict]:
    """Measure the candidate set for `family`'s current signature and
    install the winner in the tuning cache.

    Measurement protocol per candidate: one untimed warm-up dispatch
    (pays the compile), one quick timed dispatch — if that already
    exceeds ``cutoff`` x the best quick sample so far, the candidate is
    CUT OFF (its quick sample still lands in the ledger) — otherwise
    `igg.time_steps` slope timing (nt and 3*nt batches; constant
    dispatch latency cancels).  The ledger prior (:func:`igg.perf.best`)
    orders the candidates so the cutoff threshold is set by the likely
    winner first.  All samples are recorded into the perf ledger
    (source ``"autotune"``); the winner is persisted to the tuning
    cache.  Returns the winner entry (None when nothing is
    measurable)."""
    global _SEARCH_DISPATCHES
    import jax

    import igg
    from . import perf, shared

    shared.check_initialized()
    nt = int(nt if nt is not None else _env.number("IGG_TUNE_NT", 2))
    cutoff = float(cutoff if cutoff is not None
                   else _env.number("IGG_TUNE_CUTOFF", 2.0))
    cands = list(candidates if candidates is not None
                 else candidates_for(family, n_inner=n_inner,
                                     interpret=interpret))
    if not cands:
        return None

    ctx = _context(family)
    # The ledger prior orders the walk: best-known tier's candidates
    # first, so the cutoff threshold is set by the likely winner.
    prior = perf.best(family, local_shape=ctx["local_shape"] or None)
    if prior is not None:
        cands.sort(key=lambda c: 0 if c["tier"] == prior["tier"] else 1)

    from .ops import _vmem

    results = []
    best_quick = None
    entry_cap = _vmem._CAP_OVERRIDE      # restored on exit
    try:
        for cand in cands:
            label = _cand_label(cand)
            # vmem_mb=None candidates measure at the TRUE hand-derived
            # default (override cleared), never at a previously-applied
            # winner's cap — the baseline must not be biased by state.
            _vmem.set_cap_override(int(cand["vmem_mb"]) * 1024 * 1024
                                   if cand.get("vmem_mb") else None)
            try:
                state_fn, args = _build_candidate(family, cand, n_inner,
                                                  params, interpret)
                scratch = tuple(a + 0 for a in args)  # donation-safe
                # Warm-up (compile) + one quick timed dispatch.
                out = state_fn(*scratch)
                jax.block_until_ready(out)
                t0 = time.monotonic()
                out = state_fn(*out)
                jax.block_until_ready(out)
                quick = (time.monotonic() - t0) / n_inner * 1e3
                _SEARCH_DISPATCHES += 1
                cut = (best_quick is not None
                       and quick > cutoff * best_quick)
                if not cut:
                    _, sec = igg.time_steps(state_fn, out, n1=nt,
                                            n2=3 * nt, warmup=0)
                    _SEARCH_DISPATCHES += 4 * nt
                    ms = sec / n_inner * 1e3
                else:
                    ms = quick
                best_quick = (quick if best_quick is None
                              else min(best_quick, quick))
            except Exception as e:  # an inadmissible/failing candidate
                _telemetry.emit("autotune_candidate_failed",
                                family=family, candidate=label,
                                error=f"{type(e).__name__}: {e}")
                continue
            perf.record(family, cand["tier"], ms, source=source,
                        local_shape=ctx["local_shape"],
                        dtype="float32", dims=ctx["dims"],
                        backend=ctx["backend"],
                        device_kind=ctx["device_kind"])
            _telemetry.emit("autotune_sample", family=family,
                            candidate=label, ms_per_step=ms,
                            cutoff=bool(cut))
            results.append((ms, cand))
    finally:
        _vmem.set_cap_override(entry_cap)
    if not results:
        return None
    results.sort(key=lambda r: (r[0] if math.isfinite(r[0]) else
                                float("inf")))
    ms, best = results[0]
    if best.get("overlap") and not _overlap_confirmed(family, params,
                                                      n_inner):
        # The overlapped composition won the slope timing but the
        # measured step-time decomposition shows no exposed-comm drop
        # (hidden >= exchange): the timing win is noise or slab-recompute
        # luck, not hidden communication — demote to the best
        # non-overlapped candidate.  The decomposition samples are in the
        # perf ledger (family "comm", tier "overlap.<family>.xla+overlap.*",
        # source "calibrate"), so `igg.perf compare` gates the decision.
        seq = next((r for r in results if not r[1].get("overlap")), None)
        _telemetry.emit("overlap_demoted", family=family,
                        overlapped_ms=ms,
                        demoted_to=_cand_label(seq[1]) if seq else None)
        if seq is not None:
            ms, best = seq
    winner = dict(best, ms=ms)
    return record_winner(family, winner, local_shape=ctx["local_shape"])


def _overlap_confirmed(family: str, params, n_inner: int) -> bool:
    """Exposed-comm-driven selection: an overlapped candidate that wins
    the slope timing is recorded ONLY when an in-search
    :func:`igg.comm.decompose` window shows the hidden variant actually
    beating the plain exchange (measured exposed communication drops) —
    attributed to the ``"<family>.xla+overlap"`` serving config in the
    comm ledger.  Families without a step-variant recipe (spec-compiled
    ones measure through their own registered builders) pass on the
    timing evidence alone."""
    from . import comm

    try:
        mv = comm.model_step_variants(family, params)
    except GridError:
        return True
    try:
        fields = mv["init"](np.float32)
        d = comm.decompose(mv["compute"], fields[:mv["nf"]],
                           aux=fields[mv["nf"]:], radius=mv["radius"],
                           nt=2, n_inner=max(2, int(n_inner) // 2),
                           config=f"{family}.xla+overlap")
    except Exception as e:   # a failed probe must not kill the search
        _telemetry.emit("overlap_confirm_failed", family=family,
                        error=f"{type(e).__name__}: {e}")
        return True
    return d["hidden_ms"] < d["exchange_ms"]
