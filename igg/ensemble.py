"""Ensemble execution tier — M independent simulations, ONE compiled
program, per-member fault isolation.

The reference's headline workloads are parameter sweeps: many independent
simulations of the same model, each too small to need the whole machine.
:func:`run_resilient` serves exactly one simulation per mesh; this module
packs M independent *members* onto the grid and generalizes the round-8
watchdog/rollback machinery to a **per-member** verdict, so one diverging
member never rolls back, stalls, or kills the batch (the
many-scenarios-per-slice pattern of the TensorFlow-TPU CFD framework,
arXiv:2108.11076).

**Packing.**  Member states are stacked on a LEADING member axis
(`state[f]` has shape `(M,) + stacked_shape`) and the user's *local member
step* — a function over per-device local blocks, the `igg.sharded`
programming model (`igg.update_halo_local` / `igg.local_coords` allowed)
— is `jax.vmap`'d over that axis inside one `shard_map` program:

- **grid packing** (`packing="grid"`; the auto choice whenever the grid is
  decomposed): the member axis is unsharded and every member's fields are
  sharded over the grid mesh axes as usual — each device steps all M
  members' local blocks in one fused dispatch, halo ppermutes batched
  over members.
- **batch packing** (`packing="batch"`; the auto choice when the grid is
  `dims=(1,1,1)` — one device holds a whole member — and more devices
  exist): the member axis itself is sharded over an ensemble mesh of ALL
  available devices (axes `("member",) + AXIS_NAMES`, trailing grid axes
  of size 1 so the halo primitives stay bound), the batch-axis
  `NamedSharding` recipe for packing independent simulations into one
  compiled program.  Requires `M % n_devices == 0`.

**Per-member watchdog.**  Every `watch_every` steps one fused probe
computes each watched field's non-finite count REDUCED OVER GRID AXES
ONLY — an `(n_fields, M)` matrix, psum'd over the mesh (grid packing) or
member-sharded (batch packing) — fetched asynchronously exactly like the
round-8 probe (`is_ready()` polling, bounded pending queue): the hot loop
never host-syncs, and a blowup is attributed to its member ON DEVICE.

**Per-member isolation.**  Checkpoint generations gain member lanes: the
stacked fields are written MEMBER-AXIS-LAST (`(X, Y, Z, M)` — the sharded
generation format's trailing-dim support carries the lane for free, the
PR-4 elastic restore included), plus an `ensemble.json` sidecar recording
member count, per-member retry/quarantine state, and any per-member
scalar parameter fields (bit-exact, raw-byte encoded).  On detection the
loop rolls back ONLY the diverged members — their lanes are restored from
the newest generation whose *lanes* pass the finite gate, then replayed
to the front under a validity mask (healthy members' lanes are frozen
bit-exactly by a `where`-select and replay nothing; they finish
bit-identical to an uninterrupted run).  A member that exhausts its
per-member retry budget is **quarantined** — masked out of the step and
the probe verdict, `member_quarantined` event — instead of raising
:class:`igg.ResilienceError` for the batch: the `igg.degrade` philosophy
applied to ensemble members.  Preemption (SIGTERM /
`igg.resilience.request_preemption`) writes a final generation; a
relaunch with `resume=True` re-tiles it elastically onto whatever
devices/decomposition exist (`load_checkpoint(redistribute=True)`), with
quarantine state restored from the sidecar.

Every isolation path is provable deterministically on the 8-device CPU
mesh through the member-targeted injectors of :mod:`igg.chaos`
(`ChaosPlan.nan_at` accepts `(step, member, field)` entries) —
`tests/test_ensemble.py`.  Single-controller only in this round: the
fleet tier (:mod:`igg.fleet`) schedules whole jobs, not processes.
"""

from __future__ import annotations

import base64
import dataclasses
import json
import pathlib
import signal
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from . import shared
from . import telemetry as _telemetry
from .shared import AXIS_NAMES, GridError
from . import resilience as _resilience
from .resilience import Event, ResilienceError, _is_ready, \
    clear_preemption, preemption_requested, request_preemption

__all__ = ["run_ensemble", "EnsembleResult", "stack_members",
           "member_state"]

# Sidecar file inside a generation directory carrying the ensemble lane
# metadata (member count, per-member retries/quarantine, scalar parameter
# fields).  Written AFTER the generation commits; `igg.load_checkpoint`
# ignores it, so the generation stays a plain igg-sharded-v1 artifact.
_SIDECAR = "ensemble.json"
_SIDECAR_FORMAT = "igg-ensemble-v1"


def _member_retries_default() -> int:
    from . import _env

    return int(_env.integer("IGG_ENSEMBLE_RETRIES", 2))


def _max_pending_default() -> int:
    from . import _env

    return int(_env.integer("IGG_ENSEMBLE_MAX_PENDING_PROBES", 4))


@dataclasses.dataclass
class EnsembleResult:
    """What :func:`run_ensemble` returns: the stacked `state` (leading
    member axis; :meth:`member_state` slices one lane), the member count,
    `steps_done` for the batch front, per-member `retries` consumed, the
    `quarantined` member indices, whether the run was `preempted`, the
    `events` log (kinds documented in docs/resilience.md), the
    `checkpoint` path of the generation holding the returned state, and
    the `packing` that served the run ("grid" or "batch")."""
    state: Dict
    members: int
    steps_done: int
    retries: Dict[int, int]
    quarantined: List[int]
    preempted: bool
    events: List[Event]
    checkpoint: Optional[pathlib.Path]
    packing: str

    def member_state(self, m: int) -> Dict:
        return member_state(self.state, m)


def member_state(stacked: Dict, m: int) -> Dict:
    """Slice one member's state dict out of a stacked ensemble state."""
    return {k: v[m] for k, v in stacked.items()}


def stack_members(states: Sequence[Dict]) -> Dict:
    """Stack M member state dicts (same keys/shapes/dtypes) on a leading
    member axis — host-side; :func:`run_ensemble` re-shards the result
    onto the packing it chooses."""
    if not states:
        raise GridError("stack_members: no member states given.")
    keys = sorted(states[0])
    for i, st in enumerate(states):
        if sorted(st) != keys:
            raise GridError(
                f"stack_members: member {i} has fields {sorted(st)}, "
                f"member 0 has {keys} — all members must share one field "
                f"model.")
    out = {}
    for k in keys:
        arrs = [np.asarray(st[k]) for st in states]
        for i, a in enumerate(arrs):
            if a.shape != arrs[0].shape or a.dtype != arrs[0].dtype:
                raise GridError(
                    f"stack_members: field {k!r} of member {i} is "
                    f"{a.shape}/{a.dtype}, member 0 is "
                    f"{arrs[0].shape}/{arrs[0].dtype}.")
        out[k] = np.stack(arrs)
    return out


# ---------------------------------------------------------------------------
# Packing: where the member axis lives
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _Packing:
    name: str                 # "grid" | "batch"
    mesh: object              # the mesh the ensemble programs run over
    grid: object              # the live GlobalGrid
    members: int
    cpu_sync: bool            # block per dispatch (XLA:CPU rendezvous)

    def spec(self, stacked_ndim: int):
        from jax.sharding import PartitionSpec as P

        gaxes = AXIS_NAMES[:min(stacked_ndim - 1, shared.NDIMS)]
        lead = "member" if self.name == "batch" else None
        return P(lead, *gaxes)

    def sharding(self, stacked_ndim: int):
        from jax.sharding import NamedSharding

        return NamedSharding(self.mesh, self.spec(stacked_ndim))

    def mask_spec(self):
        from jax.sharding import PartitionSpec as P

        return P("member") if self.name == "batch" else P()

    def put_state(self, state: Dict) -> Dict:
        import jax

        return {k: jax.device_put(v, self.sharding(np.ndim(v)))
                for k, v in state.items()}

    def put_mask(self, mask: np.ndarray):
        import jax
        from jax.sharding import NamedSharding

        return jax.device_put(mask, NamedSharding(self.mesh,
                                                  self.mask_spec()))


def _choose_packing(grid, members: int, packing: str, devices) -> _Packing:
    import jax
    from jax.sharding import Mesh

    devs = list(devices) if devices is not None else list(jax.devices())
    platform = getattr(devs[0], "platform", "cpu") if devs else "cpu"
    batch_ok = (grid.nprocs == 1 and len(devs) > 1
                and members % len(devs) == 0)
    if packing == "auto":
        packing = "batch" if batch_ok else "grid"
    if packing == "batch":
        if not batch_ok:
            raise GridError(
                f"run_ensemble(packing='batch') needs a dims=(1,1,1) grid "
                f"(got dims={grid.dims}), more than one device, and a "
                f"member count divisible by the device count "
                f"({members} members over {len(devs)} device(s)).")
        mesh = Mesh(np.array(devs).reshape(len(devs), 1, 1, 1),
                    ("member",) + AXIS_NAMES)
        return _Packing("batch", mesh, grid, members,
                        cpu_sync=(platform == "cpu" and len(devs) > 1))
    if packing != "grid":
        raise GridError(f"run_ensemble: unknown packing {packing!r} "
                        f"(expected 'auto', 'grid', or 'batch').")
    return _Packing("grid", grid.mesh, grid, members,
                    cpu_sync=grid.needs_cpu_sync)


# ---------------------------------------------------------------------------
# Compiled programs: the masked vmapped step and the per-member probe
# ---------------------------------------------------------------------------

def _build_step(step_fn: Callable, pk: _Packing, keys, ndims: Dict[str, int],
                steps_per_call: int):
    """ONE jitted `shard_map` advancing every unmasked member
    `steps_per_call` steps: inside each device's shard the user's local
    member step is `vmap`'d over the (local) member axis, and a validity
    mask freezes rolled-back/quarantined lanes by a bit-exact
    `where`-select."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def masked(st, mask):
        def body(_, s):
            new = step_fn(dict(s))
            if not isinstance(new, dict) or sorted(new) != list(keys):
                raise GridError(
                    f"run_ensemble: step_fn must map the member state dict "
                    f"to a dict with the same fields {list(keys)}; got "
                    f"{sorted(new) if isinstance(new, dict) else type(new)}.")
            return {k: new[k] for k in keys}

        def one(s):
            stepped = jax.vmap(lambda ms: body(0, ms))(s)
            out = {}
            for k in keys:
                m = mask.reshape(mask.shape + (1,) * (stepped[k].ndim - 1))
                out[k] = jnp.where(m, stepped[k], s[k])
            return out

        if steps_per_call > 1:
            return lax.fori_loop(0, steps_per_call, lambda _, s: one(s), st)
        return one(st)

    in_specs = ({k: pk.spec(ndims[k]) for k in keys}, pk.mask_spec())
    out_specs = {k: pk.spec(ndims[k]) for k in keys}
    sm = jax.shard_map(masked, mesh=pk.mesh, in_specs=in_specs,
                       out_specs=out_specs)
    return jax.jit(sm)


def _build_probe(pk: _Packing, watch, ndims: Dict[str, int],
                 probe_fields=None, invariants=()):
    """The per-member health probe: one fused pass per watched field
    computing its non-finite count per member — reduced over GRID axes
    only, so the result is an `(n_fields, M)` matrix attributing any
    blowup to its member on device.  Grid packing psums over the mesh
    (replicated result); batch packing keeps the member axis sharded (no
    collective at all).

    With `invariants` (round 19 — the :mod:`igg.integrity` layer), the
    matrix gains ``2·n_inv`` ROWS: each invariant's per-member owned-cell
    value and scale sums (``Σ f^m`` / ``Σ|f|^m`` over the de-duplicated
    grid cells of the member's lane), fused into the SAME probe program
    and fetched by the SAME single async fetch — finite-but-wrong lanes
    become attributable with zero additional host syncs.  `probe_fields`
    widens the input set past `watch` when an invariant reads an
    unwatched field."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from .parallel import psum_grid

    fields = list(probe_fields) if probe_fields is not None else list(watch)

    def probe(*arrays):
        by_field = dict(zip(fields, arrays))
        counts = []
        for k in watch:
            a = by_field[k]
            if jnp.issubdtype(a.dtype, jnp.inexact):
                c = jnp.sum((~jnp.isfinite(a)).astype(jnp.float32),
                            axis=tuple(range(1, a.ndim)))
            else:
                c = jnp.zeros((a.shape[0],), jnp.float32)
            if pk.name == "grid":
                c = psum_grid(c)
            counts.append(c)
        rows = list(counts)
        if invariants:
            from . import integrity as _integrity

            grid = shared.global_grid()
            rows.extend(_integrity.member_invariant_rows(
                invariants, by_field, pk.name, grid))
        out = jnp.stack(rows)
        if pk.name == "batch":
            # The batch mesh's grid axes have size 1: this psum is the
            # identity, and it types the rows grid-invariant, which the
            # P(None, "member") out_specs requires under check_vma.
            out = psum_grid(out)
        return out

    in_specs = tuple(pk.spec(ndims[k]) for k in fields)
    out_specs = P(None, "member") if pk.name == "batch" else P()
    sm = jax.shard_map(probe, mesh=pk.mesh, in_specs=in_specs,
                       out_specs=out_specs)
    return jax.jit(sm)


# ---------------------------------------------------------------------------
# Generation layout: member-axis-last shards + the ensemble sidecar
# ---------------------------------------------------------------------------

def _encode_param(v: np.ndarray) -> dict:
    v = np.ascontiguousarray(v)
    return {"dtype": str(v.dtype), "shape": list(v.shape),
            "data": base64.b64encode(v.tobytes()).decode("ascii")}


def _decode_param(d: dict) -> np.ndarray:
    raw = base64.b64decode(d["data"].encode("ascii"))
    return np.frombuffer(raw, dtype=np.dtype(d["dtype"])).reshape(
        d["shape"]).copy()


def _write_sidecar(gen: pathlib.Path, meta: dict) -> None:
    from .checkpoint import _write_atomic_text

    _write_atomic_text(gen / _SIDECAR, json.dumps(meta))


def _read_sidecar(gen: pathlib.Path) -> Optional[dict]:
    try:
        meta = json.loads((gen / _SIDECAR).read_text())
    except (OSError, json.JSONDecodeError, UnicodeDecodeError):
        return None
    if meta.get("format") != _SIDECAR_FORMAT:
        return None
    return meta


def _save_generation(path: pathlib.Path, state: Dict, grid_fields, params,
                     grid, sidecar_meta: dict) -> pathlib.Path:
    """Write one ensemble generation: the stacked grid fields
    member-axis-LAST through :func:`igg.save_checkpoint_sharded` (trailing
    dims ride the existing rank-4+ support — elastic restore included),
    then the sidecar with the lane metadata and the raw-byte-encoded
    per-member scalar parameter fields.

    Generations live on the GRID mesh.  Under grid packing that is the
    O(local-per-device) layout the PR-4 format expects.  Under BATCH
    packing the grid mesh is a single device, so the device_put below
    stages the full M-member stack there for the write — fine at the
    whole-domain-fits-one-device scale batch packing targets, but a real
    memory cliff when M*domain approaches device memory (a member-sharded
    generation format is the open item; docs/resilience.md)."""
    import jax
    import jax.numpy as jnp

    from . import checkpoint as ckpt
    from .fields import sharding_for

    fields = {}
    for k in grid_fields:
        moved = jnp.moveaxis(state[k], 0, -1)
        fields[k] = jax.device_put(moved, sharding_for(moved.ndim))
    ckpt.save_checkpoint_sharded(path, **fields)
    meta = dict(sidecar_meta)
    meta["format"] = _SIDECAR_FORMAT
    meta["params"] = {k: _encode_param(np.asarray(state[k])) for k in params}
    _write_sidecar(path, meta)
    return path


def _state_from_loaded(loaded: Dict, meta: dict, gen, pk: _Packing,
                       grid_fields, params) -> Dict:
    """Convert a raw `load_checkpoint` result (member-axis-LAST fields)
    plus its sidecar into a live stacked state dict on the packing."""
    import jax
    import jax.numpy as jnp

    missing = [k for k in grid_fields if k not in loaded]
    if missing:
        raise GridError(f"run_ensemble: generation {gen} is missing "
                        f"fields {missing}.")
    state = {}
    for k in grid_fields:
        state[k] = jax.device_put(jnp.moveaxis(loaded[k], -1, 0),
                                  pk.sharding(loaded[k].ndim))
    for k in params:
        enc = meta.get("params", {}).get(k)
        if enc is None:
            raise GridError(f"run_ensemble: generation {gen} sidecar has no "
                            f"parameter field {k!r}.")
        state[k] = jax.device_put(_decode_param(enc), pk.sharding(1))
    return state


def _load_generation(gen: pathlib.Path, pk: _Packing, grid_fields, params,
                     redistribute: bool = False):
    """Full restore of an ensemble generation onto the live packing:
    `(stacked state dict, sidecar meta)`.  `redistribute=True` rides the
    PR-4 elastic path (the member axis is a trailing dim, preserved
    bit-exactly across re-tiling)."""
    from . import checkpoint as ckpt

    meta = _read_sidecar(gen)
    if meta is None:
        raise GridError(f"run_ensemble: generation {gen} has no readable "
                        f"{_SIDECAR} sidecar — not an ensemble generation.")
    loaded = ckpt.load_checkpoint(gen, redistribute=redistribute)
    return _state_from_loaded(loaded, meta, gen, pk, grid_fields,
                              params), meta


def _finite(arr) -> bool:
    """All-finite gate in the array's NATIVE dtype (ml_dtypes covers the
    extension floats; a dtype without isfinite support passes — the
    round-8 `_all_finite` convention)."""
    try:
        return bool(np.isfinite(arr).all())
    except TypeError:
        return True


def _lanes_finite(loaded: Dict, meta: dict, grid_fields, params,
                  lanes) -> bool:
    """Whether the given member lanes of every field in an already-loaded
    generation are entirely finite — the per-member analog of
    `verify_checkpoint(check_finite=True)`: a generation whose QUARANTINED
    lanes hold NaNs is still a perfectly healthy rollback target for the
    other members.  Takes the RAW `load_checkpoint` result so the rollback
    scan reads each candidate exactly once (the load already CRC-verified
    every shard it touched); the lane slice happens ON DEVICE, so the host
    fetch is O(|lanes|), not O(M)."""
    import jax.numpy as jnp

    lanes = np.asarray(list(lanes), dtype=np.int32)
    for k in grid_fields:
        if k not in loaded:
            return False
        if not jnp.issubdtype(loaded[k].dtype, jnp.inexact):
            continue
        if not _finite(np.asarray(loaded[k][..., lanes])):
            return False
    for k in params:
        enc = meta.get("params", {}).get(k)
        if enc is None:
            return False
        v = _decode_param(enc)
        if (np.issubdtype(v.dtype, np.floating)
                and not np.isfinite(v[lanes]).all()):
            return False
    return True


# ---------------------------------------------------------------------------
# The ensemble run loop
# ---------------------------------------------------------------------------

def run_ensemble(step_fn: Callable[[Dict], Dict], states, n_steps: int, *,
                 members: Optional[int] = None,
                 watch_every: int = 50,
                 watch_fields: Optional[Sequence[str]] = None,
                 checkpoint_dir=None,
                 checkpoint_every: int = 0,
                 ring: int = 3,
                 prefix: str = "ens",
                 member_retries: Optional[int] = None,
                 resume: bool = False,
                 steps_per_call: int = 1,
                 max_pending_probes: Optional[int] = None,
                 packing: str = "auto",
                 devices=None,
                 install_sigterm: bool = True,
                 on_event: Optional[Callable[[Event], None]] = None,
                 telemetry=None,
                 serve=None,
                 integrity=None,
                 chaos=None) -> EnsembleResult:
    """Drive M independent members of `step_fn` for `n_steps` steps in ONE
    compiled program with per-member fault isolation (module docstring for
    the full contract).

    - `step_fn`: the LOCAL member step — maps one member's state dict of
      per-device local blocks to the next (the `igg.sharded` programming
      model: `igg.update_halo_local`/`igg.local_coords` allowed; e.g.
      `igg.models.diffusion3d.make_member_step`).  It is vmapped over the
      member axis inside one `shard_map` program — do NOT pass an
      `igg.sharded`-wrapped step (that is a whole-mesh program already).
    - `states`: list of M member state dicts (same field model each), or
      an already-stacked dict of `(M,) + stacked_shape` arrays with
      `members=M`.  Per-member fields must be grid fields (rank >= 3) or
      scalars (a per-member parameter — carried through checkpoints via
      the sidecar, bit-exact).
    - `watch_every`/`watch_fields`: the per-member watchdog cadence (0
      disables).  `checkpoint_every`/`checkpoint_dir`/`ring`/`prefix`: the
      generation ring (always sharded directories).  `steps_per_call`
      folds that many steps into each compiled dispatch (an in-program
      `fori_loop`); cadences count steps and must be multiples of it.
    - `member_retries` (default `IGG_ENSEMBLE_RETRIES`, 2): per-member
      rollback budget; exhaustion QUARANTINES the member (frozen lane,
      `member_quarantined` event) instead of failing the batch.  A
      detection with no rollback target quarantines immediately
      (reason `no_rollback_target`).
    - `packing`: "auto" (default), "grid", or "batch" (module docstring);
      `devices` restricts batch packing's ensemble mesh (default: all).
    - `resume=True` loads the newest healthy generation elastically
      (different `dims`/device count included) and restores quarantine
      state from the sidecar.
    - `telemetry`: unified observability (:mod:`igg.telemetry` — the
      :func:`igg.run_resilient` contract: None/False/True/dir/session).
      Events flow onto the process bus regardless; with a session
      attached the run also emits per-window `step_stats` records with
      aggregate member rates (piggybacked on the per-member watchdog's
      async fetches — zero extra host syncs), exports metrics, and
      auto-dumps the flight recorder on faults.
    - `serve`: the live ops endpoint (:mod:`igg.statusd` — the
      :func:`igg.run_resilient` contract: None = ``IGG_STATUSD_PORT``-
      driven, int port, True, shared server, or False).  `/healthz`
      readiness flips false when EVERY member is quarantined — the
      batch has nothing left to serve.
    - `integrity`: the numeric-integrity layer (:mod:`igg.integrity` —
      the :func:`igg.run_resilient` contract: None = ``IGG_INTEGRITY``-
      driven, True, an :class:`igg.integrity.IntegrityConfig`, False).
      At the ensemble tier it is the PER-MEMBER invariant probe: each
      registered/declared invariant contributes per-member owned-cell
      value/scale rows to the watchdog matrix (same fused program, same
      single async fetch — zero extra host syncs), and a member whose
      invariant drifts past tolerance raises ``integrity_violation``
      attributed to its LANE and rides the per-member rollback/
      quarantine machinery exactly like a NaN verdict.  Shadow
      re-execution checks and deep-verified generation scans are the
      `run_resilient` half of the contract (lane scans stay
      finite-gated; generations are still deep-STAMPED for offline
      audit).  Requires `watch_every` > 0.
    - `chaos`: an :class:`igg.chaos.ChaosPlan`; member-targeted entries
      `(step, member, field)` poison one member's lane.

    Returns an :class:`EnsembleResult`.  Raises :class:`ResilienceError`
    only when EVERY member is quarantined (there is no batch left to
    serve); single-member failures are always isolated.
    """
    import jax

    from . import checkpoint as ckpt

    shared.check_initialized()
    grid = shared.global_grid()
    if int(jax.process_count()) > 1:
        raise GridError(
            "run_ensemble: the ensemble tier is single-controller in this "
            "round (the fleet scheduler packs whole jobs, not processes); "
            "drive multi-controller meshes through igg.run_resilient.")

    if isinstance(states, dict):
        if members is None:
            raise GridError("run_ensemble: a pre-stacked state dict needs "
                            "members=M.")
        state = {k: states[k] for k in sorted(states)}
        for k, v in state.items():
            if np.ndim(v) < 1 or np.shape(v)[0] != members:
                raise GridError(
                    f"run_ensemble: stacked field {k!r} has shape "
                    f"{np.shape(v)}; expected a leading member axis of "
                    f"{members}.")
    else:
        state = stack_members(list(states))
        members = len(states)
    if members < 1:
        raise GridError("run_ensemble: members must be >= 1.")
    if not state:
        raise GridError("run_ensemble: state must be a non-empty dict of "
                        "named member fields.")
    if steps_per_call < 1:
        raise GridError("run_ensemble: steps_per_call must be >= 1.")
    for nm, value in (("n_steps", n_steps), ("watch_every", watch_every),
                      ("checkpoint_every", checkpoint_every)):
        if value and value % steps_per_call != 0:
            raise GridError(
                f"run_ensemble: {nm}={value} is not a multiple of "
                f"steps_per_call={steps_per_call}.")
    if checkpoint_every and checkpoint_dir is None:
        raise GridError("run_ensemble: checkpoint_every > 0 requires "
                        "checkpoint_dir.")
    if resume and checkpoint_dir is None:
        raise GridError("run_ensemble: resume=True requires checkpoint_dir.")
    if ring < 1:
        raise GridError("run_ensemble: ring must be >= 1.")
    if member_retries is None:
        member_retries = _member_retries_default()
    if max_pending_probes is None:
        max_pending_probes = _max_pending_default()

    import jax.numpy as jnp

    keys = sorted(state)
    ndims = {k: int(np.ndim(state[k])) for k in keys}
    # Field model split: grid fields carry member lanes in the shard files
    # (member-axis-last); scalar per-member parameters ride the sidecar.
    grid_fields = [k for k in keys if ndims[k] >= 4]
    params = [k for k in keys if ndims[k] == 1]
    odd = [k for k in keys if k not in grid_fields and k not in params]
    if odd and (checkpoint_dir is not None):
        raise GridError(
            f"run_ensemble: per-member fields must be rank-3+ grid fields "
            f"or scalars when checkpointing is enabled; {odd} are "
            f"{[ndims[k] - 1 for k in odd]}-D per member.")
    # jnp.issubdtype so extension floats (bfloat16, float8_*) stay in the
    # default watch set (the round-8 fix); per-member scalars are watched
    # only when named explicitly (a swept parameter is not a health
    # signal).
    watch = (list(watch_fields) if watch_fields is not None
             else [k for k in keys
                   if jnp.issubdtype(getattr(state[k], "dtype", np.float64),
                                     jnp.inexact) and ndims[k] >= 2])
    missing = [k for k in watch if k not in state]
    if missing:
        raise GridError(f"run_ensemble: watch_fields {missing} not in "
                        f"state {keys}.")

    # Numeric-integrity layer (igg.integrity): per-member invariant rows
    # fused into the watchdog probe matrix.
    from . import integrity as _integrity

    int_cfg = _integrity.as_config(integrity)
    if int_cfg is not None and not (watch and watch_every):
        raise GridError(
            "run_ensemble: the integrity= probes ride the watch cadence; "
            "set watch_every > 0 (with watched fields).")
    invariants = ()
    memrefs = None
    if int_cfg is not None:
        if int_cfg.invariants is not None:
            invariants = tuple(int_cfg.invariants)
            bad_inv = [i.name for i in invariants
                       if not set(i.fields) <= set(state)]
            if bad_inv:
                raise GridError(
                    f"run_ensemble: invariant(s) {bad_inv} name fields not "
                    f"in the member state {sorted(state)}.")
        else:
            invariants = _integrity.match_invariants(state, grid)
        memrefs = _integrity.MemberRefs(invariants, members,
                                        int_cfg.resolved_tol())

    pk = _choose_packing(grid, members, packing, devices)
    state = pk.put_state(state)

    cdir = (pathlib.Path(checkpoint_dir) if checkpoint_dir is not None
            else None)
    events: List[Event] = []

    def _emit(kind, step, **detail) -> Event:
        ev = Event(kind, step, detail)
        events.append(ev)
        # The unified bus (igg.telemetry); `events` stays the per-run view.
        _telemetry.emit(kind, step=step, run="ensemble", **detail)
        if on_event is not None:
            on_event(ev)
        return ev

    # Unified telemetry session: attached before the resume scan so the
    # earliest events reach the JSONL sink (the run_resilient pattern).
    tel = _telemetry.as_session(telemetry)
    tel_owns = tel is not None and not tel.attached
    if tel_owns:
        tel.attach()
    # Live ops endpoint (igg.statusd): the run_resilient contract.
    from . import statusd as _statusd

    try:
        srv = _statusd.as_server(serve)
        srv_owns = srv is not None and not srv.started
        if srv_owns:
            srv.start()
    except BaseException:
        # A bind failure must not leak the run-owned session.
        if tel_owns:
            tel.detach()
        raise
    _telemetry.emit("run_started", run="ensemble", n_steps=n_steps,
                    members=members, packing=pk.name,
                    watch_every=watch_every, steps_per_call=steps_per_call)
    if memrefs is not None:
        _telemetry.emit("integrity_config", run="ensemble",
                        invariants=[i.name for i in invariants],
                        members=members, tol=int_cfg.resolved_tol(),
                        check_every=0, deep_verify=False,
                        shadow="off")
    # Perf-ledger context (igg.perf): the packed member-stacked block is
    # the served shape — attribution mirrors run_resilient's (host-side
    # ladder stamps on the existing fetch timestamps, zero extra syncs).
    from . import perf as _perf

    stats = _telemetry.StepStats(
        "ensemble", members=members,
        perf=(_perf.sample_context(state[watch[0]])
              if watch and _perf.enabled() else None))
    m_steps = _telemetry.counter("igg_steps_total", run="ensemble")
    m_member_steps = _telemetry.counter("igg_member_steps_total")
    m_rollbacks = _telemetry.counter("igg_rollbacks_total", run="ensemble")
    m_quarantined = _telemetry.counter("igg_member_quarantined_total")

    valid = np.ones(members, dtype=bool)       # not quarantined
    retries = {m: 0 for m in range(members)}

    # -- resume ------------------------------------------------------------
    def _generations():
        return (ckpt.list_generations(cdir, prefix)
                if cdir is not None else [])

    steps_done = 0
    resumed_step = None
    # Pre-loop failures (resume scan, stale-ring sweep, program builds)
    # must not leak the run-owned session into the process-global sink
    # list: dump + detach + re-raise (the main loop's own except/finally
    # takes over once it is entered).
    try:
        if resume and cdir is not None:
            for s, p in reversed(_generations()):
                meta = _read_sidecar(p) if p.is_dir() else None
                if meta is None or int(meta.get("members", -1)) != members:
                    continue
                active = [m for m in range(members)
                          if m not in set(meta.get("quarantined", []))]
                try:
                    cand_state, meta = _load_generation(
                        p, pk, grid_fields, params, redistribute=True)
                except GridError:
                    continue
                ok = True
                for k in grid_fields:
                    # Device-sliced to the active lanes: the host fetch
                    # is O(|active|), and a quarantined lane's NaNs never
                    # reject the candidate.
                    if active and not _finite(np.asarray(
                            cand_state[k][np.asarray(active,
                                                     dtype=np.int32)])):
                        ok = False
                        break
                if not ok:
                    continue
                state = cand_state
                steps_done = resumed_step = s
                for m in meta.get("quarantined", []):
                    valid[int(m)] = False
                for m, r in (meta.get("retries", {}) or {}).items():
                    retries[int(m)] = int(r)
                if steps_done % steps_per_call != 0:
                    raise GridError(
                        f"run_ensemble(resume=True): generation {p.name} "
                        f"is at step {steps_done}, not a multiple of "
                        f"steps_per_call={steps_per_call}.")
                _emit("resume", steps_done, path=str(p),
                      quarantined=sorted(int(m) for m in
                                         np.nonzero(~valid)[0]))
                break
            if resumed_step is None:
                # The scan matched nothing: every existing generation is
                # unusable for THIS run (wrong member count, no sidecar,
                # or active lanes non-finite).  The run starts fresh at
                # step 0 — and like a fresh run it must own its ring:
                # left in place, the stale high-step generations would
                # win every newest-`ring` prune (deleting each fresh
                # low-step write immediately) and could never serve a
                # rollback.
                for _, old in _generations():
                    ckpt.remove_generation(old)

        estep = _build_step(step_fn, pk, keys, ndims, steps_per_call)
        probe_fields = list(watch) + [
            f for inv in invariants for f in inv.fields if f not in watch]
        probe_fields = list(dict.fromkeys(probe_fields))
        eprobe = (_build_probe(pk, watch, ndims, probe_fields=probe_fields,
                               invariants=invariants)
                  if (watch and watch_every) else None)
    except BaseException as e:
        _telemetry._auto_dump(f"run_ensemble: {type(e).__name__}: {e}")
        if srv_owns:
            srv.stop()
        if tel_owns:
            tel.detach()
        raise

    pending: deque = deque()       # (probe_step, device counts, mode_snapshot)
    last_good = steps_done         # newest step probe-confirmed for all active
    last_ckpt: Optional[pathlib.Path] = None
    last_ckpt_step = -1
    # Set when a lane restore makes the live state diverge from the
    # newest generation's data (a rollback after the cadence write at the
    # same step): the final/preemption write must then REWRITE the
    # generation, not just re-seal its sidecar — `result.checkpoint`
    # promises the generation holds the returned state.
    gen_stale = False
    preempted = False

    def _sidecar_meta(step):
        return {"members": members, "step": int(step),
                "quarantined": sorted(int(m) for m in np.nonzero(~valid)[0]),
                "retries": {str(m): int(r) for m, r in retries.items()
                            if r}}

    def _gen_path(step) -> pathlib.Path:
        return cdir / f"{prefix}_{step:09d}"

    def _prune(good_until: int) -> None:
        ckpt.prune_generations(cdir, prefix, ring, good_until)

    def _save_gen(step) -> None:
        nonlocal last_ckpt, last_ckpt_step, gen_stale
        with _telemetry.span("checkpoint.generation", step=step,
                             path=str(_gen_path(step)), run="ensemble"):
            p = _save_generation(_gen_path(step), state, grid_fields,
                                 params, grid, _sidecar_meta(step))
        _prune(last_good)
        if step >= last_ckpt_step:
            last_ckpt, last_ckpt_step = p, step
        gen_stale = False
        _emit("checkpoint", step, path=str(p))

    def _mask_for(stepping: np.ndarray):
        return pk.put_mask(np.asarray(stepping, dtype=bool))

    def _dispatch(stepping_mask_dev):
        nonlocal state
        import jax as _jax

        state = estep(state, stepping_mask_dev)
        if pk.cpu_sync:
            _jax.block_until_ready(state[keys[0]])

    def _enqueue_probe(step, verdict_lanes: np.ndarray) -> None:
        pending.append((step, eprobe(*[state[k] for k in probe_fields]),
                        np.array(verdict_lanes)))

    def _poll_probes(drain: bool = False) -> Optional[Event]:
        """Fetch completed probes oldest-first; the verdict is host-masked
        to the lanes the probe was accountable for (quarantined lanes hold
        NaNs by design and must not re-trigger)."""
        nonlocal last_good
        while pending:
            step_p, counts, lanes = pending[0]
            if (not drain and len(pending) <= max_pending_probes
                    and not _is_ready(counts)):
                return None
            pending.popleft()
            host = np.asarray(counts)     # (n_fields [+ 2·n_inv], M)
            lanes = lanes & valid                 # quarantines since enqueue
            nf = host[:len(watch)]
            bad_members = sorted(
                int(m) for m in range(members)
                if lanes[m] and nf[:, m].sum() != 0)
            if bad_members:
                bad = {f: {int(m): int(nf[i, m]) for m in bad_members
                           if nf[i, m]}
                       for i, f in enumerate(watch)
                       if any(nf[i, m] for m in bad_members)}
                pending.clear()
                return _emit("member_diverged", step_p,
                             members=bad_members, counts=bad)
            if memrefs is not None:
                # Per-member invariant drift (igg.integrity): a lane whose
                # conserved/bounded quantity moved past tolerance while
                # staying FINITE — the silent-corruption verdict the NaN
                # rows above provably cannot raise.  Rides the same
                # rollback/quarantine machinery as a divergence.
                bad_inv = memrefs.check(host[len(watch):], lanes)
                if bad_inv:
                    pending.clear()
                    return _emit(
                        "integrity_violation", step_p, source="invariant",
                        members=sorted(bad_inv),
                        invariants={str(m): v
                                    for m, v in sorted(bad_inv.items())})
            if np.array_equal(lanes, valid):
                # Probe-confirmed for EVERY active lane: the generation at
                # (or newest below) this step is a protected rollback
                # target (the round-8 ring-prune guarantee, per member).
                last_good = max(last_good, step_p)
            # Step stats ride THIS fetch (igg.telemetry): the probe was
            # already materialized for the verdict — the rate telemetry
            # (incl. the aggregate member rate) costs a host timestamp,
            # zero additional syncs.
            stats.fetched(step_p, pos, active_members=int(lanes.sum()))
        return None

    def _quarantine(ms, step, reason) -> None:
        for m in ms:
            if valid[m]:
                valid[m] = False
                m_quarantined.inc()
                _emit("member_quarantined", step, member=int(m),
                      reason=reason, retries=int(retries[m]))
        if not valid.any():
            raise ResilienceError(
                f"run_ensemble: every member is quarantined (last at step "
                f"{step}, reason {reason!r}) — no batch left to serve.",
                events)

    def _restore_lanes(gen: pathlib.Path, lanes, loaded: Dict,
                       meta: dict) -> None:
        """Overwrite ONLY the given member lanes of the live state from an
        already-loaded generation — healthy lanes keep their device
        buffers bit-exactly (a `where`-select on the member axis)."""
        nonlocal state, gen_stale
        import jax

        gen_stale = True   # the newest generation no longer matches `state`

        restored = _state_from_loaded(loaded, meta, gen, pk, grid_fields,
                                      params)
        sel = np.zeros(members, dtype=bool)
        sel[list(lanes)] = True
        out = dict(state)
        for k in keys:
            m = jnp.asarray(sel).reshape((members,) + (1,) * (ndims[k] - 1))
            out[k] = jax.device_put(jnp.where(m, restored[k], state[k]),
                                    pk.sharding(ndims[k]))
        state = out

    def _find_lane_target(max_step: int, lanes) -> Optional[tuple]:
        """Newest generation at or below `max_step` whose *given lanes*
        are finite — the per-member analog of the round-8 rollback scan.
        Each candidate is read exactly ONCE (`load_checkpoint` CRC-verifies
        every shard it reads; an unreadable/corrupt candidate just falls
        through to the next) and the loaded arrays are returned for the
        restore to reuse: `(step, path, loaded, meta)`.  The load is
        ELASTIC (`redistribute=True` — a 1:1 restore on matching
        geometry): after an elastic resume the ring still holds
        generations written under the OLD decomposition, and those must
        stay valid rollback targets, not read as corrupt."""
        for s, p in reversed(_generations()):
            if s > max_step or not p.is_dir():
                continue
            meta = _read_sidecar(p)
            if meta is None or int(meta.get("members", -1)) != members:
                continue
            try:
                loaded = ckpt.load_checkpoint(p, redistribute=True)
            except GridError:
                continue
            if _lanes_finite(loaded, meta, grid_fields, params, lanes):
                return s, p, loaded, meta
        return None

    def _handle_failure(ev: Event, carry: Optional[List[int]] = None):
        """Per-member rollback: restore ONLY the diverged lanes from the
        newest lane-healthy generation and return the catch-up cohort
        `(members, from_step)` — or None when every failing member was
        quarantined instead.  `carry` is the cohort already mid-replay
        (a nested failure): those lanes are re-restored from the common
        target too, so the whole cohort replays from ONE uniform step —
        deterministic replay makes the extra distance bit-exact, never a
        divergence."""
        F = [m for m in ev.detail["members"] if valid[m]]
        if not F and not carry:
            return None
        exhausted = []
        for m in F:
            retries[m] += 1
            if retries[m] > member_retries:
                exhausted.append(m)
        _quarantine(exhausted, ev.step, reason="retry_budget")
        lanes = sorted({m for m in F + list(carry or []) if valid[m]})
        if not lanes:
            return None
        if cdir is None:
            _quarantine(lanes, ev.step, reason="no_rollback_target")
            return None
        target = _find_lane_target(ev.step, lanes)
        if target is None:
            _quarantine(lanes, ev.step, reason="no_rollback_target")
            return None
        s0, gen, loaded, meta = target
        pending.clear()
        m_rollbacks.inc()
        with _telemetry.span("ensemble.member_rollback", step=ev.step,
                             target_step=s0, lanes=len(lanes)):
            _restore_lanes(gen, lanes, loaded, meta)
        _emit("member_rollback", s0, members=lanes, from_step=ev.step,
              path=str(gen),
              attempts={str(m): int(retries[m]) for m in lanes})
        return lanes, s0

    installed = False
    old_handler = None
    if install_sigterm:
        try:
            old_handler = signal.signal(signal.SIGTERM, request_preemption)
            installed = True
        except ValueError:
            pass

    try:
        if cdir is not None and not resume:
            for _, old in _generations():
                ckpt.remove_generation(old)
        if checkpoint_every and steps_done != resumed_step:
            _save_gen(steps_done)

        cohort: Optional[List[int]] = None   # members replaying to the front
        pos = steps_done                     # the replaying cohort's step

        def _stepping():
            if cohort is not None:
                sel = np.zeros(members, dtype=bool)
                sel[[m for m in cohort if valid[m]]] = True
                return sel
            return valid.copy()

        mask_dev = _mask_for(_stepping())
        mask_sig = _stepping().tobytes()

        def _refresh_mask():
            nonlocal mask_dev, mask_sig
            sig = _stepping().tobytes()
            if sig != mask_sig:
                mask_dev = _mask_for(_stepping())
                mask_sig = sig

        while True:
            in_catchup = cohort is not None
            front_done = (not in_catchup) and steps_done >= n_steps
            if front_done or (preemption_requested() and not in_catchup):
                # Tail window: probe the final partial window, drain, and
                # isolate any straggler blowup before finishing.
                if (eprobe is not None and pos % watch_every != 0
                        and valid.any()):
                    _enqueue_probe(pos, _stepping())
                fail = _poll_probes(drain=True)
                if fail is not None:
                    got = _handle_failure(fail, carry=cohort)
                    if fail.kind == "integrity_violation":
                        # Handled — restored from a lane-healthy
                        # generation or quarantined; either way the
                        # verdict is no longer live (statusd recovers).
                        _emit("integrity_resolved", fail.step,
                              members=fail.detail.get("members"),
                              rolled_back=got is not None)
                    cohort, pos = got if got is not None else (
                        None, steps_done)
                    _refresh_mask()
                    continue
                if preemption_requested() and not front_done:
                    preempted = True
                break

            _refresh_mask()
            state_tap = _resilience._CHAOS_STATE_TAP
            if state_tap is not None:
                # Silent-corruption seam (igg.chaos.silent_corruption
                # with member=): one lane perturbed finitely.
                poisoned = state_tap(state, pos, _emit, steps_per_call)
                if poisoned is not state:
                    state = pk.put_state(poisoned)
            if chaos is not None:
                poisoned = chaos.apply(state, pos, _emit,
                                       span=steps_per_call)
                if poisoned is not state:
                    state = pk.put_state(poisoned)
                # Honor a (possibly chaos-injected) preemption before the
                # next dispatch — but only outside a catch-up replay: a
                # cohort must reach the front first (the loop's exit
                # condition requires it), else this skip would starve the
                # replay and spin forever.
                if preemption_requested() and not in_catchup:
                    continue

            _dispatch(mask_dev)
            pos += steps_per_call
            m_member_steps.inc(steps_per_call * int(_stepping().sum()))
            if not in_catchup:
                steps_done = pos
                m_steps.inc(steps_per_call)

            fail = None
            if eprobe is not None and pos % watch_every == 0:
                _enqueue_probe(pos, _stepping())
            if tel is not None:
                tel.maybe_export_metrics()   # one clock read when idle
            if fail is None:
                fail = _poll_probes()
            if fail is not None:
                got = _handle_failure(fail, carry=cohort)
                if fail.kind == "integrity_violation":
                    _emit("integrity_resolved", fail.step,
                          members=fail.detail.get("members"),
                          rolled_back=got is not None)
                if got is not None:
                    cohort, pos = got
                else:
                    # Every failing lane quarantined — and any cohort lane
                    # that survived was re-restored by _handle_failure, so
                    # a None here means no lane is left mid-replay.
                    cohort, pos = None, steps_done
                _refresh_mask()
                continue

            if in_catchup and pos >= steps_done:
                cohort, pos = None, steps_done
                _refresh_mask()
                continue

            if (not in_catchup and checkpoint_every
                    and pos % checkpoint_every == 0):
                _save_gen(pos)

        if preempted:
            if cdir is not None:
                have = (last_ckpt_step == steps_done) and not gen_stale
                if not have:
                    _save_gen(steps_done)
                else:
                    # Re-seal the lane metadata: quarantines since the
                    # cadence write must survive the relaunch.
                    old = _read_sidecar(last_ckpt) or {}
                    _write_sidecar(last_ckpt, {
                        **_sidecar_meta(steps_done),
                        "format": _SIDECAR_FORMAT,
                        "params": old.get("params", {}),
                    })
            _emit("preempt", steps_done,
                  path=str(last_ckpt) if last_ckpt else None)
            _telemetry._auto_dump(f"preempt at step {steps_done}")
        elif checkpoint_every and (steps_done % checkpoint_every != 0
                                   or gen_stale):
            # Off-cadence front, or a tail-window rollback replayed PAST
            # the cadence write at this step (its lanes are poisoned):
            # (re)write so `result.checkpoint` holds the returned state.
            _save_gen(steps_done)
        elif last_ckpt is not None:
            # A quarantine at the tail probe post-dates the final cadence
            # write: re-seal its lane metadata so a resume masks the NaN
            # lane instead of rejecting the generation.
            old = _read_sidecar(last_ckpt) or {}
            _write_sidecar(last_ckpt, {
                **_sidecar_meta(steps_done), "format": _SIDECAR_FORMAT,
                "params": old.get("params", {})})
    except BaseException as e:
        # ResilienceError (all members quarantined) and any unhandled
        # escape: dump the flight recorder wherever a sink is configured,
        # then re-raise — a ResilienceError additionally carries the dump
        # path(s), so the exception message NAMES the operator's first
        # postmortem artifact.
        paths = _telemetry._auto_dump(f"run_ensemble: "
                                      f"{type(e).__name__}: {e}")
        if isinstance(e, ResilienceError):
            e.dump_paths.extend(p for p in paths if p not in e.dump_paths)
        raise
    finally:
        if installed:
            signal.signal(signal.SIGTERM, old_handler)
            # Only the owner of the handler clears the shared flag: with
            # install_sigterm=False a scheduler (igg.run_fleet) owns the
            # wiring, and clearing here would swallow a SIGTERM that
            # landed after this run's last check — the fleet must still
            # see it and stop draining.
            clear_preemption()
        _telemetry.emit("run_finished", step=steps_done, run="ensemble",
                        preempted=preempted,
                        quarantined=sorted(int(m)
                                           for m in np.nonzero(~valid)[0]))
        if srv_owns:
            srv.stop()
        if tel is not None:
            # Owned sessions export inside detach(); exporting here too
            # would write two identical back-to-back snapshots.
            if tel_owns:
                tel.detach()
            else:
                tel.export_metrics()

    return EnsembleResult(
        state=state, members=members, steps_done=steps_done,
        retries={m: r for m, r in retries.items() if r},
        quarantined=sorted(int(m) for m in np.nonzero(~valid)[0]),
        preempted=preempted, events=events, checkpoint=last_ckpt,
        packing=pk.name)
