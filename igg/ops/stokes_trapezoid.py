"""K-iteration temporal-blocking (trapezoid chunk) tier for the Stokes
pseudo-transient solver.

`docs/stokes_roofline.md` proves the per-iteration fused kernel
(`stokes_pallas`, 0.143 ms/iter at 128^3 f32) is jointly DMA- and
VPU-bound with a ~2.1x-over-composition ceiling, and names the only
escape: temporal blocking.  This module is that escape — the four-field,
staggered-shapes instance of the shared K-step chunk engine
(`igg.ops.chunk_engine`):

  1. Once per K-iteration chunk, each device extends its block by
     `E = 2K` margin rows per extended dimension via ONE grouped
     `ppermute` pair per dimension per shape group (P and Vx share x-slab
     shapes and ride one permute, exactly like their shared plane shapes
     in `stokes_pallas`; Rho's loop-invariant extension is hoisted out of
     the chunk loop entirely) — `chunk_engine.extend_fields`.
  2. K coupled iterations run on the extended windows with NO exchange:
     per iteration the windows lose (at most) 2 rows of validity per
     extended side — the pseudo-transient chain (pressure update read by
     the velocity updates, Gauss-Seidel flavor) reads at most one row
     below and one above per iteration, so the `2K` margin over-provisions
     the front by 2x (the contract `igg.hide_communication` uses for the
     same chain, radius=2).  After K iterations the device's own block
     (interior AND halo rows) carries per-iteration-path values: every
     block row is produced by the identical `iteration_core` arithmetic
     the neighbor would apply to the same global cells.
  3. Wrap dims (single periodic device) re-apply the per-field staggered
     self-wrap each iteration; OPEN dims re-freeze the three velocity
     fields' boundary planes each iteration from VMEM-held chunk-entry
     freeze planes gated by SMEM `axis_index` edge flags (the
     "frozen"/"oext" mechanism the diffusion chunk kernel proved).
     Pressure is NOT frozen: the per-iteration path's full-shape pressure
     update writes the boundary plane too (its no-write halo fallback IS
     the computed plane — `stokes_pallas._sends_and_stales`), and the
     boundary-adjacent expressions read nothing beyond the frozen
     velocity planes, so a single frozen velocity plane per open side
     quarantines the beyond-domain shoulder garbage (worked through in
     `docs/stokes_roofline.md`).

Two realizations of the same window dynamics:

  - **Pure-XLA window path** (`_window_iters_xla`) — interpret mode, CPU
    meshes, the driver dryrun: `iteration_core` + `interior_add` on the
    full extended window per iteration through the engine's generic
    per-dim halo loop (`chunk_engine.window_chunk_xla`, velocities
    frozen on open dims).  This is the realization the 8-device mesh
    equivalence tests pin against `stokes3d.local_iteration`.
  - **Mosaic chunk kernel** — compiled mode: the engine's generic
    VMEM-resident banded kernel (`chunk_engine.resident_chunk_call`),
    instantiated with this family's config — five fields resident for
    the whole chunk (grid `(K, nb)`, "arbitrary" semantics), updated IN
    PLACE in x-row bands with a one-row lag buffer carrying each band's
    overwritten tail row to its successor (margin-1 windows, Vx's
    x-staggered high margin 2 — the per-iteration kernel's proven
    margins), velocities (fields 1-3) freeze-gated on open dims.  HBM
    traffic per chunk is ONE read of the five extended fields and ONE
    write of the four updated ones — `(5R+4W)/K` per iteration instead
    of the per-iteration kernel's `5R+4W`, the 1/K amortization the
    roofline demands.  `_band_update` — the shared per-band value
    computation — keeps `stokes3d.iteration_core` the single source of
    arithmetic truth, and is pinned against the window realization by
    the banded-scheme simulation in `tests/test_stokes_trapezoid.py`;
    the compiled instantiation is pinned on hardware by
    `tests/test_mega_tpu.py::test_stokes_trapezoid_matches_per_iteration`.

VMEM is the K-bound: the resident working set grows with `K` through the
`2K`-row extensions (plus the Vz lane padding the roofline documents), so
`stokes_trapezoid_supported` does the accounting against the shared
budget authority (`igg.ops._vmem.chunk_budget`) and `fit_stokes_K`
(`_vmem.fit_chunk_K`) picks the largest admissible K — at 128^3 f32 on
an `(N,1,1)` mesh that is K=8 (~70 MB modeled; K=16 would need the
2x-margin model past the 110 MB budget).  `docs/stokes_roofline.md`
carries the full analysis.

The compiled dispatcher (`stokes3d.make_iteration`) runs one per-iteration
fused kernel FIRST — consuming (and replacing) the entry halos exactly
like every other path, establishing the exchange-fresh window state the
validity argument requires — then `n_inner // K` chunks, then the
remainder through the per-iteration kernel.
"""

from __future__ import annotations

from functools import partial

from ._vmem import chunk_budget, fit_chunk_K
from .chunk_engine import (admit_chunk_common, admit_send_slabs,
                           admit_sublane_extension, band_halo,
                           dim_modes as _dim_modes, ext_shape as _ext_shape_e,
                           extend_dim_grouped, extend_fields, field_ols,
                           pad8 as _pad8, pad128 as _pad128,
                           resident_chunk_call, run_chunks, window_chunk_xla,
                           wrap_edges as _wrap_edges)

_BX = 8          # x band height of the chunk kernel (rows per program)

# Engine aliases (historical private names, still used by tests/benchmarks).
_extend_dim_grouped = extend_dim_grouped
_extend_fields = extend_fields


def _field_shapes(shape):
    """Local shapes of (P, Vx, Vy, Vz, Rho) from the unstaggered P shape."""
    S0, S1, S2 = shape
    return [(S0, S1, S2), (S0 + 1, S1, S2), (S0, S1 + 1, S2),
            (S0, S1, S2 + 1), (S0, S1, S2)]


def _ols(grid, shapes):
    """Per-field per-dim staggered overlaps (`ol(dim, A)`,
    `/root/reference/src/shared.jl:81`)."""
    return field_ols(grid, shapes)


def _ext_shape(s, E, modes):
    return _ext_shape_e(s, E, modes)


def _vmem_need(shape, K, modes, itemsize: int = 4) -> int:
    """Modeled VMEM bytes of the resident chunk kernel at iteration depth
    K: the five tile-padded extended fields (Vy sublane-padded, Vz
    lane-padded — the 2x physical footprint of its `(S,S,S+1)` shape the
    roofline documents), the per-field lag rows, the open-dim freeze
    planes, and a 2x-margin band-temporary term for `iteration_core`'s
    stress/residual chain (~16 band-row intermediates; the 2x absorbs
    Mosaic's own scratch, the same calibration style as
    `stokes_pallas._vmem_need`)."""
    E = 2 * K
    exts = [_ext_shape(s, E, modes) for s in _field_shapes(shape)]
    need = sum(a * _pad8(b) * _pad128(c) for a, b, c in exts) * itemsize
    row = _pad8(exts[2][1]) * _pad128(exts[3][2]) * itemsize
    need += 2 * 4 * row                       # lag rows (2 slots x 4 fields)
    for d in range(3):                        # freeze planes (3 velocities)
        if modes[d] in ("oext", "frozen"):
            for a, b, c in exts[1:4]:
                plane = (a, b, c)[:d] + (a, b, c)[d + 1:]
                need += 2 * _pad8(plane[0]) * _pad128(plane[1]) * itemsize
    need += 2 * 16 * (_BX + 3) * row          # band temporaries, 2x margin
    return need


def stokes_trapezoid_supported(grid, shape, K: int, n_inner: int, dtype,
                               interpret: bool = False):
    """Whether the K-iteration Stokes chunk tier applies: overlap-3 grid
    (the per-iteration kernel's prerequisite — it runs the warm-up and
    remainder iterations), at least one full chunk, `E = 2K` send slabs
    inside every extended dimension's block (per-field staggered ol), the
    kernel's band/tile-alignment geometry, and the resident working set
    within the VMEM budget.  Both realizations take the same gates so
    interpret meshes exercise the compiled tier's exact admission
    decisions (the `diffusion_trapezoid` convention).  Returns an
    :class:`igg.degrade.Admission` (truthy/falsy) carrying the structured
    refusal reason."""
    import numpy as np

    from ..degrade import Admission

    common = admit_chunk_common(grid, K, n_inner)
    if common is not None:
        return common
    if grid.overlaps != (3, 3, 3):
        return Admission.no(f"grid overlaps {grid.overlaps} != (3, 3, 3)")
    if tuple(shape) != tuple(grid.nxyz):
        return Admission.no(f"local shape {tuple(shape)} != grid block "
                            f"{tuple(grid.nxyz)}")
    if np.dtype(dtype) != np.float32:
        return Admission.no(f"dtype {np.dtype(dtype)} is not float32")
    modes = _dim_modes(grid)
    E = 2 * K
    S0, S1, S2 = shape
    if S0 % _BX != 0 or S0 < 2 * _BX:
        return Admission.no(f"x extent {S0} not band-divisible "
                            f"(needs S0 % {_BX} == 0, S0 >= {2 * _BX})")
    if S1 % 8 != 0 or S2 % 128 != 0:
        # Mosaic tile-aligned leading-dim VMEM slices (staggered trailing
        # extents are padded by the kernel; the base extents must align).
        return Admission.no(f"local y/z extents ({S1}, {S2}) not Mosaic "
                            f"tile-aligned (y % 8, z % 128)")
    if modes[0] != "frozen" and (2 * E) % _BX != 0:
        # S0e = S0 + 2E must stay band-divisible.
        return Admission.no(f"extended x span S0 + {2 * E} not "
                            f"band-divisible by {_BX}")
    sub = admit_sublane_extension(E, modes)
    if sub is not None:
        # Central y window slice offset must stay on sublane tiles (the
        # shared engine gate — a structured refusal where Mosaic would
        # crash deep in lowering).
        return sub
    shapes = _field_shapes(shape)
    ols = _ols(grid, shapes)
    slabs = admit_send_slabs(shapes, ols, E, modes, grid=grid)
    if slabs is not None:
        return slabs
    need = _vmem_need(shape, K, modes)
    if need > chunk_budget():
        return Admission.no(f"resident working set {need} bytes exceeds "
                            f"the VMEM budget {chunk_budget()}")
    return Admission.yes()


def fit_stokes_K(grid, shape, n_inner: int, dtype,
                 interpret: bool = False, kmax: int = 8) -> int:
    """Largest admissible chunk depth K <= kmax (halving, >= 2;
    `_vmem.fit_chunk_K`); 0 when none applies.  Even K keeps
    `S0e = S0 + 4K` band-divisible on extended-x meshes."""
    return fit_chunk_K(
        lambda K: stokes_trapezoid_supported(grid, tuple(shape), K, n_inner,
                                             dtype, interpret=interpret),
        kmax)


# ---------------------------------------------------------------------------
# The shared per-band value computation (single source of arithmetic truth
# with the per-iteration paths: stokes3d.iteration_core)
# ---------------------------------------------------------------------------

def _band_update(Wp, Wvx, Wvy, Wvz, Wrho, *, bx, scal):
    """New band values (rows [a, a+bx), window row offset 1) from margin-1
    windows: P/Vy/Vz/Rho window rows [a-1, a+bx+1), the x-staggered Vx
    [a-1, a+bx+2) — the per-iteration kernel's proven minimal margins
    (`stokes_pallas._kernel`: out row j <-> ext row j+1 <-> increment
    row j).  Rho's margin rows are dummies (read row-locally).  Pure
    values: shared verbatim by the Mosaic kernel and the banded-scheme
    simulation test."""
    import jax.numpy as jnp

    from ..models.stokes3d import iteration_core

    Pn, dVx, dVy, dVz = iteration_core(Wp, Wvx, Wvy, Wvz, Wrho, **scal)
    outs = [Pn[1:1 + bx]]
    for W, dV in ((Wvx, dVx), (Wvy, dVy), (Wvz, dVz)):
        o = W[1:1 + bx]
        inner = o[:, 1:-1, 1:-1] + dV[0:bx]
        mid = jnp.concatenate([o[:, 1:-1, 0:1], inner, o[:, 1:-1, -1:]],
                              axis=2)
        outs.append(jnp.concatenate([o[:, 0:1, :], mid, o[:, -1:, :]],
                                    axis=1))
    return tuple(outs)


def _band_halo(news, a, bx, flags, frx, fryz, cfg):
    """Per-band halo handling of the four new-band value arrays — the
    engine's generic `chunk_engine.band_halo` with this family's freeze
    set (the three velocities).  Kept as the historical entry point for
    the banded-scheme simulation test."""
    cfg = dict(cfg)
    cfg.setdefault("freeze_fields", (1, 2, 3))
    return band_halo(news, a, bx, flags, frx, fryz, cfg)


# ---------------------------------------------------------------------------
# Pure-XLA window realization (interpret mode / CPU meshes)
# ---------------------------------------------------------------------------

def _window_iters_xla(Pe, Vxe, Vye, Vze, Rhoe, *, K, E, modes, grid, scal,
                      ols, shapes):
    """K coupled iterations on the extended windows: full-window
    `iteration_core` + `interior_add`, then the engine's per-dim halo
    handling in dimension order — wrap dims self-wrap with per-field
    staggered ol; open dims re-freeze the VELOCITY shoulder+boundary band
    from the chunk-entry buffers on the global-edge devices (pressure is
    computed everywhere, its boundary value being the per-iteration
    path's computed no-write plane).  The freeze width differs from the
    Mosaic kernel (whole shoulder band vs exactly the boundary plane);
    the two agree on the central window because influence from the
    shoulder can only pass THROUGH the frozen boundary plane, which
    never reads it (the diffusion chunk kernel's quarantine argument,
    radius checked for the coupled chain in `docs/stokes_roofline.md`)."""
    from .stencil import interior_add

    def core(P, Vx, Vy, Vz):
        from ..models.stokes3d import iteration_core

        P, dVx, dVy, dVz = iteration_core(P, Vx, Vy, Vz, Rhoe, **scal)
        return (P, interior_add(Vx, dVx), interior_add(Vy, dVy),
                interior_add(Vz, dVz))

    return window_chunk_xla((Pe, Vxe, Vye, Vze), K=K, E=E, modes=modes,
                            grid=grid, ols=ols, shapes=shapes,
                            freeze_fields=(1, 2, 3), core=core)


# ---------------------------------------------------------------------------
# The Mosaic chunk realization: the engine's generic resident banded kernel
# ---------------------------------------------------------------------------

def _chunk_call(exts, Rho_ext, *, K, modes, grid, scal, ols, shapes,
                interpret=False):
    """Advance K coupled iterations on the extended buffers; returns the
    four central local blocks.  Compiled mode runs the engine's generic
    VMEM-resident banded kernel with this family's config (4 updated
    fields + const Rho, Vx's x-staggered high margin 2, velocities
    frozen on open dims); interpret mode runs the pure-XLA window
    realization."""
    E = 2 * K

    def window():
        return _window_iters_xla(*exts, Rho_ext, K=K, E=E, modes=modes,
                                 grid=grid, scal=scal, ols=ols,
                                 shapes=shapes)

    return resident_chunk_call(
        list(exts), [Rho_ext], K=K, bx=_BX, modes=modes, grid=grid,
        ols=ols, shapes=shapes, E=E,
        band_update=partial(_band_update, scal=scal),
        extras=(1, 2, 1, 1, 1), freeze_fields=(1, 2, 3),
        window_fallback=window, interpret=interpret)


# ---------------------------------------------------------------------------
# Chunk driver
# ---------------------------------------------------------------------------

def fused_stokes_trapezoid_iters(P, Vx, Vy, Vz, Rho, *, n_inner: int,
                                 K: int, dx, dy, dz, mu, dtP, dtV,
                                 interpret: bool = False):
    """Advance `n_inner // K` full K-iteration chunks (the caller handles
    the warm-up iteration before and the per-K remainder after, through
    the per-iteration fused kernel); returns
    `(P, Vx, Vy, Vz, iters_done)`.

    Entry contract — OVERLAP-CONSISTENT, exchange-fresh state: the
    duplicated overlap-region rows must be globally equal (every state
    reachable from a global-coordinates init — `init_fields`,
    `igg.coord_fields` — through per-iteration evolution is; an
    overlap-3 grid exchanges one plane per side, so `update_halo` alone
    cannot synchronize arbitrary interior duplicates, and the chunk
    windows read the NEIGHBOR's copy of those rows where the
    per-iteration path reads the local one).  The compiled dispatcher's
    warm-up iteration re-establishes halo freshness; consistency is an
    invariant of the model paths.  Call inside SPMD code (`igg.sharded`
    / shard_map); fully-frozen 1-device grids also run under plain
    `jax.jit`."""
    from .. import shared

    grid = shared.global_grid()
    modes = _dim_modes(grid)
    E = 2 * K
    shapes = _field_shapes(P.shape)
    ols = _ols(grid, shapes)
    scal = dict(dx=dx, dy=dy, dz=dz, mu=mu, dtP=dtP, dtV=dtV)

    # Rho never changes: its extension (one grouped ppermute set) is
    # hoisted out of the chunk loop entirely.
    Rho_ext = extend_fields([Rho], [ols[4]], E, grid, modes)[0]

    def one(P, Vx, Vy, Vz):
        exts = extend_fields([P, Vx, Vy, Vz], ols[:4], E, grid, modes)
        return _chunk_call(exts, Rho_ext, K=K, modes=modes, grid=grid,
                           scal=scal, ols=ols, shapes=shapes,
                           interpret=interpret)

    *S, done = run_chunks((P, Vx, Vy, Vz), n_inner=n_inner, K=K,
                          one_chunk=one)
    return (*S, done)
