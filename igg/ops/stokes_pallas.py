"""Fused Pallas Stokes iteration — mesh-capable (any dims / periodicity).

One `pallas_call` performs a full pseudo-transient Stokes iteration —
pressure update, six stresses, three momentum residuals, velocity updates,
AND the grouped halo update of P/Vx/Vy/Vz — reading each field once and
writing each updated field once.  The XLA composition
(`stokes3d.local_iteration`: compute + `update_halo_local(P,Vx,Vy,Vz)`)
costs ~0.27 ms/iter at 128^3 on v5e, of which the 4-field halo phase alone
is ~0.26 ms measured in isolation (each field pays its own read+write
assembly pass, plus XLA's multi-field layout copies); the fused kernel's
traffic is the ideal 5 reads + 4 writes.

This is the TPU re-expression of the reference's native-kernel performance
tier (">10x faster" than the array-broadcast form,
`/root/reference/README.md:161`) for BASELINE config 5's Stokes solver, on
*every* rank of a decomposed run — the per-rank property of the
reference's native tier — not just the single-device configuration.

Measured on v5e at 128^3 f32 (median-of-3, 100-iteration dispatches,
self-wrap grid): **0.143 ms/iter** vs 0.224 for the XLA composition
(1.57x; round-5 artifact refresh — the round-5 ext-plane writer gate
also sped the composition itself up from 0.278);
matches the XLA path to ~1e-7 relative on the chip (identical
`iteration_core` arithmetic).  The DMA floor of this structure measured
with a no-op core is 0.108 ms (~790 GB/s on ~85 MB/iter of traffic,
including the 2x lane padding of Vz's (S,S,S+1) shape), so the remaining
gap to ideal is non-overlapped VPU time.  `docs/stokes_roofline.md`
carries the full traffic accounting: the structure is jointly DMA- and
VPU-bound and its ceiling is ~2.1x over the round-5 composition — no
per-iteration kernel of this solver reaches 3x at f32 128^3; only
temporal blocking or bf16 break the bound.

Structure (the radius-2 staggered four-field instance of the
`diffusion_pallas` recipe):

1. **Send planes from thin-window recomputation** — the updated inner
   boundary planes `ol-1` / `s-ol` of each exchanged field (per-field
   staggered `ol`, reference `/root/reference/src/shared.jl:81`) are
   produced by `compute_iteration` on 5-cell-row windows (the staggered
   field of the window's axis contributes 6 face rows), O(s²) work
   data-independent of the main kernel.  z windows are computed TRANSPOSED
   (axes 1<->2, Vy/Vz slots and dy/dz swapped, `buoy_axis=1` keeping the
   buoyancy on physical Vz), yielding the squeezed z planes directly — a
   `(S0,S1,5)` window would be lane-padded ~26x through the whole radius-2
   temporary chain.
2. **Dimension-sequential plane exchange** — `exchange_all_dims_grouped`
   over the four fields (P and Vx share plane shapes and ride one
   ppermute; Vy/Vz planes are staggered-shaped), with corner/edge
   propagation, open-boundary stale fallbacks, and self-wrap local copies
   (`/root/reference/src/update_halo.jl:36,130,516-532`).
3. **Fused compute + assembly kernel** — grid over x-slabs of `bx` rows;
   each program reads its slab plus 2 (3 for the x-staggered Vx) margin
   rows per side as single-row block refs with modular index maps — edge
   programs read wrapped rows whose results land only in halo rows that
   the halo phase overwrites.  The slab arithmetic is LITERALLY
   `stokes3d.iteration_core` — one source of truth with the XLA path.
   Received planes are assembled in dimension order: x planes by the edge
   programs, then y rows, then z columns winning the shared corners.
   Per-dimension halo modes as in `diffusion_pallas`: y/z dims periodic
   with a single device are in-VMEM self-wrap aliases (per-field staggered
   `ol`); exchanged or open dims take received/stale planes as blocked
   inputs.  Vx's extra global row `S0` lies outside the block grid; it is
   the x-side `s-1` halo row, assembled after the kernel from the received
   x plane with the y/z updates applied on top (one cheap dim-0 DUS).

Semantics match :func:`igg.hide_communication` exactly — which for the
slice-based `iteration_core` means identical to the plain sequential
composition *everywhere*, including the open-boundary planes that the
full-shape pressure update writes (the no-write fallback planes are
window-computed, see `_sends_and_stales`); decomposition invariance holds
on any mesh.

Requirements: overlap 3 (the radius-2 chain), unstaggered-pressure 3-D
local blocks large enough to slab, equal f32 dtypes; any device count and
periodicity.  Multi-device z decompositions pay a per-iteration strided
z-window extraction (~2 lane-tile passes); prefer `(N,1,1)`/`(N,M,1)`
meshes where z stays device-local, as with the diffusion kernel.
"""

from __future__ import annotations

from functools import partial

from .diffusion_pallas import _wrap_dims, _wrap_set

# Deliberately TIGHT when the working set allows: the scoped-vmem budget
# steers Mosaic's scheduling, and a small budget produces far better
# DMA/compute interleaving for this kernel.  Swept on v5e at 128^3
# (median-of-3, ms/iter): 20MB 0.138, 26MB 0.137, 32MB 0.136, 44MB 0.139,
# 56MB 0.157, 64MB 0.175, 100MB 0.224, 128MB 0.40.  At 128^3 the working
# set sits below 20MB and the floor budget applies; larger y*z areas
# (256^3-class) NEED more than the floor — the per-call limit grows with
# `_vmem_need` up to the hard cap (round 5: 256^3 OOM'd at Mosaic compile
# under the fixed 32MB budget; with the grown 82MB budget it runs
# bx=8 at 2.19 ms/iter vs 7.42 XLA — 3.4x — the shipped-and-measured
# configuration).
from ._vmem import fit_bx, vmem_limit


def _vmem_limit(bx: int, S1: int, S2: int) -> int:
    return vmem_limit(_vmem_need(bx, S1, S2))


def _vmem_need(bx: int, S1: int, S2: int, itemsize: int = 4) -> int:
    """VMEM bytes the fused iteration's windows demand at slab height
    `bx`: per input field (P,Vx,Vy,Vz) a bx-row center window plus 2-3
    single-row side windows, a bx-row Rho window, four bx-row outputs —
    all double-buffered — plus ~10 single-buffered plane windows.  The
    row count is `9*bx + 10` to first order; the 2.0x margin absorbs
    Mosaic's own scratch (calibrated against the observed 256^3 compile
    footprint: 69.3 MB demanded where the first-order model says 41)."""
    rows = 9 * bx + 10
    return int(2 * rows * S1 * S2 * itemsize * 2.0)


def _fit_bx(bx: int, S0: int, S1: int, S2: int,
            check_vmem: bool = True) -> int:
    return fit_bx(_vmem_need, bx, S0, S1, S2, min_bx=4,
                  check_vmem=check_vmem)


def stokes_pallas_supported(grid, P, interpret: bool = False):
    """Whether the fused iteration applies: overlap-3 grid (any device
    count and any periodicity — the exchange engine handles open boundaries
    and multi-device meshes), unstaggered-pressure local block large enough
    to slab, and some slab height whose windows fit VMEM (large y*z areas
    push the per-slab windows past the budget — caught by the round-5
    256^3 probe, where the unguarded kernel OOM'd at Mosaic compile).
    Returns an :class:`igg.degrade.Admission` (truthy/falsy) carrying the
    structured refusal reason."""
    from ..degrade import Admission

    if grid.overlaps != (3, 3, 3):
        return Admission.no(f"grid overlaps {grid.overlaps} != (3, 3, 3)")
    if P.ndim != 3:
        return Admission.no(f"pressure rank {P.ndim} != 3")
    s = tuple(grid.local_shape_any(P))
    if s != tuple(grid.nxyz):
        return Admission.no(f"staggered local shape {s} != grid block "
                            f"{tuple(grid.nxyz)}")
    if not (s[0] % 8 == 0 and s[0] >= 16 and s[1] >= 8 and s[2] >= 8):
        return Admission.no(f"local block {s} too small to slab "
                            f"(needs x % 8 == 0, x >= 16, y >= 8, z >= 8)")
    if _fit_bx(8, s[0], s[1], s[2], check_vmem=not interpret) < 4:
        return Admission.no(f"no slab height bx >= 4 fits the VMEM budget "
                            f"for local y*z area {s[1]}x{s[2]}")
    return Admission.yes()


def _win_x(P, Vx, Vy, Vz, Rho, scal, lo, hi):
    """`compute_iteration` on the contiguous x window of cell rows
    [lo, hi) (Vx contributes hi+1 face rows): valid updated cell rows are
    the window interior."""
    from jax import lax

    from ..models.stokes3d import compute_iteration

    cut = lambda A: lax.slice_in_dim(A, lo, hi, axis=0)
    cutx = lambda A: lax.slice_in_dim(A, lo, hi + 1, axis=0)
    return compute_iteration(cut(P), cutx(Vx), cut(Vy), cut(Vz), cut(Rho),
                             **scal)


def _win_y(P, Vx, Vy, Vz, Rho, scal, lo, hi):
    from jax import lax

    from ..models.stokes3d import compute_iteration

    cut = lambda A: lax.slice_in_dim(A, lo, hi, axis=1)
    cuty = lambda A: lax.slice_in_dim(A, lo, hi + 1, axis=1)
    return compute_iteration(cut(P), cut(Vx), cuty(Vy), cut(Vz), cut(Rho),
                             **scal)


def _win_z(P, Vx, Vy, Vz, Rho, scal, lo, hi):
    """TRANSPOSED z window: axes 1<->2, Vy/Vz slots and dy/dz swapped,
    buoyancy kept on physical Vz via `buoy_axis=1`.  Returns the updated
    transposed windows in PHYSICAL field order (P, Vx, Vy, Vz)."""
    import jax.numpy as jnp
    from jax import lax

    from ..models.stokes3d import compute_iteration

    cut = lambda A: jnp.swapaxes(lax.slice_in_dim(A, lo, hi, axis=2), 1, 2)
    cutz = lambda A: jnp.swapaxes(lax.slice_in_dim(A, lo, hi + 1, axis=2),
                                  1, 2)
    swapped = dict(scal)
    swapped["dy"], swapped["dz"] = scal["dz"], scal["dy"]
    Pt, Vxt, Vzt, Vyt = compute_iteration(
        cut(P), cut(Vx), cutz(Vz), cut(Vy), cut(Rho), **swapped,
        buoy_axis=1)
    return Pt, Vxt, Vyt, Vzt


def _sends_and_stales(P, Vx, Vy, Vz, Rho, scal, wrap_yz):
    """Keepdims send planes (updated inner planes `ol-1` / `s-ol`, staggered
    per field) and the open-boundary no-write fallback planes for the four
    exchanged fields, as parallel lists of `{(dim, side): plane}` dicts for
    `exchange_all_dims_grouped`.  Wrapped y/z dims need neither.

    The fallback planes are the *window-computed* outermost planes, NOT the
    pre-iteration ones: the full-shape pressure update writes its outermost
    planes too, and the plain composition (reference no-write semantics,
    `/root/reference/test/test_update_halo.jl:727-732`) keeps those computed
    values at an open boundary.  Window row values equal full-array row
    values because `iteration_core` is slice-based (see `igg.overlap`,
    same contract)."""
    import jax.numpy as jnp

    wy, wz = wrap_yz
    S0, S1, S2 = P.shape
    sends = [{}, {}, {}, {}]
    stales = [{}, {}, {}, {}]

    def put(side, d, planes, stale_planes):
        for i, pl_ in enumerate(planes):
            sends[i][(d, side)] = pl_
        for i, pl_ in enumerate(stale_planes):
            stales[i][(d, side)] = pl_

    # x: low window cells [0,5) -> updated row 2 (= ol-1) for P/Vy/Vz, row 3
    # for the x-staggered Vx (ol=4); high window cells [S0-5,S0) -> updated
    # row S0-3 (= s-ol) for every field.  Fallbacks: the windows' outermost
    # updated planes (local 0 low; local 4, or 5 for the staggered field,
    # high).
    Pw, Vxw, Vyw, Vzw = _win_x(P, Vx, Vy, Vz, Rho, scal, 0, 5)
    put(0, 0, (Pw[2:3], Vxw[3:4], Vyw[2:3], Vzw[2:3]),
        (Pw[0:1], Vxw[0:1], Vyw[0:1], Vzw[0:1]))
    Pw, Vxw, Vyw, Vzw = _win_x(P, Vx, Vy, Vz, Rho, scal, S0 - 5, S0)
    put(1, 0, (Pw[2:3], Vxw[2:3], Vyw[2:3], Vzw[2:3]),
        (Pw[4:5], Vxw[5:6], Vyw[4:5], Vzw[4:5]))

    if not wy:
        Pw, Vxw, Vyw, Vzw = _win_y(P, Vx, Vy, Vz, Rho, scal, 0, 5)
        put(0, 1, (Pw[:, 2:3], Vxw[:, 2:3], Vyw[:, 3:4], Vzw[:, 2:3]),
            (Pw[:, 0:1], Vxw[:, 0:1], Vyw[:, 0:1], Vzw[:, 0:1]))
        Pw, Vxw, Vyw, Vzw = _win_y(P, Vx, Vy, Vz, Rho, scal, S1 - 5, S1)
        put(1, 1, (Pw[:, 2:3], Vxw[:, 2:3], Vyw[:, 2:3], Vzw[:, 2:3]),
            (Pw[:, 4:5], Vxw[:, 4:5], Vyw[:, 5:6], Vzw[:, 4:5]))
    if not wz:
        ex = lambda W, j: jnp.expand_dims(W[:, j, :], 2)
        Pw, Vxw, Vyw, Vzw = _win_z(P, Vx, Vy, Vz, Rho, scal, 0, 5)
        put(0, 2, (ex(Pw, 2), ex(Vxw, 2), ex(Vyw, 2), ex(Vzw, 3)),
            (ex(Pw, 0), ex(Vxw, 0), ex(Vyw, 0), ex(Vzw, 0)))
        Pw, Vxw, Vyw, Vzw = _win_z(P, Vx, Vy, Vz, Rho, scal, S2 - 5, S2)
        put(1, 2, (ex(Pw, 2), ex(Vxw, 2), ex(Vyw, 2), ex(Vzw, 2)),
            (ex(Pw, 4), ex(Vxw, 4), ex(Vyw, 4), ex(Vzw, 5)))
    return sends, stales


def _kernel(*refs, bx, nb, shapes, scal, wrap_y, wrap_z):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from ..models.stokes3d import iteration_core
    from .diffusion_pallas import _ref_taker

    take = _ref_taker(refs)

    # Extended slabs: rows [a-1, a+bx+1) of each field (the x-staggered Vx
    # one row more).  Minimal margins — out rows that would read beyond them
    # are halo rows overwritten below.  Rho is read row-locally, so its
    # margin rows are dummies taken from the center block (values unused).
    m1, cP, p1 = take(3)
    eP = jnp.concatenate([m1[:], cP[:], p1[:]], axis=0)
    m1, cVx, p1, p2 = take(4)
    eVx = jnp.concatenate([m1[:], cVx[:], p1[:], p2[:]], axis=0)
    m1, cVy, p1 = take(3)
    eVy = jnp.concatenate([m1[:], cVy[:], p1[:]], axis=0)
    m1, cVz, p1 = take(3)
    eVz = jnp.concatenate([m1[:], cVz[:], p1[:]], axis=0)
    (cRho,) = take(1)
    r = cRho[:]
    eRho = jnp.concatenate([r[0:1], r, r[0:1]], axis=0)
    pf, vxf, vyf, vzf = take(4)        # x first planes (squeezed)
    pl_, vyl, vzl = take(3)            # x last planes (Vx's is post-kernel)
    y_in = take(0 if wrap_y else 8)    # (P f,l, Vx f,l, Vy f,l, Vz f,l)
    z_in = take(0 if wrap_z else 8)
    oP, oVx, oVy, oVz = take(4)

    Pn, dVx, dVy, dVz = iteration_core(eP, eVx, eVy, eVz, eRho, **scal)

    # Output rows j ↔ ext rows j+1; increments are on the ext interior
    # (offset 1), so out row j ↔ increment row j.
    oP[:] = Pn[1:1 + bx]
    for o_ref, ext, dV in ((oVx, eVx, dVx), (oVy, eVy, dVy), (oVz, eVz, dVz)):
        o_ref[:] = ext[1:1 + bx]
        o_ref[:, 1:-1, 1:-1] = (ext[1:1 + bx, 1:-1, 1:-1]
                                + dV[0:bx])

    i = pl.program_id(0)

    # x halo planes (dimension-sequential: x first, y/z own shared cells).
    @pl.when(i == 0)
    def _():
        oP[0:1] = pf[:][None]
        oVx[0:1] = vxf[:][None]
        oVy[0:1] = vyf[:][None]
        oVz[0:1] = vzf[:][None]

    @pl.when(i == nb - 1)
    def _():
        oP[bx - 1:bx] = pl_[:][None]
        oVy[bx - 1:bx] = vyl[:][None]
        oVz[bx - 1:bx] = vzl[:][None]
        # Vx's last halo row is global row S0, outside the block grid —
        # written by the caller after the kernel.

    # y halo rows (full x/z extent; z writes own the shared cells below).
    if wrap_y:
        for o_ref, (_, sy, sz), oly in ((oP, shapes[0], 3),
                                        (oVx, shapes[1], 3),
                                        (oVy, shapes[2], 4),
                                        (oVz, shapes[3], 3)):
            o_ref[:, 0:1, :] = o_ref[:, sy - oly:sy - oly + 1, :]
            o_ref[:, sy - 1:sy, :] = o_ref[:, oly - 1:oly, :]
    else:
        for o_ref, (_, sy, _), f, l in (
                (oP, shapes[0], y_in[0], y_in[1]),
                (oVx, shapes[1], y_in[2], y_in[3]),
                (oVy, shapes[2], y_in[4], y_in[5]),
                (oVz, shapes[3], y_in[6], y_in[7])):
            o_ref[:, 0:1, :] = jnp.expand_dims(f[:], 1)
            o_ref[:, sy - 1:sy, :] = jnp.expand_dims(l[:], 1)
    # z halo columns (own all shared corners).
    if wrap_z:
        for o_ref, (_, _, sz), olz in ((oP, shapes[0], 3),
                                       (oVx, shapes[1], 3),
                                       (oVy, shapes[2], 3),
                                       (oVz, shapes[3], 4)):
            o_ref[:, :, 0:1] = o_ref[:, :, sz - olz:sz - olz + 1]
            o_ref[:, :, sz - 1:sz] = o_ref[:, :, olz - 1:olz]
    else:
        for o_ref, (_, _, sz), f, l in (
                (oP, shapes[0], z_in[0], z_in[1]),
                (oVx, shapes[1], z_in[2], z_in[3]),
                (oVy, shapes[2], z_in[4], z_in[5]),
                (oVz, shapes[3], z_in[6], z_in[7])):
            o_ref[:, :, 0:1] = jnp.expand_dims(f[:], 2)
            o_ref[:, :, sz - 1:sz] = jnp.expand_dims(l[:], 2)


def fused_stokes_iteration(P, Vx, Vy, Vz, Rho, *, dx, dy, dz, mu, dtP, dtV,
                           bx: int = 8, interpret: bool = False):
    """One fused Stokes pseudo-transient iteration
    `(P, Vx, Vy, Vz, Rho) -> (P', Vx', Vy', Vz')` with halo maintenance
    included, on any mesh (see module docstring).  Call inside SPMD code
    (`igg.sharded` / shard_map); on a 1-device grid the exchange
    degenerates to local copies and the function also works under plain
    `jax.jit`.  Matches `stokes3d.local_iteration(..., overlap=True)` to
    Mosaic-vs-XLA rounding (overlap semantics are built in)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from .. import shared
    from ..halo import active_dims, exchange_all_dims_grouped

    grid = shared.global_grid()
    S0, S1, S2 = P.shape
    # Shrink the slab height until it divides S0 AND (compiled mode) its
    # windows fit the VMEM budget, which scales with S1*S2 (`_vmem_need`).
    bx = _fit_bx(bx, S0, S1, S2, check_vmem=not interpret)
    if bx < 4:
        raise ValueError(
            f"x size {S0} not divisible into slabs of >= 4 rows whose "
            f"windows fit VMEM at y*z area {S1}x{S2}")
    nb = S0 // bx
    scal = dict(dx=dx, dy=dy, dz=dz, mu=mu, dtP=dtP, dtV=dtV)
    shapes = [P.shape, Vx.shape, Vy.shape, Vz.shape, Rho.shape]
    wrap_yz = _wrap_dims(grid)
    wy, wz = wrap_yz
    wrap = _wrap_set(wrap_yz)

    fields = [P, Vx, Vy, Vz]
    sends, stales = _sends_and_stales(P, Vx, Vy, Vz, Rho, scal, wrap_yz)
    dims_actives = [active_dims(F.shape, grid) for F in fields]
    recvs = exchange_all_dims_grouped(
        [F.shape for F in fields], sends, dims_actives, grid,
        stales=stales, wraps=[wrap] * 4, blocks=fields)
    rq = [{d: (jnp.squeeze(a, d), jnp.squeeze(b, d))
           for d, (a, b) in r.items()} for r in recvs]

    operands, in_specs = [], []
    for F in (P, Vx, Vy, Vz, Rho):
        sx = F.shape[0]
        yz = F.shape[1:]
        if F is Rho:
            rows = ["c"]                    # row-local reads only
        elif F is Vx:
            rows = [-1, "c", bx, bx + 1]    # staggered: one extra top row
        else:
            rows = [-1, "c", bx]
        for r in rows:
            operands.append(F)
            if r == "c":
                in_specs.append(pl.BlockSpec((bx, *yz),
                                             lambda i: (i, 0, 0)))
            else:
                in_specs.append(pl.BlockSpec(
                    (1, *yz),
                    lambda i, rr=r, ss=sx: ((i * bx + rr) % ss, 0, 0)))
    # x planes: first of all four fields, last of P/Vy/Vz (Vx's handled
    # after the kernel).
    x_planes = [rq[0][0][0], rq[1][0][0], rq[2][0][0], rq[3][0][0],
                rq[0][0][1], rq[2][0][1], rq[3][0][1]]
    for pln in x_planes:
        operands.append(pln)
        in_specs.append(pl.BlockSpec(pln.shape, lambda i: (0, 0)))
    if not wy:
        for k in range(4):
            for side in (0, 1):
                pln = rq[k][1][side]        # squeezed (sx, S2)
                operands.append(pln)
                in_specs.append(pl.BlockSpec((bx, pln.shape[1]),
                                             lambda i: (i, 0)))
    if not wz:
        for k in range(4):
            for side in (0, 1):
                pln = rq[k][2][side]        # squeezed (sx, sy)
                operands.append(pln)
                in_specs.append(pl.BlockSpec((bx, pln.shape[1]),
                                             lambda i: (i, 0)))

    vmas = [getattr(getattr(x, "aval", None), "vma", None) for x in operands]
    vma = frozenset().union(*[v for v in vmas if v])

    def shp(dims, dt):
        return (jax.ShapeDtypeStruct(dims, dt, vma=vma) if vma
                else jax.ShapeDtypeStruct(dims, dt))

    # Vx's out_shape is its full (S0+1) extent; the block grid covers rows
    # [0, S0) and the caller writes row S0 below.
    out_shape = [shp(F.shape, F.dtype) for F in (P, Vx, Vy, Vz)]
    out_specs = [pl.BlockSpec((bx, *s[1:]), lambda i: (i, 0, 0))
                 for s in (P.shape, Vx.shape, Vy.shape, Vz.shape)]

    kwargs = {}
    if not interpret:
        from jax.experimental.pallas import tpu as pltpu
        kwargs["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(bx, S1, S2),
            dimension_semantics=("parallel",))

    Pn, Vxn, Vyn, Vzn = pl.pallas_call(
        partial(_kernel, bx=bx, nb=nb, shapes=shapes[:4], scal=scal,
                wrap_y=wy, wrap_z=wz),
        name="stokes3d_iteration",
        grid=(nb,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        **kwargs,
    )(*operands)

    # Vx's outside halo row (global S0): the x-side `s-1` halo row, from the
    # received x plane, with the later dimensions' updates applied on top —
    # the sequential-dimension semantics for a row outside the block grid.
    vx_out = rq[1][0][1]                   # (S1, S2)
    if wy:
        vx_out = _wrap_row(vx_out, 0, S1, 3)
    else:
        vx_out = vx_out.at[0, :].set(rq[1][1][0][S0, :])
        vx_out = vx_out.at[S1 - 1, :].set(rq[1][1][1][S0, :])
    if wz:
        vx_out = _wrap_row(vx_out, 1, S2, 3)
    else:
        vx_out = vx_out.at[:, 0].set(rq[1][2][0][S0, :])
        vx_out = vx_out.at[:, S2 - 1].set(rq[1][2][1][S0, :])
    from jax import lax

    Vxn = lax.dynamic_update_slice_in_dim(Vxn, vx_out[None], S0, axis=0)
    return Pn, Vxn, Vyn, Vzn


def _wrap_row(v, axis, size, ol):
    """Periodic self-wrap of the outermost rows of a plane along `axis`."""
    import jax.numpy as jnp
    from jax import lax

    idx = lax.broadcasted_iota(jnp.int32, v.shape, axis)
    v = jnp.where(idx == 0, lax.slice_in_dim(v, size - ol, size - ol + 1,
                                             axis=axis), v)
    return jnp.where(idx == size - 1,
                     lax.slice_in_dim(v, ol - 1, ol, axis=axis), v)
