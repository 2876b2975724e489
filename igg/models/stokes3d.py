"""3-D staggered-grid Stokes solver (pseudo-transient iteration).

BASELINE config 5 ("3-D staggered-grid Stokes solver with comm/compute
overlap").  The classic ParallelStencil-style miniapp the reference is used
with: cell-centered pressure and normal stresses, face-staggered velocities,
edge-staggered shear stresses, iterated to steady state with pseudo-time
damping.  Per iteration the pressure and
velocities are exchanged — grouped into one call (`/root/reference/src/update_halo.jl:19-20`); the whole
iteration is one SPMD program, so XLA overlaps the three ppermute pairs with
the interior stress/velocity updates (the structural analog of
ParallelStencil's `@hide_communication`, `/root/reference/README.md:9`).

Buoyancy-driven setup: a dense spherical inclusion in a periodic box drives
a convection cell; the solver relaxes momentum + continuity residuals.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import numpy as np

import igg


@dataclasses.dataclass(frozen=True)
class Params:
    mu: float = 1.0          # viscosity
    rho_g: float = 1.0       # buoyancy contrast of the inclusion
    lx: float = 10.0
    ly: float = 10.0
    lz: float = 10.0
    vdamp: float = 4.0       # velocity damping (pseudo-transient accelerator)

    def spacing(self) -> Tuple[float, float, float]:
        return igg.tools.spacing(self.lx, self.ly, self.lz)


def init_fields(params: Params = Params(), dtype=np.float32):
    """Pressure/velocities at rest; buoyancy from a spherical inclusion."""
    import jax.numpy as jnp

    grid = igg.get_global_grid()
    nx, ny, nz = grid.nxyz
    dx, dy, dz = params.spacing()

    P = igg.zeros((nx, ny, nz), dtype=dtype)
    X, Y, Z = (a.astype(dtype) for a in igg.coord_fields(dx, dy, dz, P))
    r2 = ((X - params.lx / 2) ** 2 + (Y - params.ly / 2) ** 2
          + (Z - params.lz / 2) ** 2)
    Rho = params.rho_g * jnp.exp(-r2) + 0 * P   # smooth inclusion
    Vx = igg.zeros((nx + 1, ny, nz), dtype=dtype)
    Vy = igg.zeros((nx, ny + 1, nz), dtype=dtype)
    Vz = igg.zeros((nx, ny, nz + 1), dtype=dtype)
    return P, Vx, Vy, Vz, Rho


def divergence(Vx, Vy, Vz, *, dx, dy, dz):
    """The velocity divergence at cell centers (full pressure shape)."""
    return ((Vx[1:, :, :] - Vx[:-1, :, :]) / dx
            + (Vy[:, 1:, :] - Vy[:, :-1, :]) / dy
            + (Vz[:, :, 1:] - Vz[:, :, :-1]) / dz)


def iteration_core(P, Vx, Vy, Vz, Rho, *, dx, dy, dz, mu, dtP, dtV,
                   buoy_axis: int = 2):
    """The raw coupled arithmetic shared VERBATIM by the XLA path and the
    fused Pallas kernel (`igg.ops.stokes_pallas`) — one source of truth, so
    the two paths agree to Mosaic-vs-XLA rounding (~1 ulp).  Returns the
    full-shape updated pressure and the *interior* velocity increments
    `(P', rx, ry, rz)`; callers apply the increments with
    :func:`igg.ops.interior_add` (XLA) or interior ref writes (kernel).

    `buoy_axis` names the axis whose velocity the buoyancy term drives
    (physical z by default).  The arithmetic is otherwise symmetric under a
    y<->z swap of axes, fields, and spacings, which the fused kernel's
    transposed z-window send-plane computation exploits with
    `buoy_axis=1`."""
    divV = divergence(Vx, Vy, Vz, dx=dx, dy=dy, dz=dz)
    P = P - dtP * divV
    rx, ry, rz = momentum_residuals(P, Vx, Vy, Vz, Rho, divV, dx=dx, dy=dy,
                                    dz=dz, mu=mu, buoy_axis=buoy_axis)
    return P, dtV * rx, dtV * ry, dtV * rz


def momentum_residuals(P, Vx, Vy, Vz, B, divV, *, dx, dy, dz, mu,
                       buoy_axis: int = 2, buoy_sign: int = 1):
    """The momentum chain both pseudo-transient families share: the
    deviatoric stresses of the velocities (`divV` their divergence at
    cell centres) and the momentum residuals on the interior faces, the
    pressure `P` read as given, plus `buoy_sign` times the buoyancy `B`
    averaged onto the faces of the `buoy_axis` velocity.  Returns
    `(rx, ry, rz)`.  Stokes passes its updated P and Rho; HydroMech3D
    (`igg.models.hydromech3d`) its updated total pressure and, with
    `buoy_sign=-1`, its density Rog."""
    # Deviatoric normal stresses at centers
    txx = 2.0 * mu * ((Vx[1:, :, :] - Vx[:-1, :, :]) / dx - divV / 3.0)
    tyy = 2.0 * mu * ((Vy[:, 1:, :] - Vy[:, :-1, :]) / dy - divV / 3.0)
    tzz = 2.0 * mu * ((Vz[:, :, 1:] - Vz[:, :, :-1]) / dz - divV / 3.0)

    # Shear stresses on interior edges (no halo needed: computed locally
    # from halo-valid velocities, used only for interior velocity updates)
    txy = mu * ((Vx[1:-1, 1:, :] - Vx[1:-1, :-1, :]) / dy
                + (Vy[1:, 1:-1, :] - Vy[:-1, 1:-1, :]) / dx)
    txz = mu * ((Vx[1:-1, :, 1:] - Vx[1:-1, :, :-1]) / dz
                + (Vz[1:, :, 1:-1] - Vz[:-1, :, 1:-1]) / dx)
    tyz = mu * ((Vy[:, 1:-1, 1:] - Vy[:, 1:-1, :-1]) / dz
                + (Vz[:, 1:, 1:-1] - Vz[:, :-1, 1:-1]) / dy)

    # Momentum residuals on interior faces
    rx = ((txx[1:, 1:-1, 1:-1] - txx[:-1, 1:-1, 1:-1]) / dx
          + (txy[:, 1:, 1:-1] - txy[:, :-1, 1:-1]) / dy
          + (txz[:, 1:-1, 1:] - txz[:, 1:-1, :-1]) / dz
          - (P[1:, 1:-1, 1:-1] - P[:-1, 1:-1, 1:-1]) / dx)
    ry = ((tyy[1:-1, 1:, 1:-1] - tyy[1:-1, :-1, 1:-1]) / dy
          + (txy[1:, :, 1:-1] - txy[:-1, :, 1:-1]) / dx
          + (tyz[1:-1, :, 1:] - tyz[1:-1, :, :-1]) / dz
          - (P[1:-1, 1:, 1:-1] - P[1:-1, :-1, 1:-1]) / dy)
    rz = ((tzz[1:-1, 1:-1, 1:] - tzz[1:-1, 1:-1, :-1]) / dz
          + (txz[1:, 1:-1, :] - txz[:-1, 1:-1, :]) / dx
          + (tyz[1:-1, 1:, :] - tyz[1:-1, :-1, :]) / dy
          - (P[1:-1, 1:-1, 1:] - P[1:-1, 1:-1, :-1]) / dz)
    if buoy_axis == 2:                                   # buoyancy drives Vz
        bz = 0.5 * (B[1:-1, 1:-1, 1:] + B[1:-1, 1:-1, :-1])
        rz = rz + bz if buoy_sign > 0 else rz - bz
    else:                  # transposed windows: physical z sits on axis 1
        by = 0.5 * (B[1:-1, 1:, 1:-1] + B[1:-1, :-1, 1:-1])
        ry = ry + by if buoy_sign > 0 else ry - by
    return rx, ry, rz


def compute_iteration(P, Vx, Vy, Vz, Rho, *, dx, dy, dz, mu, dtP, dtV,
                      buoy_axis: int = 2):
    """The pure coupled update (no halo exchange): pressure then velocities,
    interior cells only — shift-invariant, so it applies both full-domain
    and to the boundary slabs of :func:`igg.hide_communication`.  Effective
    stencil radius is 2 (Gauss-Seidel flavor: the velocity updates read the
    freshly-updated pressure, which itself reads velocities at +-1)."""
    from igg.ops import interior_add

    P, dVx, dVy, dVz = iteration_core(P, Vx, Vy, Vz, Rho, dx=dx, dy=dy,
                                      dz=dz, mu=mu, dtP=dtP, dtV=dtV,
                                      buoy_axis=buoy_axis)
    Vx = interior_add(Vx, dVx)
    Vy = interior_add(Vy, dVy)
    Vz = interior_add(Vz, dVz)
    return P, Vx, Vy, Vz


def local_iteration(P, Vx, Vy, Vz, Rho, *, dx, dy, dz, mu, dtP, dtV,
                    overlap: bool = False, use_pallas: bool = False,
                    pallas_interpret: bool = False, assembly=None):
    """One pseudo-transient iteration over per-device local arrays.

    With `overlap=False`: compute, then one grouped exchange for everything
    that crosses device boundaries (multi-field pipelining,
    `/root/reference/src/update_halo.jl:19-20`).  With `overlap=True` the
    iteration is restructured by :func:`igg.hide_communication` (multi-field
    form) so the exchanges are data-independent of the full-domain stencils;
    the radius-2 update chain requires a grid initialized with
    overlap >= 3 (BASELINE config 5: "Stokes solver with comm/compute
    overlap").  With `use_pallas=True` the whole iteration (compute + the
    grouped halo update) runs as ONE fused kernel
    (`igg.ops.fused_stokes_iteration`, any mesh); it raises `GridError`
    when the kernel is inapplicable (the auto-fallback lives in
    :func:`make_iteration`)."""
    kw = dict(dx=dx, dy=dy, dz=dz, mu=mu, dtP=dtP, dtV=dtV)
    if use_pallas:
        from igg.ops import fused_stokes_iteration

        if overlap:
            raise igg.GridError(
                "the fused Stokes iteration has overlap "
                "(hide_communication) semantics built in; drop "
                "overlap=True when passing use_pallas.")
        _pallas_applicable(True, P, interpret=pallas_interpret)  # or raises
        return fused_stokes_iteration(P, Vx, Vy, Vz, Rho, **kw,
                                      interpret=pallas_interpret)
    if overlap:
        return igg.hide_communication(
            (P, Vx, Vy, Vz),
            lambda P, Vx, Vy, Vz, Rho: compute_iteration(P, Vx, Vy, Vz, Rho,
                                                         **kw),
            Rho, radius=2, assembly=assembly)
    P, Vx, Vy, Vz = compute_iteration(P, Vx, Vy, Vz, Rho, **kw)
    return igg.update_halo_local(P, Vx, Vy, Vz, assembly=assembly)


_PALLAS_REQ = (
    "the fused Stokes iteration requires TPU devices (or "
    "pallas_interpret=True), an overlap-3 grid, f32 fields with local "
    "shape divisible into x-slabs (x % 8 == 0, x >= 16, y >= 8, z >= 8), "
    "and in compiled mode a y*z area small enough that some slab height's "
    "windows fit the VMEM budget (igg.ops.stokes_pallas._vmem_need); use "
    "the XLA path otherwise.")

_TRAPEZOID_REQ = (
    "the K-iteration Stokes chunk tier requires the fused per-iteration "
    "kernel's prerequisites (TPU devices or pallas_interpret=True, "
    "overlap-3 grid, f32 fields) plus: n_inner >= K+1 (one warm-up "
    "iteration + at least one full chunk), tile-aligned local shape "
    "(x % 8 == 0, y % 8 == 0, z % 128 == 0), 2K-deep send slabs inside "
    "every split dimension's block, and a VMEM-resident working set for "
    "the five 2K-extended fields "
    "(igg.ops.stokes_trapezoid.stokes_trapezoid_supported); use "
    "trapezoid='auto' or the per-iteration kernel otherwise.")

def _pallas_applicable(use_pallas, P, interpret: bool = False) -> bool:
    from igg.ops import stokes_pallas_supported

    from ._dispatch import pallas_applicable

    # `pallas_applicable` threads `interpret` into the gate (no Mosaic,
    # no VMEM budget there), so large-y*z grids stay interpret-runnable.
    return pallas_applicable(use_pallas, P,
                             supported_fn=stokes_pallas_supported,
                             requirement=_PALLAS_REQ, interpret=interpret)


def _pseudo_steps(params: Params):
    dx, dy, dz = params.spacing()
    n_min = min(igg.nx_g(), igg.ny_g(), igg.nz_g())
    dtV = min(dx, dy, dz) ** 2 / params.mu / 8.1 / params.vdamp
    dtP = 4.1 * params.mu / n_min
    return dict(dx=dx, dy=dy, dz=dz, mu=params.mu, dtP=dtP, dtV=dtV)


def make_iteration(params: Params = Params(), *, donate: bool = True,
                   overlap="auto", n_inner: int = 1,
                   use_pallas="auto", pallas_interpret: bool = False,
                   trapezoid="auto", K: int = None, verify=None,
                   tune=None):
    """Compiled `(P, Vx, Vy, Vz, Rho) -> (P, Vx, Vy, Vz)` advancing
    `n_inner` iterations in one SPMD program.  `use_pallas`: "auto"
    (default) uses the fused kernel when it applies — TPU devices,
    overlap-3 grid, f32 fields, any device count/periodicity; False forces
    the portable shard_map/XLA path; True requires the kernel and raises if
    inapplicable.  `overlap` restructures the XLA path with
    `igg.hide_communication` ("auto" follows the `IGG_OVERLAP` knob, then
    the autotuner's cached winner — the Gauss-Seidel iteration has radius
    2, so admission needs an overlap-3 grid); the fused kernel has overlap
    semantics built in, so it satisfies both settings.

    `trapezoid` admits the K-iteration temporal-blocking chunk tier
    (`igg.ops.stokes_trapezoid`) on top of the fused kernel: "auto"
    (default) engages it when `stokes_trapezoid_supported` admits some K
    (one warm-up per-iteration kernel, `(n_inner-1) // K` chunks, the
    remainder through the per-iteration kernel); False pins the
    per-iteration kernel; True requires the chunk tier and raises
    `GridError` when inapplicable.  `K` overrides the auto-fitted chunk
    depth (`fit_stokes_K`).

    The factory's dispatch is the family's degradation ladder
    (`igg.degrade`): `stokes3d.trapezoid` → `stokes3d.mosaic` (the
    per-iteration fused kernel) → `stokes3d.xla` (the composition truth),
    so a quarantined chunk tier falls to the per-iteration kernel and a
    quarantined kernel falls to pure XLA.  `verify="first_use"` (or
    `IGG_VERIFY_KERNELS=1`) numerically checks each fast tier against the
    truth before it serves traffic.  `tune` consults the autotuner's
    cached winner for this signature ("auto"/True/False, default the
    `IGG_TUNE` knob; `igg.autotune`): a hit supplies the chunk depth `K`
    and may pin the tier when the caller left the defaults."""
    from jax import lax

    from igg.overlap import resolve_overlap

    from ._dispatch import apply_tuned

    K, K_from_cache, trapezoid, use_pallas, tuned = apply_tuned(
        "stokes3d", tune, n_inner=n_inner, interpret=pallas_interpret,
        K=K, chunk_knob=trapezoid, use_pallas=use_pallas,
        tiers=("trapezoid", "mosaic", "xla"))
    overlap = resolve_overlap(overlap, family="stokes3d", tuned=tuned,
                              radius=2, chunk_active=trapezoid is True)

    kw = _pseudo_steps(params)
    dx, dy, dz = kw["dx"], kw["dy"], kw["dz"]
    mu, dtP, dtV = kw["mu"], kw["dtP"], kw["dtV"]
    # NOTE: the step closures capture only hashable scalars so recreated
    # closures share one compiled program (`igg.parallel._fn_key`).

    def build_xla(assembly):
        def xla_it(P, Vx, Vy, Vz, Rho):
            return lax.fori_loop(
                0, n_inner,
                lambda _, S: local_iteration(*S, Rho, dx=dx, dy=dy, dz=dz,
                                             mu=mu, dtP=dtP, dtV=dtV,
                                             overlap=overlap,
                                             assembly=assembly),
                (P, Vx, Vy, Vz))

        return igg.sharded(xla_it,
                           donate_argnums=(0, 1, 2, 3) if donate else ())

    from ._dispatch import measured_assembly_path

    xla_path = measured_assembly_path(
        build_xla, tag=f"stokes3d:{n_inner}:{overlap}:{donate}",
        wrap=lambda fn: lambda P, Vx, Vy, Vz, Rho: (*fn(P, Vx, Vy, Vz, Rho),
                                                    Rho))

    if trapezoid is True and use_pallas is False:
        raise igg.GridError(_TRAPEZOID_REQ)
    if trapezoid is True:
        use_pallas = True    # the chunk tier rides the fused kernel

    donate_argnums = (0, 1, 2, 3) if donate else ()

    def _fit_K(grid, lshape, dtype):
        """The chunk depth the trapezoid tier will run (0 when none
        applies) — shared by the tier's admission gate and its traced
        body so the two can never disagree."""
        from igg.ops.stokes_trapezoid import (fit_stokes_K,
                                              stokes_trapezoid_supported)

        from ._dispatch import resolve_chunk_K

        if trapezoid is False or n_inner < 3:
            return 0
        return resolve_chunk_K(
            K, K_from_cache,
            lambda k: stokes_trapezoid_supported(
                grid, tuple(lshape), k, n_inner - 1, dtype,
                interpret=pallas_interpret),
            lambda: fit_stokes_K(grid, tuple(lshape), n_inner - 1, dtype,
                                 interpret=pallas_interpret))

    def admit_trapezoid(args):
        from igg.degrade import Admission
        from igg.ops import stokes_pallas_supported

        from ._dispatch import pallas_applicable

        if use_pallas is False:
            # The chunk tier rides the fused kernel: an explicit XLA pin
            # must reach the truth rung, not a Pallas-backed chunk (the
            # per-step tiers' probe enforces this for them; round 16
            # closed the same hole here).
            return Admission.no("use_pallas=False pins the XLA path")
        if trapezoid is False:
            return Admission.no("trapezoid=False pins the per-iteration "
                                "kernel")
        # Non-raising base probe ("auto", never the forced form): the
        # chunk tier rides the fused kernel, but a use_pallas=True refusal
        # belongs to the mosaic rung.
        base = pallas_applicable("auto", args[0],
                                 supported_fn=stokes_pallas_supported,
                                 requirement=_PALLAS_REQ,
                                 interpret=pallas_interpret)
        if not base:
            return Admission.no(f"fused per-iteration kernel (the chunk "
                                f"tier's carrier) inadmissible: "
                                f"{getattr(base, 'reason', '')}")
        if n_inner < 3:
            return Admission.no(f"n_inner={n_inner} < 3: no warm-up plus "
                                f"full chunk fits")
        grid = igg.get_global_grid()
        P = args[0]
        if not _fit_K(grid, grid.local_shape_any(P), P.dtype):
            return Admission.no(
                "no chunk depth K admissible "
                "(igg.ops.stokes_trapezoid_supported)")
        return Admission.yes()

    def build_trapezoid():
        from igg.ops import fused_stokes_iteration
        from igg.ops.stokes_pallas import stokes_iterations
        from igg.ops.stokes_trapezoid import fused_stokes_trapezoid_iters

        def trap_it(P, Vx, Vy, Vz, Rho):
            # Built inside the closure: the cells must stay hashable
            # scalars so recreated closures share one compiled program
            # (`igg.parallel._fn_key`, see the NOTE above).
            kw_it = dict(dx=dx, dy=dy, dz=dz, mu=mu, dtP=dtP, dtV=dtV)
            grid = igg.get_global_grid()
            Kf = _fit_K(grid, P.shape, P.dtype)   # local block inside sharded
            if not Kf:    # admission gate and trace share _fit_K
                raise igg.GridError(_TRAPEZOID_REQ)
            # Warm-up per-iteration kernel: consumes (and replaces) the
            # entry halos exactly like every other path — the
            # exchange-fresh window state the chunk's validity argument
            # requires, for ANY input.
            state = fused_stokes_iteration(
                P, Vx, Vy, Vz, Rho, **kw_it, interpret=pallas_interpret)
            *state, done = fused_stokes_trapezoid_iters(
                *state, Rho, n_inner=n_inner - 1, K=Kf, **kw_it,
                interpret=pallas_interpret)
            # The remainder through the per-iteration kernel.
            return stokes_iterations(state, Rho, n_inner - 1 - done,
                                     **kw_it, interpret=pallas_interpret)

        return igg.sharded(trap_it, donate_argnums=donate_argnums,
                           check_vma=not pallas_interpret)

    def build_pallas_steps():
        from igg.ops.stokes_pallas import stokes_iterations

        def pallas_it(P, Vx, Vy, Vz, Rho):
            kw_it = dict(dx=dx, dy=dy, dz=dz, mu=mu, dtP=dtP, dtV=dtV)
            return stokes_iterations((P, Vx, Vy, Vz), Rho, n_inner, **kw_it,
                                     interpret=pallas_interpret)

        return pallas_it

    from igg.degrade import Tier
    from igg.ops import stokes_pallas_supported

    from ._dispatch import auto_dispatch

    trap_tier = Tier(name="stokes3d.trapezoid", rung=0,
                     build=build_trapezoid, admit=admit_trapezoid,
                     required=trapezoid is True, requirement=_TRAPEZOID_REQ)
    return auto_dispatch(
        use_pallas=use_pallas, interpret=pallas_interpret,
        supported_fn=stokes_pallas_supported, requirement=_PALLAS_REQ,
        xla_path=xla_path, build_pallas_steps=build_pallas_steps,
        donate_argnums=donate_argnums,
        family="stokes3d", verify=verify,
        extra_tiers=(trap_tier,))


def residual_norms(P, Vx, Vy, Vz, Rho, *, dx, dy, dz, mu):
    """The four max-norms of the state's residuals over this device's
    block, reduced over the whole grid (`pmax`): the continuity residual
    `divergence` at cell centers and the momentum residuals of
    `iteration_core` (with `dtP=0`, so they read the pressure as given)
    on the faces the iteration updates.  Call inside SPMD code; every op
    runs under the `igg.residual` scope."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from igg import profiling

    with jax.named_scope(profiling.RESIDUAL):
        div = divergence(Vx, Vy, Vz, dx=dx, dy=dy, dz=dz)
        _, rx, ry, rz = iteration_core(P, Vx, Vy, Vz, Rho, dx=dx, dy=dy,
                                       dz=dz, mu=mu, dtP=0.0, dtV=1.0)
        norms = jnp.stack([jnp.max(jnp.abs(r)) for r in (div, rx, ry, rz)])
        return lax.pmax(norms, igg.AXIS_NAMES)


def residual(P, Vx, Vy, Vz, Rho, params: Params = Params()):
    """(max|div V|, max|Rx|, max|Ry|, max|Rz|) of the state, as host
    floats: the check the published pseudo-transient solver makes every
    `nout` iterations to decide whether to stop.  One `igg.sharded`
    program (the norms and their global `pmax`) and one read-back to the
    host, which waits for every dispatch queued before it.  Counts each
    check in `igg_residual_checks_total{family="stokes3d"}`."""
    import jax

    from igg import telemetry

    kw = _pseudo_steps(params)
    dx, dy, dz, mu = kw["dx"], kw["dy"], kw["dz"], kw["mu"]

    def norms(P, Vx, Vy, Vz, Rho):
        return residual_norms(P, Vx, Vy, Vz, Rho, dx=dx, dy=dy, dz=dz,
                              mu=mu)

    telemetry.counter("igg_residual_checks_total", family="stokes3d").inc()
    got = jax.device_get(igg.sharded(norms)(P, Vx, Vy, Vz, Rho))
    return tuple(float(v) for v in got)


def run(n_iters: int, params: Params = Params(), dtype=np.float32,
        overlap="auto", n_inner: int = 1, use_pallas="auto"):
    """Slope-timed relaxation (see :func:`igg.time_steps`); returns fields
    and seconds/iteration."""
    P, Vx, Vy, Vz, Rho = init_fields(params, dtype=dtype)
    it = make_iteration(params, overlap=overlap, n_inner=n_inner,
                        use_pallas=use_pallas)
    n1 = max(1, n_iters // 4)
    state, sec = igg.time_steps(
        lambda P, Vx, Vy, Vz, Rho: it(P, Vx, Vy, Vz, Rho) + (Rho,),
        (P, Vx, Vy, Vz, Rho), n1=n1, n2=max(n_iters - n1, n1 + 1))
    return state, sec / n_inner
