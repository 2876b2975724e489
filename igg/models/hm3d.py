"""3-D nonlinear two-field compaction (a stand-in for porous flow).

A two-field compaction model: effective pressure `Pe` diffusing through a
porosity field `phi` with porosity-dependent (cubic) permeability, coupled
back through compaction.  It has no solid velocities and no total
pressure; the published two-phase model of BASELINE config 4
(ParallelStencil's HydroMech3D miniapp) is `igg.models.hydromech3d`.
Two mutually-coupled fields exchanged in one grouped halo update per step;
the nonlinear face permeabilities make the stencil state-dependent, unlike
the constant-coefficient diffusion flagship.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import numpy as np

import igg


@dataclasses.dataclass(frozen=True)
class Params:
    phi0: float = 0.1        # background porosity
    npow: int = 3            # permeability exponent k ~ (phi/phi0)^n
    eta: float = 1.0         # compaction viscosity
    lx: float = 10.0
    ly: float = 10.0
    lz: float = 10.0

    def spacing(self) -> Tuple[float, float, float]:
        return igg.tools.spacing(self.lx, self.ly, self.lz)

    def timestep(self) -> float:
        dx, dy, dz = self.spacing()
        # Permeability k = (phi/phi0)^n reaches 8 at the initial 2*phi0
        # anomaly and keeps growing while compaction feeds the porosity
        # wave; the divisor bounds k*dt/dx^2 with headroom for that growth
        # (long runs at k up to ~25 stay stable).
        return min(dx * dx, dy * dy, dz * dz) / 8.1 / 32.0


def init_fields(params: Params = Params(), dtype=np.float32):
    """Gaussian porosity anomaly in a uniform background; Pe at rest."""
    import jax.numpy as jnp

    grid = igg.get_global_grid()
    nx, ny, nz = grid.nxyz
    dx, dy, dz = params.spacing()

    Pe0 = igg.zeros((nx, ny, nz), dtype=dtype)
    X, Y, Z = (a.astype(dtype) for a in igg.coord_fields(dx, dy, dz, Pe0))
    r2 = ((X - params.lx / 2) ** 2 + (Y - params.ly / 2) ** 2
          + (Z - params.lz / 3) ** 2)
    phi = params.phi0 * (1.0 + 1.0 * jnp.exp(-r2)) + 0 * Pe0
    Pe = -0.5 * jnp.exp(-r2) + 0 * Pe0    # under-pressured anomaly
    return Pe, phi


def step_core(Pe, phi, *, dx, dy, dz, dt, phi0, npow, eta):
    """The coupled increments `(dPe, dphi)` on a window's interior cells:
    radius-1 shift-invariant, the single source of arithmetic truth shared
    by the XLA step, the `hide_communication` slabs, and the fused Pallas
    kernel (`igg.ops.hm3d_pallas`)."""
    k = (phi / phi0) ** npow
    # Face permeabilities (arithmetic mean) and Darcy fluxes on inner faces
    kx = 0.5 * (k[1:, 1:-1, 1:-1] + k[:-1, 1:-1, 1:-1])
    ky = 0.5 * (k[1:-1, 1:, 1:-1] + k[1:-1, :-1, 1:-1])
    kz = 0.5 * (k[1:-1, 1:-1, 1:] + k[1:-1, 1:-1, :-1])
    qx = -kx * (Pe[1:, 1:-1, 1:-1] - Pe[:-1, 1:-1, 1:-1]) / dx
    qy = -ky * (Pe[1:-1, 1:, 1:-1] - Pe[1:-1, :-1, 1:-1]) / dy
    qz = -kz * (Pe[1:-1, 1:-1, 1:] - Pe[1:-1, 1:-1, :-1]) / dz
    divq = ((qx[1:, :, :] - qx[:-1, :, :]) / dx
            + (qy[:, 1:, :] - qy[:, :-1, :]) / dy
            + (qz[:, :, 1:] - qz[:, :, :-1]) / dz)
    inner = (slice(1, -1),) * 3
    # Fluid mass balance: Pe relaxes by Darcy flow + compaction closure;
    # compaction: porosity responds to the (updated) effective pressure
    # (Gauss-Seidel coupling).
    dPe = dt * (-divq - Pe[inner] * phi[inner] / eta)
    Pe_new = Pe[inner] + dPe
    dphi = dt * (-phi[inner] * (1.0 - phi[inner]) * Pe_new / eta)
    return dPe, dphi


def compute_step(Pe, phi, *, dx, dy, dz, dt, phi0, npow, eta):
    """The pure coupled update (no halo exchange): radius-1 shift-invariant,
    usable full-domain and on :func:`igg.hide_communication` slabs."""
    from igg.ops import interior_add

    dPe, dphi = step_core(Pe, phi, dx=dx, dy=dy, dz=dz, dt=dt, phi0=phi0,
                          npow=npow, eta=eta)
    return interior_add(Pe, dPe), interior_add(phi, dphi)


def local_step(Pe, phi, *, dx, dy, dz, dt, phi0, npow, eta,
               overlap: bool = False, use_pallas: bool = False,
               pallas_interpret: bool = False, assembly=None):
    """One coupled step over per-device local arrays; two mutually-coupled
    fields in one grouped halo update (multi-field pipelining,
    `/root/reference/src/update_halo.jl:19-20`).  `overlap=True`
    restructures with the multi-field :func:`igg.hide_communication`
    (BASELINE config 4's weak-scaling workload).  `use_pallas=True` runs
    the whole step (compute + grouped halo update) as ONE fused kernel
    (`igg.ops.fused_hm3d_step`, any mesh); it raises `GridError` when the
    kernel is inapplicable (the auto-fallback lives in :func:`make_step`)."""
    kw = dict(dx=dx, dy=dy, dz=dz, dt=dt, phi0=phi0, npow=npow, eta=eta)
    if use_pallas:
        from igg.ops import fused_hm3d_step

        if overlap:
            raise igg.GridError(
                "the fused HM3D step has overlap (hide_communication) "
                "semantics built in; drop overlap=True when passing "
                "use_pallas.")
        _pallas_applicable(True, Pe, interpret=pallas_interpret)  # or raises
        return fused_hm3d_step(Pe, phi, **kw, interpret=pallas_interpret)
    if overlap:
        return igg.hide_communication(
            (Pe, phi), lambda Pe, phi: compute_step(Pe, phi, **kw),
            assembly=assembly)
    return igg.update_halo_local(*compute_step(Pe, phi, **kw),
                                 assembly=assembly)


_PALLAS_REQ = (
    "the fused HM3D step requires TPU devices (or pallas_interpret=True), "
    "an overlap-2 grid, and f32 unstaggered fields with local shape "
    "divisible into x-slabs (x % 4 == 0, y >= 8, z >= 8; z >= 128 when z "
    "is exchanged), and in compiled mode a y*z area small enough that some "
    "slab height's windows fit the VMEM budget "
    "(igg.ops.hm3d_pallas._vmem_need); use the XLA path otherwise.")


def _pallas_applicable(use_pallas, Pe, interpret: bool = False) -> bool:
    from igg.ops import hm3d_pallas_supported

    from ._dispatch import pallas_applicable

    # `pallas_applicable` threads `interpret` into the gate (no Mosaic,
    # no VMEM budget there), so large-y*z grids stay interpret-runnable.
    return pallas_applicable(use_pallas, Pe,
                             supported_fn=hm3d_pallas_supported,
                             requirement=_PALLAS_REQ, interpret=interpret)


_TRAPEZOID_REQ = (
    "the K-step HM3D chunk tier requires the fused per-step kernel's "
    "prerequisites (TPU devices or pallas_interpret=True, overlap-2 "
    "grid, f32 fields) plus: n_inner >= K+1 (one warm-up step + at "
    "least one full chunk), band/tile-aligned local shape (x % 8 == 0, "
    "y % 8 == 0, z % 128 == 0), K-deep send slabs inside every split "
    "dimension's block, and a VMEM-resident working set for the two "
    "K-extended fields (igg.ops.hm3d_trapezoid.hm3d_trapezoid_supported)"
    "; use trapezoid='auto' or the per-step kernel otherwise.")


def make_step(params: Params = Params(), *, donate: bool = True,
              overlap="auto", n_inner: int = 1,
              use_pallas="auto", pallas_interpret: bool = False,
              trapezoid="auto", K: int = None, verify=None, tune=None):
    """Compiled `(Pe, phi) -> (Pe, phi)` advancing `n_inner` steps in one
    SPMD program.  `use_pallas`: "auto" (default) uses the fused kernel
    (`igg.ops.fused_hm3d_steps`, with boundary-slab carry) when it applies —
    TPU devices, overlap-2 grid, f32 fields, any device count/periodicity;
    False forces the portable shard_map/XLA path; True requires the kernel
    and raises if inapplicable.  `overlap` restructures the XLA path with
    `igg.hide_communication` ("auto" follows the `IGG_OVERLAP` knob, then
    the autotuner's cached winner, else off); the fused kernel has overlap
    semantics built in (its exchange is always data-independent of the
    main kernel), so it satisfies both settings — exactly like
    diffusion3d.
    `verify`: "first_use" numerically checks the fused tier against the
    XLA composition before it serves traffic (`igg.degrade`; defaults to
    the `IGG_VERIFY_KERNELS` environment knob).

    `trapezoid` admits the K-step temporal-blocking chunk tier
    (`igg.ops.hm3d_trapezoid`, round 16 — generated from the shared
    chunk engine) on top of the fused kernel: "auto" (default) engages
    it when `hm3d_trapezoid_supported` admits some K (one warm-up
    per-step kernel, `(n_inner-1) // K` chunks, the remainder through
    the per-step kernel); False pins the per-step kernel; True requires
    the chunk tier and raises `GridError` when inapplicable.  `K`
    overrides the auto-fitted chunk depth (`fit_hm3d_K`).  `tune`
    consults the autotuner's cached winner for this signature
    ("auto"/True/False; `igg.autotune`)."""
    from jax import lax

    dx, dy, dz = params.spacing()
    dt = params.timestep()
    phi0, npow, eta = params.phi0, params.npow, params.eta
    # NOTE: the step closures capture only hashable scalars so recreated
    # closures share one compiled program (`igg.parallel._fn_key`).

    from igg.overlap import resolve_overlap

    from ._dispatch import apply_tuned

    K, K_from_cache, trapezoid, use_pallas, tuned = apply_tuned(
        "hm3d", tune, n_inner=n_inner, interpret=pallas_interpret, K=K,
        chunk_knob=trapezoid, use_pallas=use_pallas,
        tiers=("trapezoid", "mosaic", "xla"))
    overlap = resolve_overlap(overlap, family="hm3d", tuned=tuned,
                              radius=1, chunk_active=trapezoid is True)

    def build_xla(assembly):
        def xla_steps(Pe, phi):
            return lax.fori_loop(
                0, n_inner,
                lambda _, S: local_step(*S, dx=dx, dy=dy, dz=dz, dt=dt,
                                        phi0=phi0, npow=npow, eta=eta,
                                        overlap=overlap, assembly=assembly),
                (Pe, phi))

        return igg.sharded(xla_steps,
                           donate_argnums=(0, 1) if donate else ())

    from ._dispatch import measured_assembly_path

    xla_path = measured_assembly_path(
        build_xla, tag=f"hm3d:{n_inner}:{overlap}:{donate}",
        wrap=lambda fn: fn)

    def build_pallas_steps():
        from igg.ops import fused_hm3d_steps

        def pallas_steps(Pe, phi):
            return fused_hm3d_steps(
                Pe, phi, n_inner=n_inner, dx=dx, dy=dy, dz=dz, dt=dt,
                phi0=phi0, npow=npow, eta=eta, interpret=pallas_interpret)

        return pallas_steps

    if trapezoid is True and use_pallas is False:
        raise igg.GridError(_TRAPEZOID_REQ)
    if trapezoid is True:
        use_pallas = True    # the chunk tier rides the fused kernel

    donate_argnums = (0, 1) if donate else ()

    def _fit_K(grid, lshape, dtype):
        """The chunk depth the trapezoid tier will run (0 when none
        applies) — shared by the tier's admission gate and its traced
        body so the two can never disagree."""
        from igg.ops.hm3d_trapezoid import (fit_hm3d_K,
                                            hm3d_trapezoid_supported)

        from ._dispatch import resolve_chunk_K

        if trapezoid is False or n_inner < 3:
            return 0
        return resolve_chunk_K(
            K, K_from_cache,
            lambda k: hm3d_trapezoid_supported(
                grid, tuple(lshape), k, n_inner - 1, dtype,
                interpret=pallas_interpret),
            lambda: fit_hm3d_K(grid, tuple(lshape), n_inner - 1, dtype,
                               interpret=pallas_interpret))

    def admit_trapezoid(args):
        from igg.degrade import Admission
        from igg.ops import hm3d_pallas_supported

        from ._dispatch import pallas_applicable

        if use_pallas is False:
            return Admission.no("use_pallas=False pins the XLA path")
        if trapezoid is False:
            return Admission.no("trapezoid=False pins the per-step "
                                "kernel")
        base = pallas_applicable("auto", args[0],
                                 supported_fn=hm3d_pallas_supported,
                                 requirement=_PALLAS_REQ,
                                 interpret=pallas_interpret)
        if not base:
            return Admission.no(f"fused per-step kernel (the chunk "
                                f"tier's carrier) inadmissible: "
                                f"{getattr(base, 'reason', '')}")
        if n_inner < 3:
            return Admission.no(f"n_inner={n_inner} < 3: no warm-up plus "
                                f"full chunk fits")
        grid = igg.get_global_grid()
        Pe = args[0]
        if not _fit_K(grid, grid.local_shape_any(Pe), Pe.dtype):
            return Admission.no(
                "no chunk depth K admissible "
                "(igg.ops.hm3d_trapezoid.hm3d_trapezoid_supported)")
        return Admission.yes()

    def build_trapezoid():
        from igg.ops import fused_hm3d_step
        from igg.ops.hm3d_trapezoid import fused_hm3d_trapezoid_steps

        def trap_steps(Pe, phi):
            kw_it = dict(dx=dx, dy=dy, dz=dz, dt=dt, phi0=phi0,
                         npow=npow, eta=eta)
            grid = igg.get_global_grid()
            Kf = _fit_K(grid, Pe.shape, Pe.dtype)
            if not Kf:    # admission gate and trace share _fit_K
                raise igg.GridError(_TRAPEZOID_REQ)
            # Warm-up per-step kernel: consumes (and replaces) the entry
            # halos exactly like every other path — the exchange-fresh
            # window state the chunk's validity argument requires, for
            # ANY input.
            Pe, phi = fused_hm3d_step(Pe, phi, **kw_it,
                                      interpret=pallas_interpret)
            Pe, phi, done = fused_hm3d_trapezoid_steps(
                Pe, phi, n_inner=n_inner - 1, K=Kf, **kw_it,
                interpret=pallas_interpret)
            n = n_inner - 1 - done
            if n:    # remainder through the per-step kernel
                Pe, phi = lax.fori_loop(
                    0, n,
                    lambda _, S: fused_hm3d_step(
                        *S, **kw_it, interpret=pallas_interpret),
                    (Pe, phi))
            return Pe, phi

        return igg.sharded(trap_steps, donate_argnums=donate_argnums,
                           check_vma=not pallas_interpret)

    from igg.degrade import Tier
    from igg.ops import hm3d_pallas_supported

    from ._dispatch import auto_dispatch

    trap_tier = Tier(name="hm3d.trapezoid", rung=0,
                     build=build_trapezoid, admit=admit_trapezoid,
                     required=trapezoid is True,
                     requirement=_TRAPEZOID_REQ)
    return auto_dispatch(
        use_pallas=use_pallas, interpret=pallas_interpret,
        supported_fn=hm3d_pallas_supported, requirement=_PALLAS_REQ,
        xla_path=xla_path, build_pallas_steps=build_pallas_steps,
        donate_argnums=donate_argnums,
        family="hm3d", verify=verify,
        extra_tiers=(trap_tier,))


def run(nt: int, params: Params = Params(), dtype=np.float32,
        overlap="auto", n_inner: int = 1, use_pallas="auto"):
    """Slope-timed run (see :func:`igg.time_steps`)."""
    Pe, phi = init_fields(params, dtype=dtype)
    step = make_step(params, overlap=overlap, n_inner=n_inner,
                     use_pallas=use_pallas)
    n1 = max(1, nt // 4)
    state, sec = igg.time_steps(step, (Pe, phi),
                                n1=n1, n2=max(nt - n1, n1 + 1))
    return state, sec / n_inner
