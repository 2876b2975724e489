"""3-D two-phase hydro-mechanics: ParallelStencil.jl's HydroMech3D miniapp.

BASELINE config 4 ("3-D hydro-mechanical porous flow (ParallelStencil.jl
HM3D), v5p-64 weak scaling").  A viscous solid (Stokes) coupled to a
Darcy fluid through the porosity `Phi`: the buoyant porosity anomaly
rises as a porosity wave.  The pseudo-transient (PT) system, in the
miniapp's notation:

    EtaC  <- (1-th_e)*EtaC  + th_e*(mus/Phi*eta2mus*(1 + (1/R-1)/2
                                   *(1 + tanh((Pf - Pt)/lamPe))))
    K_muf <- (1-th_k)*K_muf + th_k*k_muf0*(Phi/phi0)^nperm
    Rog   =  rhofg*Phi + rhosg*(1 - Phi) - rhogBG
    qD_i  =  -avg_i(K_muf)*(d_i Pf (+ rhofg - rhogBG on z))
    RPt   =  -div V - (Pt - Pf)/(EtaC*(1 - Phi))
    RPf   <- Pfdmp*RPf - div qD + (Pt - Pf)/(EtaC*(1 - Phi))
    Pt    += dtauPt*RPt ;  Pf += min(dx,dy,dz)^2/maxloc(K_muf)/6.1/Pfsc*RPf
    R_i   =  d_j tau_ij - d_i Pt (- avg_z(Rog) on z)
    dV_i  <- damp_i*dV_i + R_i ;  V_i += dtauV*dV_i

and once per physical step, between PT solves, the porosity update
`Phi <- Phi_o + (1 - Phi_o)*(CN*div V_o + (1 - CN)*div V)*dt`.

The state a PT iteration carries is `STATE`: total and fluid pressure,
the damped fluid residual, the relaxed bulk viscosity and permeability at
cell centres, and the solid velocities and their damped accumulators on
faces; the porosity `Phi` is read only.  The Darcy flux is not state: the
flux an iteration writes is `-avg(K_muf)*(grad Pf + g)` of the `K_muf` and
`Pf` it leaves, so the next iteration recomputes it from them, bit for
bit.  That keeps every update within one cell of the state it reads (the
`maxloc` reads `K_muf` one cell away, the flux divergence `Pf` and
`K_muf`), so the grid's one-plane halos suffice at overlap 3.

The momentum chain is Stokes's own (`stokes3d.momentum_residuals`, the
total pressure in place of P and `-Rog` as the buoyancy).  The family's
ladder is `hydromech3d.mosaic` (`igg.ops.hydromech_pallas`, one fused
kernel an iteration over x-slabs, an HBM ping-pong across iterations) →
`hydromech3d.xla` (this module's composition, the truth rung).
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import numpy as np

import igg

from .stokes3d import divergence, momentum_residuals

STATE = ("Pt", "Pf", "RPf", "EtaC", "K_muf", "Vx", "Vy", "Vz",
         "dVx", "dVy", "dVz")
# The fields whose halo planes the iteration exchanges: those it updates
# on interior cells only.  Pt, EtaC and K_muf are updated on every cell
# from halo-valid inputs, so their halo planes are right without one.
EXCHANGED = ("Pf", "RPf", "Vx", "Vy", "Vz", "dVx", "dVy", "dVz")


@dataclasses.dataclass(frozen=True)
class Params:
    """The miniapp's constants (its names in the comments)."""
    rho_fg: float = 1.0      # rhofg: fluid rho*g
    k_muf0: float = 1.0      # k_muf0: reference permeability
    eta_c0: float = 1.0      # etaC0: reference bulk viscosity
    eta2mus: float = 10.0    # eta2mus: bulk/shear viscosity ratio
    R: float = 500.0         # compaction/decompaction strength ratio
    nperm: float = 3.0       # Carman-Kozeny exponent
    phi0: float = 0.01       # reference porosity
    lam_pe: float = 0.01     # lamPe: effective pressure transition zone
    rho_sg: float = 2.0      # rhosg: solid rho*g
    theta_e: float = 0.9     # relaxation of the bulk viscosity
    theta_k: float = 0.1     # relaxation of the permeability
    beta_n: float = 1.0      # numerical compressibility
    vdmp: float = 5.0        # Vdmp: velocity damping
    pfdmp: float = 0.8       # Pfdmp: fluid pressure damping
    vsc: float = 2.0         # Vsc: reduction of the velocity PT step
    ptsc: float = 2.0        # Ptsc: reduction of the total pressure PT step
    pfsc: float = 4.0        # Pfsc: reduction of the fluid pressure PT step
    cn: float = 0.5          # CN: Crank-Nicolson weight of the porosity
    dt_red: float = 1e-3     # dt_red: reduction of the physical step
    lx: float = 20.0
    ly: float = 20.0
    lz: float = 40.0

    @property
    def mu_s(self) -> float:
        """Solid shear viscosity etaC0*phi0/eta2mus."""
        return self.eta_c0 * self.phi0 / self.eta2mus

    @property
    def rho_gbg(self) -> float:
        """Background density rhofg*phi0 + rhosg*(1 - phi0)."""
        return self.rho_fg * self.phi0 + self.rho_sg * (1.0 - self.phi0)

    def spacing(self) -> Tuple[float, float, float]:
        return igg.tools.spacing(self.lx, self.ly, self.lz)


def pseudo_steps(params: Params = Params()) -> dict:
    """The scalars an iteration reads, as hashable floats: spacings, the
    PT steps `dtauV = min(d)^2/mus/(1+beta_n)/6.1/Vsc`, `dtauPt =
    6.1*mus*(1+beta_n)/n/Ptsc` (n the smallest global grid size), the
    fluid step's numerator `min(d)^2/6.1/Pfsc`, the per-axis velocity
    damping `1 - Vdmp/n_i` and the material constants."""
    dx, dy, dz = params.spacing()
    n = (igg.nx_g(), igg.ny_g(), igg.nz_g())
    d2 = min(dx, dy, dz) ** 2
    mu = params.mu_s
    return dict(
        dx=dx, dy=dy, dz=dz, mu=mu,
        dtau_v=d2 / mu / (1.0 + params.beta_n) / 6.1 / params.vsc,
        dtau_pt=6.1 * mu * (1.0 + params.beta_n) / min(n) / params.ptsc,
        dtau_pf=d2 / 6.1 / params.pfsc,
        damp_x=1.0 - params.vdmp / n[0], damp_y=1.0 - params.vdmp / n[1],
        damp_z=1.0 - params.vdmp / n[2],
        eta_s=mu * params.eta2mus, r_fac=0.5 * (1.0 / params.R - 1.0),
        lam_pe=params.lam_pe, theta_e=params.theta_e,
        theta_k=params.theta_k, k_muf0=params.k_muf0, phi0=params.phi0,
        nperm=params.nperm, pfdmp=params.pfdmp, rho_fg=params.rho_fg,
        rho_sg=params.rho_sg, rho_gbg=params.rho_gbg,
        g_z=params.rho_fg - params.rho_gbg)


def init_fields(params: Params = Params(), dtype=np.float32):
    """The state at rest and the porosity: `phi0` plus the miniapp's
    anomaly (amplitude `2*phi0`, four times wider in x and y than in z,
    centred at (lx/2, ly/2, lz/4)) as a Gaussian less its box mean, so
    that the background density removes the mean buoyancy; `EtaC` and
    `K_muf` at the porosity's values, everything else zero.  Returns
    `(state, Phi)`, `state` in `STATE` order."""
    import jax.numpy as jnp

    grid = igg.get_global_grid()
    nx, ny, nz = grid.nxyz
    dx, dy, dz = params.spacing()
    widths = (4.0, 4.0, 1.0)
    centre = (params.lx / 2, params.ly / 2, params.lz / 4)
    n_g = (igg.nx_g(), igg.ny_g(), igg.nz_g())
    mean = float(np.prod([np.exp(-((np.arange(n) * h - c) / w) ** 2).mean()
                          for n, h, c, w in zip(n_g, (dx, dy, dz), centre,
                                                widths)]))
    Pt = igg.zeros((nx, ny, nz), dtype=dtype)
    X, Y, Z = (a.astype(dtype) for a in igg.coord_fields(dx, dy, dz, Pt))
    g = (jnp.exp(-((X - centre[0]) / widths[0]) ** 2)
         * jnp.exp(-((Y - centre[1]) / widths[1]) ** 2)
         * jnp.exp(-((Z - centre[2]) / widths[2]) ** 2))
    Phi = (params.phi0 + 2.0 * params.phi0 * (g - mean)
           + 0 * Pt).astype(dtype)
    EtaC = (params.mu_s / Phi * params.eta2mus).astype(dtype)
    K_muf = (params.k_muf0 * (Phi / params.phi0)).astype(dtype)
    face = [igg.zeros(tuple(n + (d == i)
                            for d, n in enumerate((nx, ny, nz))), dtype=dtype)
            for i in range(3)]
    state = (Pt, Pt + 0, Pt + 0, EtaC, K_muf, *face,
             *(f + 0 for f in face))
    return state, Phi


def _pow(x, n):
    """`x**n`, as repeated products where n is a whole number."""
    return x ** int(n) if float(n).is_integer() else x ** n


def maxloc(K):
    """The largest of each interior cell and its six neighbours."""
    import jax.numpy as jnp

    c = K[1:-1, 1:-1, 1:-1]
    m = jnp.maximum(jnp.maximum(K[:-2, 1:-1, 1:-1], K[2:, 1:-1, 1:-1]), c)
    m = jnp.maximum(m, jnp.maximum(K[1:-1, :-2, 1:-1], K[1:-1, 2:, 1:-1]))
    return jnp.maximum(m, jnp.maximum(K[1:-1, 1:-1, :-2], K[1:-1, 1:-1, 2:]))


def darcy_divergence(K, Pf, *, dx, dy, dz, g_z):
    """div qD on the interior cells, qD = -avg(K_muf)*(grad Pf + g) on the
    faces between them (`g_z` on z only)."""
    def flux(a, b, ka, kb, h, g):
        grad = (b - a) / h
        return -(0.5 * (ka + kb)) * (grad + g if g else grad)

    I = slice(1, -1)
    qx = flux(Pf[:-1, I, I], Pf[1:, I, I], K[:-1, I, I], K[1:, I, I], dx, 0)
    qy = flux(Pf[I, :-1, I], Pf[I, 1:, I], K[I, :-1, I], K[I, 1:, I], dy, 0)
    qz = flux(Pf[I, I, :-1], Pf[I, I, 1:], K[I, I, :-1], K[I, I, 1:], dz,
              g_z)
    return ((qx[1:] - qx[:-1]) / dx + (qy[:, 1:] - qy[:, :-1]) / dy
            + (qz[:, :, 1:] - qz[:, :, :-1]) / dz)


def fluid_core(Pt, Pf, RPf, EtaC, K, Phi, Vx, Vy, Vz, *, c):
    """The materials and both pressure equations of one PT iteration,
    shared by the XLA rung and the fused kernel.  `RPf` is given on the
    interior cells.  Returns `(EtaC', K', Pt', RPf', Pf')`: the first
    three on every cell, the last two on the interior cells."""
    import jax.numpy as jnp

    EtaC = ((1.0 - c["theta_e"]) * EtaC + c["theta_e"] * (
        c["eta_s"] / Phi * (1.0 + c["r_fac"] * (
            1.0 + jnp.tanh((Pf - Pt) / c["lam_pe"])))))
    Kn = ((1.0 - c["theta_k"]) * K
          + c["theta_k"] * (c["k_muf0"] * _pow(Phi / c["phi0"], c["nperm"])))
    divV = divergence(Vx, Vy, Vz, dx=c["dx"], dy=c["dy"], dz=c["dz"])
    coup = (Pt - Pf) / (EtaC * (1.0 - Phi))
    Ptn = Pt + c["dtau_pt"] * (-divV - coup)
    I = slice(1, -1)
    RPf = (c["pfdmp"] * RPf
           - darcy_divergence(K, Pf, dx=c["dx"], dy=c["dy"], dz=c["dz"],
                              g_z=c["g_z"])
           + coup[I, I, I])
    Pfn = Pf[I, I, I] + c["dtau_pf"] / maxloc(Kn) * RPf
    return EtaC, Kn, Ptn, RPf, Pfn


def density(Phi, *, c):
    """Rog = rhofg*Phi + rhosg*(1 - Phi) - rhogBG."""
    return c["rho_fg"] * Phi + c["rho_sg"] * (1.0 - Phi) - c["rho_gbg"]


def momentum_core(Pt, Vx, Vy, Vz, dVx, dVy, dVz, Phi, *, c):
    """The solid's half of one PT iteration, shared by the XLA rung and
    the fused kernel: Stokes's momentum residuals of the velocities with
    the (updated) total pressure `Pt` and the buoyancy `-Rog`, into the
    damped accumulators (given on the interior faces).  Returns the new
    accumulators and the velocity increments `dtauV*dV'`, both on the
    interior faces."""
    divV = divergence(Vx, Vy, Vz, dx=c["dx"], dy=c["dy"], dz=c["dz"])
    rx, ry, rz = momentum_residuals(Pt, Vx, Vy, Vz, density(Phi, c=c), divV,
                                    dx=c["dx"], dy=c["dy"], dz=c["dz"],
                                    mu=c["mu"], buoy_sign=-1)
    dVx = c["damp_x"] * dVx + rx
    dVy = c["damp_y"] * dVy + ry
    dVz = c["damp_z"] * dVz + rz
    return (dVx, dVy, dVz,
            c["dtau_v"] * dVx, c["dtau_v"] * dVy, c["dtau_v"] * dVz)


def _interior_set(A, v):
    """A with its interior cells replaced by `v` (the edges kept)."""
    import jax.numpy as jnp
    from jax import lax

    mask = None
    for d in range(3):
        i = lax.broadcasted_iota(jnp.int32, A.shape, d)
        m = (i > 0) & (i < A.shape[d] - 1)
        mask = m if mask is None else mask & m
    return jnp.where(mask, jnp.pad(v, 1), A)


def compute_iteration(Pt, Pf, RPf, EtaC, K, Vx, Vy, Vz, dVx, dVy, dVz,
                      Phi, *, c):
    """One PT iteration without the halo exchange (the XLA rung's
    arithmetic): Pt, EtaC and K_muf on every cell, the rest on the
    interior cells and faces."""
    from igg.ops import interior_add

    I = slice(1, -1)
    EtaC, K, Pt, RPfn, Pfn = fluid_core(Pt, Pf, RPf[I, I, I], EtaC, K, Phi,
                                        Vx, Vy, Vz, c=c)
    dV = (dVx, dVy, dVz)
    dVxn, dVyn, dVzn, ix, iy, iz = momentum_core(
        Pt, Vx, Vy, Vz, *(a[I, I, I] for a in dV), Phi, c=c)
    return (Pt, _interior_set(Pf, Pfn), _interior_set(RPf, RPfn), EtaC, K,
            interior_add(Vx, ix), interior_add(Vy, iy), interior_add(Vz, iz),
            _interior_set(dVx, dVxn), _interior_set(dVy, dVyn),
            _interior_set(dVz, dVzn))


def local_iteration(*state, Phi, c):
    """One PT iteration over per-device local arrays: the update, then one
    grouped halo exchange of the fields it updates on interior cells only
    (`EXCHANGED`)."""
    out = list(compute_iteration(*state, Phi, c=c))
    idx = [STATE.index(f) for f in EXCHANGED]
    new = igg.update_halo_local(*(out[i] for i in idx))
    for i, a in zip(idx, new):
        out[i] = a
    return tuple(out)


_PALLAS_REQ = (
    "the fused HydroMech3D iteration requires TPU devices (or "
    "pallas_interpret=True), an overlap-3 grid, f32 fields with local "
    "shape divisible into x-slabs (x % 8 == 0, x >= 16, y >= 8, z >= 8), "
    "and in compiled mode a y*z area small enough that some slab height's "
    "windows fit the VMEM budget "
    "(igg.ops.hydromech_pallas.hydromech_pallas_supported); the XLA rung "
    "serves otherwise.")


def _scalars(params: Params) -> tuple:
    """`pseudo_steps` as a sorted tuple of pairs: hashable, so recreated
    closures share one compiled program (`igg.parallel._fn_key`)."""
    return tuple(sorted(pseudo_steps(params).items()))


def make_iteration(params: Params = Params(), *, n_inner: int = 1,
                   donate: bool = True, use_pallas="auto",
                   pallas_interpret: bool = False, verify=None):
    """Compiled `(*state, Phi) -> state` advancing `n_inner` PT iterations
    at fixed porosity in one SPMD program (`state` in `STATE` order).
    The dispatch is the family's degradation ladder (`igg.degrade`):
    `hydromech3d.mosaic` (the fused per-iteration kernel,
    `igg.ops.hydromech_pallas`) where it admits the grid, else
    `hydromech3d.xla` (the composition truth).  `use_pallas`: "auto"
    (default), False (pins the XLA rung) or True (requires the kernel);
    `verify="first_use"` checks the kernel against the truth before it
    serves."""
    from jax import lax

    from igg.ops.hydromech_pallas import hydromech_pallas_supported

    from ._dispatch import auto_dispatch

    scal = _scalars(params)
    donate_argnums = tuple(range(len(STATE))) if donate else ()

    def xla_it(*args):
        *state, Phi = args
        c = dict(scal)
        return lax.fori_loop(
            0, n_inner, lambda _, S: local_iteration(*S, Phi=Phi, c=c),
            tuple(state))

    xla_path = igg.sharded(xla_it, donate_argnums=donate_argnums)

    def build_pallas_steps():
        from igg.ops.hydromech_pallas import hydromech_iterations

        def pallas_it(*args):
            *state, Phi = args
            return hydromech_iterations(tuple(state), Phi, n_inner,
                                        c=dict(scal),
                                        interpret=pallas_interpret)

        return pallas_it

    return auto_dispatch(
        use_pallas=use_pallas, interpret=pallas_interpret,
        supported_fn=hydromech_pallas_supported, requirement=_PALLAS_REQ,
        xla_path=xla_path, build_pallas_steps=build_pallas_steps,
        donate_argnums=donate_argnums, family="hydromech3d", verify=verify)


def residual_norms(*args, c):
    """The five max-norms of the state's residuals over this device's
    block, reduced over the whole grid (`pmax`): the momentum residuals
    `(Rx, Ry, Rz)` with Pt as given, `RPt = -div V - (Pt - Pf)/(EtaC*(1 -
    Phi))` and the undamped fluid residual `RPf = -div qD + (Pt -
    Pf)/(EtaC*(1 - Phi))`, on the interior cells and faces.  Call inside
    SPMD code; every op runs under the `igg.residual` scope."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from igg import profiling

    Pt, Pf, _, EtaC, K, Vx, Vy, Vz, _, _, _, Phi = args
    I = slice(1, -1)
    with jax.named_scope(profiling.RESIDUAL):
        divV = divergence(Vx, Vy, Vz, dx=c["dx"], dy=c["dy"], dz=c["dz"])
        rx, ry, rz = momentum_residuals(
            Pt, Vx, Vy, Vz, density(Phi, c=c), divV, dx=c["dx"],
            dy=c["dy"], dz=c["dz"], mu=c["mu"], buoy_sign=-1)
        coup = ((Pt - Pf) / (EtaC * (1.0 - Phi)))[I, I, I]
        rpt = -divV[I, I, I] - coup
        rpf = coup - darcy_divergence(K, Pf, dx=c["dx"], dy=c["dy"],
                                      dz=c["dz"], g_z=c["g_z"])
        norms = jnp.stack([jnp.max(jnp.abs(r))
                           for r in (rx, ry, rz, rpt, rpf)])
        return lax.pmax(norms, igg.AXIS_NAMES)


def residual(*args, params: Params = Params()):
    """(max|Rx|, max|Ry|, max|Rz|, max|RPt|, max|RPf|) of `(*state, Phi)`,
    as host floats: the check the miniapp makes every `nout` iterations to
    decide whether to stop.  One `igg.sharded` program (the norms and
    their global `pmax`) and one read-back to the host.  Counts each check
    in `igg_residual_checks_total{family="hydromech3d"}`."""
    import jax

    from igg import telemetry

    scal = _scalars(params)

    def norms(*a):
        return residual_norms(*a, c=dict(scal))

    telemetry.counter("igg_residual_checks_total",
                      family="hydromech3d").inc()
    got = jax.device_get(igg.sharded(norms)(*args))
    return tuple(float(v) for v in got)


def physical_step(state, Phi, divV_old, params: Params = Params()):
    """The porosity update between two PT solves: `dt = dt_red/(1e-10 +
    max|div V|)` over the whole grid, then `Phi <- Phi + (1 - Phi)*(CN*
    divV_old + (1 - CN)*div V)*dt` on every cell, `div V` of the state's
    (converged) velocities.  Returns `(Phi', div V, dt)`: `div V` is the
    next step's `divV_old` (zeros before the first step)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    scal = _scalars(params)
    cn, dt_red = params.cn, params.dt_red

    def step(Vx, Vy, Vz, Phi, divV_old):
        c = dict(scal)
        divV = divergence(Vx, Vy, Vz, dx=c["dx"], dy=c["dy"], dz=c["dz"])
        dt = dt_red / (1e-10 + lax.pmax(jnp.max(jnp.abs(divV)),
                                        igg.AXIS_NAMES))
        Phi = Phi + (1.0 - Phi) * (cn * divV_old + (1.0 - cn) * divV) * dt
        return Phi, divV, dt

    Vx, Vy, Vz = state[5:8]
    Phi, divV, dt = igg.sharded(step)(Vx, Vy, Vz, Phi, divV_old)
    return Phi, divV, float(jax.device_get(dt))
