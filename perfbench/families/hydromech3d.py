"""hydromech3d: the two-phase porosity-wave solver's seeded fields, its
eleven-field state, the entry the window drives, the plain reference,
its lower-precision control, the faults and the compulsory byte count.

Everything a cell of this family needs that is not generic harness code
lives here.  Only `Case` (made by `build`) touches the program (`igg`);
the generator, the layout maps, the reference, the faults and the byte
count import nothing of it.

State.  `FIELDS`: at cell centres (the grid block) the total and fluid
pressures Pt and Pf, the damped fluid residual RPf, the relaxed bulk
viscosity EtaC and permeability K_muf; on faces, each one cell longer
along its own axis, the solid velocities Vx, Vy, Vz and their damped
accumulators dVx, dVy, dVz.  The porosity Phi has Pt's shape and does not
change during a PT solve; it takes the place the harness gives a
constant coefficient.  Every state a `Case` hands out is a `Stepped`,
which also keeps the residual norms the convergence check read from it.

Layout.  As `stokes3d`'s: at overlap 3 every field holds n - 3 unique
cells per block along each periodic dimension, and blocked index q
(block b = q // m, local l = q % m) holds reference cell
(b*(n - 3) + l - 1) mod G.  Reference face i of Vx lies between cells
i-1 and i (likewise along y and z).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import numpy as np

OVERLAP = 3
DTYPE_BYTES = {"float32": 4}
LOWER = {"float32": "bfloat16"}   # the control's precision
FIELDS = ("Pt", "Pf", "RPf", "EtaC", "K_muf", "Vx", "Vy", "Vz",
          "dVx", "dVy", "dVz")
# The damped accumulators: sums of residuals, which cancel to a few digits
# as a solve converges, so their gap to the reference as a share of their
# own max grows with it (PERF.md section 2); they are compared apart.
ACCUMULATORS = ("RPf", "dVx", "dVy", "dVz")
STAGGER = {"Pt": None, "Pf": None, "RPf": None, "EtaC": None,
           "K_muf": None, "Phi": None, "Vx": 0, "Vy": 1, "Vz": 2,
           "dVx": 0, "dVy": 1, "dVz": 2}
NORMS = ("Rx", "Ry", "Rz", "RPt", "RPf")


class State(NamedTuple):
    """The solver's state, a pytree of eleven blocked (or reference)
    arrays."""
    Pt: object
    Pf: object
    RPf: object
    EtaC: object
    K_muf: object
    Vx: object
    Vy: object
    Vz: object
    dVx: object
    dVy: object
    dVz: object

    def block_until_ready(self):
        import jax

        jax.block_until_ready(tuple(self))
        return self


class Stepped(State):
    """A state on the program's side.  `norms` holds the five residual
    norms (host floats) the convergence check read from it, None until
    checked."""
    norms = None


def stepped(S, norms=None):
    out = Stepped(*S)
    out.norms = norms
    return out


# --------------------------------------------------------------------------
# Geometry and constants (no program code)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Geometry:
    local: tuple        # per-device block of Pt, halos included
    dims: tuple         # devices per dimension

    @classmethod
    def of(cls, config):
        if tuple(config["periods"]) != (1, 1, 1):
            raise ValueError("the hydromech3d family supports periodic "
                             "dimensions only")
        return cls(tuple(config["local_shape"]), tuple(config["dims"]))

    def global_size(self, d):
        """Unique cells of every field along d."""
        return self.dims[d] * (self.local[d] - OVERLAP)

    @property
    def ref_shape(self):
        return tuple(self.global_size(d) for d in range(3))

    def size(self, field, d):
        return self.local[d] + (STAGGER[field] == d)

    def blocked_shape(self, field):
        return tuple(self.dims[d] * self.size(field, d) for d in range(3))

    def blocked_to_ref(self, field, d):
        m, k = self.size(field, d), self.dims[d]
        q = np.arange(k * m)
        inner = self.local[d] - OVERLAP
        return ((q // m) * inner + q % m - 1) % self.global_size(d)

    def owner(self, field, d):
        m, inner = self.size(field, d), self.local[d] - OVERLAP
        p = np.arange(self.global_size(d))
        return (p // inner) * m + p % inner + 1


def constants(geom, params):
    """The iteration's scalars from the configuration's `params` (the
    miniapp's names): dx = l/(n_g - 1); dtauV = min(d)^2/mus/(1 +
    beta_n)/6.1/Vsc; dtauPt = 6.1*mus*(1 + beta_n)/min(n_g)/Ptsc; the
    fluid step's numerator min(d)^2/6.1/Pfsc; damp_i = 1 - Vdmp/n_i;
    mus = etaC0*phi0/eta2mus; rhogBG = rhofg*phi0 + rhosg*(1 - phi0)."""
    p = params
    ls = (p["lx"], p["ly"], p["lz"])
    n = geom.ref_shape
    d = tuple(ls[i] / (n[i] - 1) for i in range(3))
    d2 = min(d) ** 2
    mu = p["eta_c0"] * p["phi0"] / p["eta2mus"]
    return dict(dx=d[0], dy=d[1], dz=d[2], mu=mu,
                dtau_v=d2 / mu / (1.0 + p["beta_n"]) / 6.1 / p["vsc"],
                dtau_pt=6.1 * mu * (1.0 + p["beta_n"]) / min(n) / p["ptsc"],
                dtau_pf=d2 / 6.1 / p["pfsc"],
                damp_x=1.0 - p["vdmp"] / n[0], damp_y=1.0 - p["vdmp"] / n[1],
                damp_z=1.0 - p["vdmp"] / n[2],
                rho_gbg=p["rho_fg"] * p["phi0"]
                + p["rho_sg"] * (1.0 - p["phi0"]))


def compulsory_bytes(config, traffic):
    """Least HBM traffic of one dispatch on one device: every field the
    dispatch reads is read once (the eleven state fields and Phi) and
    every field it returns is written once (the eleven), whatever
    implements the iteration and however many iterations it fuses."""
    n = config["local_shape"]
    cells = int(np.prod(n))
    faces = sum(cells // n[d] * (n[d] + 1) for d in range(3))
    read = 6 * cells + 2 * faces
    written = 5 * cells + 2 * faces
    return (read + written) * DTYPE_BYTES[config["dtype"]]


# --------------------------------------------------------------------------
# Seeded fields: the miniapp's porosity anomaly, drawn from the seed
# --------------------------------------------------------------------------

def anomaly(seed, params):
    """Centre, widths and amplitude of the porosity anomaly (the miniapp
    puts one of amplitude 2*phi0, widths (4, 4, 1)*lambda, at (lx/2, ly/2,
    lz/4); here they are drawn from the seed around those)."""
    rng = np.random.default_rng(int(seed))
    L = np.array([params["lx"], params["ly"], params["lz"]])
    return dict(c=L * np.array([0.5, 0.5, 0.25]) * rng.uniform(0.8, 1.2, 3),
                w=np.array([4.0, 4.0, 1.0]) * rng.uniform(0.8, 1.2),
                a=2.0 * params["phi0"] * rng.uniform(0.5, 1.5))


def _profiles(geom, params, seed):
    """The anomaly's separable 1-D Gaussian profiles over the reference
    cells (float64, host), the distance taken to the nearest periodic
    image; and the box mean of the 3-D product."""
    an = anomaly(seed, params)
    h = constants(geom, params)
    out = []
    for d, dx in enumerate((h["dx"], h["dy"], h["dz"])):
        period = geom.global_size(d) * dx
        x = np.arange(geom.global_size(d)) * dx
        dist = (x - an["c"][d] + period / 2) % period - period / 2
        out.append(np.exp(-(dist / an["w"][d]) ** 2))
    mean = float(np.prod([p.mean() for p in out]))
    return out, an["a"], mean


def make_phi(geom, params, seed, *, blocked, dtype="float32",
             sharding=None):
    """Phi = phi0 + a*(g - mean g), g the Gaussian anomaly: its box mean
    is phi0, so the background density rhogBG removes the mean buoyancy
    and the periodic box carries no net force.  In the blocked layout
    (with `sharding`) or the reference layout; every blocked copy of a
    cell holds the cell's value."""
    prof, a, mean = _profiles(geom, params, seed)
    if blocked:
        prof = [p[geom.blocked_to_ref("Phi", d)] for d, p in enumerate(prof)]
    prof = [np.asarray(p, dtype) for p in prof]
    return _phi_jit(sharding)(*prof, np.asarray(a, dtype),
                              np.asarray(mean, dtype),
                              np.asarray(params["phi0"], dtype))


@functools.lru_cache(maxsize=None)
def _phi_jit(sharding):
    import jax

    def phi(fx, fy, fz, a, mean, phi0):
        g = fx[:, None, None] * fy[None, :, None] * fz[None, None, :]
        return phi0 + a * (g - mean)

    return jax.jit(phi, out_shardings=sharding)


@functools.lru_cache(maxsize=None)
def _rest_jit(shapes, dtype, consts, sharding):
    """The state at rest from Phi: EtaC = mus*eta2mus/Phi and K_muf =
    k_muf0*Phi/phi0 (the miniapp's initial values), the rest zero."""
    import jax
    import jax.numpy as jnp

    eta, k0, phi0 = consts

    def rest(Phi):
        z = [jnp.zeros(s, dtype) for s in shapes]
        return State(z[0], z[1], z[2], (eta / Phi).astype(dtype),
                     (k0 * (Phi / phi0)).astype(dtype), *z[5:])

    return jax.jit(rest, out_shardings=State(*(sharding,) * 11)
                   if sharding else None)


def make_fields(geom, params, seed, *, dtype="float32", sharding=None):
    """(State at rest, Phi) in the blocked layout, on the device."""
    Phi = make_phi(geom, params, seed, blocked=True, dtype=dtype,
                   sharding=sharding)
    shapes = tuple(geom.blocked_shape(f) for f in FIELDS)
    mu = params["eta_c0"] * params["phi0"] / params["eta2mus"]
    consts = (mu * params["eta2mus"], params["k_muf0"], params["phi0"])
    return _rest_jit(shapes, dtype, consts, sharding)(Phi), Phi


# --------------------------------------------------------------------------
# The plain reference (the iteration on the periodic reference layout)
# --------------------------------------------------------------------------

def _ops(geom, params, dtype):
    """The discrete operators of the reference in `dtype`: backward and
    forward differences (periodic, by `jnp.roll`), the divergence, the
    Darcy flux divergence, the density and the momentum residuals.  Every
    operand and intermediate is held in `dtype` (the control runs it
    lower)."""
    import jax.numpy as jnp

    dt_ = jnp.dtype(dtype)
    s = constants(geom, params)

    def c(v):
        return jnp.asarray(v, dt_)

    dx, dy, dz, mu = c(s["dx"]), c(s["dy"]), c(s["dz"]), c(s["mu"])
    h = (dx, dy, dz)

    def back(A, d):         # A[i] - A[i-1]
        return A - jnp.roll(A, 1, d)

    def fwd(A, d):          # A[i+1] - A[i]
        return jnp.roll(A, -1, d) - A

    def div(Vx, Vy, Vz):
        return fwd(Vx, 0) / dx + fwd(Vy, 1) / dy + fwd(Vz, 2) / dz

    def div_qd(K, Pf):
        """div qD, qD on face i = -(K[i-1] + K[i])/2*((Pf[i] - Pf[i-1])/h
        (+ rhofg - rhogBG on z))."""
        g = c(params["rho_fg"] - s["rho_gbg"])
        out = 0
        for d in range(3):
            grad = back(Pf, d) / h[d]
            if d == 2:
                grad = grad + g
            q = -(c(0.5) * (K + jnp.roll(K, 1, d))) * grad
            out = out + fwd(q, d) / h[d]
        return out

    def rog(Phi):
        return (c(params["rho_fg"]) * Phi + c(params["rho_sg"])
                * (c(1.0) - Phi) - c(s["rho_gbg"]))

    def coupling(Pt, Pf, EtaC, Phi):
        return (Pt - Pf) / (EtaC * (c(1.0) - Phi))

    def momentum(Pt, Vx, Vy, Vz, R, dv):
        """Rx, Ry, Rz on every face: deviatoric normal stresses at
        centres, shear stresses on edges, their divergence less the
        pressure gradient, less the density averaged onto z faces."""
        txx = c(2.0) * mu * (fwd(Vx, 0) / dx - dv / c(3.0))
        tyy = c(2.0) * mu * (fwd(Vy, 1) / dy - dv / c(3.0))
        tzz = c(2.0) * mu * (fwd(Vz, 2) / dz - dv / c(3.0))
        txy = mu * (back(Vx, 1) / dy + back(Vy, 0) / dx)
        txz = mu * (back(Vx, 2) / dz + back(Vz, 0) / dx)
        tyz = mu * (back(Vy, 2) / dz + back(Vz, 1) / dy)
        rx = (back(txx, 0) / dx + fwd(txy, 1) / dy + fwd(txz, 2) / dz
              - back(Pt, 0) / dx)
        ry = (back(tyy, 1) / dy + fwd(txy, 0) / dx + fwd(tyz, 2) / dz
              - back(Pt, 1) / dy)
        rz = (back(tzz, 2) / dz + fwd(txz, 0) / dx + fwd(tyz, 1) / dy
              - back(Pt, 2) / dz - c(0.5) * (R + jnp.roll(R, 1, 2)))
        return rx, ry, rz

    return c, s, div, div_qd, rog, coupling, momentum


def reference_iters(geom, params, n_iters, dtype="float32"):
    """A jitted `(State, Phi) -> State` over the reference layout
    advancing `n_iters` PT iterations of the miniapp's system on every
    cell and face of the periodic box: materials, both pressure
    equations (the fluid step from the largest K_muf of each cell and its
    six neighbours), then the momentum residuals with the updated Pt into
    the damped accumulators and the velocities."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    c, s, div, div_qd, rog, coupling, momentum = _ops(geom, params, dtype)
    p = params
    mu = s["mu"]
    th_e, th_k = c(p["theta_e"]), c(p["theta_k"])
    n = float(p["nperm"])

    def perm(Phi):
        x = Phi / c(p["phi0"])
        return x ** int(n) if n.is_integer() else x ** c(n)

    def maxloc(K):
        m = K
        for d in range(3):
            m = jnp.maximum(m, jnp.maximum(jnp.roll(K, 1, d),
                                           jnp.roll(K, -1, d)))
        return m

    def one(S, Phi):
        Pt, Pf, RPf, EtaC, K, Vx, Vy, Vz, dVx, dVy, dVz = S
        EtaC = (c(1.0) - th_e) * EtaC + th_e * (
            c(mu) / Phi * c(p["eta2mus"]) * (c(1.0) + c(0.5) * (
                c(1.0 / p["R"] - 1.0)) * (c(1.0) + jnp.tanh(
                    (Pf - Pt) / c(p["lam_pe"])))))
        Kn = (c(1.0) - th_k) * K + th_k * (c(p["k_muf0"]) * perm(Phi))
        dv = div(Vx, Vy, Vz)
        cp = coupling(Pt, Pf, EtaC, Phi)
        RPt = -dv - cp
        RPf = RPf * c(p["pfdmp"]) - div_qd(K, Pf) + cp
        Pt = Pt + c(s["dtau_pt"]) * RPt
        Pf = Pf + c(s["dtau_pf"]) / maxloc(Kn) * RPf
        rx, ry, rz = momentum(Pt, Vx, Vy, Vz, rog(Phi), dv)
        dVx = c(s["damp_x"]) * dVx + rx
        dVy = c(s["damp_y"]) * dVy + ry
        dVz = c(s["damp_z"]) * dVz + rz
        dtv = c(s["dtau_v"])
        return State(Pt, Pf, RPf, EtaC, Kn, Vx + dtv * dVx, Vy + dtv * dVy,
                     Vz + dtv * dVz, dVx, dVy, dVz)

    @jax.jit
    def run(S, Phi):
        S = State(*(a.astype(dtype) for a in S))
        Phi = Phi.astype(dtype)
        return lax.fori_loop(0, n_iters, lambda _, x: one(x, Phi), S)

    return run


def reference_residual(geom, params, dtype="float32"):
    """A jitted `(State, Phi) -> (max|Rx|, max|Ry|, max|Rz|, max|RPt|,
    max|RPf|)` over the reference layout in `dtype`, the pressures read
    as given and RPf undamped (-div qD + (Pt - Pf)/(EtaC*(1 - Phi)))."""
    import jax
    import jax.numpy as jnp

    _, _, div, div_qd, rog, coupling, momentum = _ops(geom, params, dtype)

    @jax.jit
    def norms(S, Phi):
        S = State(*(a.astype(dtype) for a in S))
        Phi = Phi.astype(dtype)
        dv = div(S.Vx, S.Vy, S.Vz)
        cp = coupling(S.Pt, S.Pf, S.EtaC, Phi)
        rs = (*momentum(S.Pt, S.Vx, S.Vy, S.Vz, rog(Phi), dv), -dv - cp,
              cp - div_qd(S.K_muf, S.Pf))
        return tuple(jnp.max(jnp.abs(r)) for r in rs)

    return norms


def porosity_update(geom, params, dtype="float32"):
    """A jitted `(Phi, divV_old, State) -> (Phi', divV)`: the miniapp's
    porosity update between PT solves, dt = dt_red/(1e-10 + max|div V|)
    and Phi' = Phi + (1 - Phi)*(CN*divV_old + (1 - CN)*div V)*dt."""
    import jax
    import jax.numpy as jnp

    c, _, div, *_ = _ops(geom, params, dtype)
    cn = c(params["cn"])

    @jax.jit
    def update(Phi, divV_old, S):
        dv = div(S.Vx, S.Vy, S.Vz)
        dt = c(params["dt_red"]) / (c(1e-10) + jnp.max(jnp.abs(dv)))
        return Phi + (c(1.0) - Phi) * (cn * divV_old
                                       + (c(1.0) - cn) * dv) * dt, dv

    return update


# --------------------------------------------------------------------------
# Numbers compared
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _rel_fn():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def rel(got, want):
        return tuple(jnp.max(jnp.abs(g.astype(w.dtype) - w))
                     / jnp.max(jnp.abs(w)) for g, w in zip(got, want))

    return rel


def compare(got, want):
    """The numbers one checked dispatch gives: for each field the widest
    gap to the reference as a share of the field's max|reference|; their
    largest over the solution's seven fields (`max_rel_err`) and over the
    four damped accumulators (`accum_rel_err`), both compared."""
    rel = dict(zip(FIELDS, (float(v) for v in
                            _rel_fn()(tuple(got), tuple(want)))))
    out = {f"rel_{f}": v for f, v in rel.items()}
    out["max_rel_err"] = max(v for f, v in rel.items()
                             if f not in ACCUMULATORS)
    out["accum_rel_err"] = max(rel[f] for f in ACCUMULATORS)
    return out


def compare_norms(got, want):
    """The numbers of one convergence check: for each residual norm the
    program read, its gap to the reference's norm of the same state as a
    share of that norm, and their largest (`residual_rel_err`)."""
    rel = [abs(g - w) / w if w else float(g != w)
           for g, w in zip(got, want)]
    out = {f"rel_res_{n}": v for n, v in zip(NORMS, rel)}
    out["residual_rel_err"] = max(rel)
    return out


@functools.lru_cache(maxsize=None)
def _owned_fn(geom):
    import jax
    import jax.numpy as jnp

    idx = {f: [jnp.asarray(geom.owner(f, d)) for d in range(3)]
           for f in FIELDS}

    @jax.jit
    def owned(S):
        return State(*(a[idx[f][0]][:, idx[f][1]][:, :, idx[f][2]]
                       for f, a in zip(FIELDS, S)))

    return owned


def to_reference(geom, blocked, device):
    """The owned cells of a blocked state, in the reference layout on
    `device`."""
    import jax

    return _owned_fn(geom)(jax.device_put(tuple(blocked), device))


class Checker:
    """The plain reference over the checked dispatches, built once for a
    cell: `numbers(seed, samples)` runs it over each (before, after) pair
    of blocked states the program read and returned, and compares; where
    the convergence check read `after`'s residual norms, they are
    compared with the reference's norms of `after`'s owned cells.  With
    `control=True` the reference in the nearest precision below the
    configuration's (`LOWER`) takes the program's place in both."""

    def __init__(self, config, traffic, device):
        self.geom = Geometry.of(config)
        self.params = config["params"]
        self.dtype = config["dtype"]
        self.device = device
        n = int(traffic["n_inner"])
        self.ref = reference_iters(self.geom, self.params, n, self.dtype)
        self.low = reference_iters(self.geom, self.params, n,
                                   LOWER[self.dtype])
        self.norms = reference_residual(self.geom, self.params, self.dtype)
        self.low_norms = reference_residual(self.geom, self.params,
                                            LOWER[self.dtype])

    def numbers(self, seed, samples, control=False):
        import jax

        Phi = jax.device_put(make_phi(self.geom, self.params, seed,
                                      blocked=False, dtype=self.dtype),
                             self.device)
        out = []
        for before, after in samples:
            b = to_reference(self.geom, before, self.device)
            a = to_reference(self.geom, after, self.device)
            nums = compare(self.low(b, Phi) if control else a,
                           self.ref(b, Phi))
            norms = getattr(after, "norms", None)
            if norms is not None:
                ref = [float(v) for v in self.norms(a, Phi)]
                if control:
                    norms = [float(v) for v in self.low_norms(a, Phi)]
                nums.update(compare_norms(norms, ref))
            del a, b
            out.append(nums)
        return out


# --------------------------------------------------------------------------
# The cell as the harness drives it
# --------------------------------------------------------------------------

class Case:
    """One cell of this family: grid, fields and the entry the window
    drives, built through the user's path with the library defaults
    (`hydromech3d.make_iteration`, whatever rung its ladder admits)."""

    family = "hydromech3d"

    def __init__(self, config, traffic, devices, *, interpret=False,
                 log=print):
        import jax

        import igg
        from igg.models import hydromech3d as hm

        self.config, self.traffic = config, traffic
        self.geom = g = Geometry.of(config)
        igg.init_global_grid(
            *g.local, dimx=g.dims[0], dimy=g.dims[1], dimz=g.dims[2],
            periodx=1, periody=1, periodz=1, overlapx=OVERLAP,
            overlapy=OVERLAP, overlapz=OVERLAP, devices=list(devices),
            quiet=True)
        nxyz_g = tuple(igg.get_global_grid().nxyz_g)
        self.sharding = igg.sharding_for(3)
        self.params = hm.Params(**config["params"])
        if nxyz_g != g.ref_shape:
            raise RuntimeError(f"the program's global grid {nxyz_g} is not "
                               f"the benchmark's {g.ref_shape}")
        mine, theirs = constants(g, config["params"]), \
            hm.pseudo_steps(self.params)
        if any(not np.isclose(mine[k], theirs[k], rtol=1e-12, atol=0)
               for k in mine):
            raise RuntimeError(f"the program's constants {theirs} are not "
                               f"the reference's {mine}")
        it = hm.make_iteration(self.params, n_inner=int(traffic["n_inner"]),
                               pallas_interpret=interpret)
        self.step = lambda S, Phi: stepped(it(*S, Phi))
        self._hm = hm
        self._copy = jax.jit(lambda S: State(*(x.copy() for x in S)),
                             out_shardings=State(*(self.sharding,) * 11))
        self._probe = jax.jit(lambda x: x[:1, :1, :1] + 0)
        self.phi = None

    def fields(self, seed):
        S, self.phi = make_fields(self.geom, self.config["params"], seed,
                                  dtype=self.config["dtype"],
                                  sharding=self.sharding)
        return stepped(S), self.phi

    def served(self):
        import igg.degrade

        return igg.degrade.active().get(self.family), igg.degrade.status()

    def copy(self, S):
        """A device copy of the state, with the norms read from it."""
        return stepped(self._copy(S), getattr(S, "norms", None))

    def probe(self, S):
        """A one-element read of Pt on its first device, ordered after the
        dispatch that made it (the state itself is donated to the next
        one)."""
        return self._probe(S.Pt.addressable_shards[0].data)

    def frame(self, S, kind):
        """The miniapp's convergence check: the five residual norms over
        the whole grid of this dispatch's output (`hydromech3d.residual`),
        read to host floats before the next dispatch is enqueued, and kept
        on the state (`Stepped.norms`)."""
        if kind != "residual":
            raise ValueError(f"unknown frame kind {kind!r}")
        S.norms = self._hm.residual(*S, self.phi, params=self.params)
        return S.norms

    def compulsory_bytes(self):
        return compulsory_bytes(self.config, self.traffic)

    def close(self):
        import igg

        self.phi = None
        if igg.grid_is_initialized():
            igg.finalize_global_grid()


# --------------------------------------------------------------------------
# Faults: the timed path broken on purpose, to show the check catches it
# --------------------------------------------------------------------------

def faults(config, traffic):
    """name -> wrap(step) -> a broken step `(State, Phi) -> State` on the
    blocked layout: the state returned unchanged; half of the grid (x <
    X/2 of each blocked field) left unstepped; one answer altered where it
    is produced (one owned Pt cell off by 1% of max|Pt|); Pf returned from
    before the dispatch; and the fluid half left out (`no_darcy`: Pf and
    its damped residual RPf from before the dispatch, so no Darcy flux
    moved the fluid)."""
    import jax
    import jax.numpy as jnp

    geom = Geometry.of(config)

    @jax.jit
    def keep_half(before, after):
        def half(b, a):
            x = jnp.arange(b.shape[0])[:, None, None]
            return jnp.where(x < b.shape[0] // 2, b, a)
        return State(*(half(b, a) for b, a in zip(before, after)))

    mid = tuple(int(geom.owner("Pt", d)[geom.global_size(d) // 2])
                for d in range(3))

    @jax.jit
    def alter(after):
        Pt = after.Pt.at[mid].add(0.01 * jnp.max(jnp.abs(after.Pt)))
        return after._replace(Pt=Pt)

    def half(step):
        def broken(S, Phi):
            before = State(*(x.copy() for x in S))
            return stepped(keep_half(before, step(S, Phi)))
        return broken

    def stale(*names):
        def wrap(step):
            def broken(S, Phi):
                kept = {n: getattr(S, n).copy() for n in names}
                return stepped(step(S, Phi)._replace(**kept))
            return broken
        return wrap

    def altered(step):
        return lambda S, Phi: stepped(alter(step(S, Phi)))

    return {"unchanged": lambda step: lambda S, Phi: S,
            "half": half,
            "altered": altered,
            "stale_pf": stale("Pf"),
            "no_darcy": stale("Pf", "RPf")}


def build(config, traffic, devices, **kw):
    return Case(config, traffic, devices, **kw)
