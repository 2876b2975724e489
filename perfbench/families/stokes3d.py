"""stokes3d: the pseudo-transient Stokes solver's seeded fields, its
four-field state, the entry the window drives, the plain reference, its
lower-precision control, the faults and the compulsory byte count.

Everything a cell of this family needs that is not generic harness code
lives here.  Only `Case` (made by `build`) touches the program (`igg`);
the generator, the layout maps, the reference, the faults and the byte
count import nothing of it.

State.  Four fields on three staggered shapes: the pressure P at cell
centres (the grid block), and the velocities Vx, Vy, Vz on faces, each one
cell longer along its own axis.  The buoyancy Rho has P's shape and never
changes; it takes the place the harness gives a constant coefficient.
`State` holds the four and waits for them (`block_until_ready`) as the
harness waits for a one-field state.  Every state a `Case` hands out is a
`Stepped`, which also keeps the residual norms the convergence check read
from it, so that a snapshot of a checked state carries them to `Checker`.

Layout.  The program holds each field as one "blocked" array: each
device's local block stacked along each dimension.  At overlap 3 a field
of local size m along a dimension holds m - ol unique cells per block,
where ol is 3, or 4 along a face field's own axis (the reference's
per-array overlap), so m - ol = n - 3 for every field.  Only periodic
dimensions are supported: each then has G = dims*(n - 3) unique cells for
every field, and blocked index q (block b = q // m, local l = q % m) holds
reference cell (b*(n - 3) + l - 1) mod G.  Reference face i of Vx lies
between cells i-1 and i (likewise Vy, Vz along their axes).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import numpy as np

OVERLAP = 3
DTYPE_BYTES = {"float32": 4}
LOWER = {"float32": "bfloat16"}   # the control's precision
FIELDS = ("P", "Vx", "Vy", "Vz")
STAGGER = {"P": None, "Vx": 0, "Vy": 1, "Vz": 2, "Rho": None}


class State(NamedTuple):
    """The solver's state, a pytree of four blocked (or reference)
    arrays."""
    P: object
    Vx: object
    Vy: object
    Vz: object

    def block_until_ready(self):
        import jax

        jax.block_until_ready(tuple(self))
        return self


class Stepped(State):
    """A state on the program's side.  `norms` holds the four residual
    norms (host floats) the convergence check read from it, None until
    checked."""
    norms = None


def stepped(S, norms=None):
    out = Stepped(*S)
    out.norms = norms
    return out


# --------------------------------------------------------------------------
# Geometry (no program code)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Geometry:
    local: tuple        # per-device block of P, halos included
    dims: tuple         # devices per dimension

    @classmethod
    def of(cls, config):
        if tuple(config["periods"]) != (1, 1, 1):
            raise ValueError("the stokes3d family supports periodic "
                             "dimensions only")
        return cls(tuple(config["local_shape"]), tuple(config["dims"]))

    def global_size(self, d):
        """Unique cells of every field along d (the reference's `nx_g`
        of a periodic dimension)."""
        return self.dims[d] * (self.local[d] - OVERLAP)

    @property
    def ref_shape(self):
        return tuple(self.global_size(d) for d in range(3))

    def size(self, field, d):
        """Local size of `field` along d."""
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


def pseudo_steps(geom, params):
    """dx = l/(n_g - 1), dtV = min(dx)^2/mu/8.1/vdamp, dtP = 4.1*mu/min(n_g):
    the program's pseudo-transient steps (`stokes3d._pseudo_steps`)."""
    ls = (params["lx"], params["ly"], params["lz"])
    dx = tuple(ls[d] / (geom.global_size(d) - 1) for d in range(3))
    mu = params["mu"]
    return dict(dx=dx[0], dy=dx[1], dz=dx[2], mu=mu,
                dtP=4.1 * mu / min(geom.ref_shape),
                dtV=min(dx) ** 2 / mu / 8.1 / params["vdamp"])


def compulsory_bytes(config, traffic):
    """Least HBM traffic of one dispatch on one device: every field the
    dispatch reads is read once (P, Vx, Vy, Vz, Rho) and every field it
    returns is written once (P, Vx, Vy, Vz), whatever implements the
    iteration and however many iterations it fuses."""
    n = config["local_shape"]
    cells = int(np.prod(n))
    faces = sum(cells // n[d] * (n[d] + 1) for d in range(3))
    return (2 * cells + faces + cells + faces) * DTYPE_BYTES[config["dtype"]]


# --------------------------------------------------------------------------
# Seeded fields: init_fields' buoyant inclusion, drawn from the seed
# --------------------------------------------------------------------------

def inclusion(seed, params):
    """Centre, radius and amplitude of the buoyant inclusion
    (`init_fields` puts a unit one at the box centre; here they are drawn
    from the seed, around the same place)."""
    rng = np.random.default_rng(int(seed))
    L = np.array([params["lx"], params["ly"], params["lz"]])
    return dict(c=L * rng.uniform(0.3, 0.7, 3), r=rng.uniform(0.8, 1.5),
                a=params["rho_g"] * rng.uniform(0.5, 1.5))


def _profiles(geom, params, seed):
    """The inclusion's separable 1-D Gaussian profiles over the reference
    cells (float64, host), the distance taken to the nearest periodic image
    so the field is smooth across the wrap; and the box mean of the 3-D
    product."""
    inc = inclusion(seed, params)
    h = pseudo_steps(geom, params)
    out = []
    for d, dx in enumerate((h["dx"], h["dy"], h["dz"])):
        period = geom.global_size(d) * dx
        x = np.arange(geom.global_size(d)) * dx
        dist = (x - inc["c"][d] + period / 2) % period - period / 2
        out.append(np.exp(-(dist / inc["r"]) ** 2))
    mean = inc["a"] * np.prod([p.mean() for p in out])
    return out, inc["a"], mean


def make_rho(geom, params, seed, *, blocked, dtype="float32",
             sharding=None):
    """Rho = a*exp(-|x - c|^2/r^2) less its box mean, so the periodic box
    carries no net force and the iteration has a steady state to relax
    to; in the blocked layout (with `sharding`) or the reference layout.
    Every blocked copy of a cell holds the same value as the cell."""
    prof, a, mean = _profiles(geom, params, seed)
    if blocked:
        prof = [p[geom.blocked_to_ref("Rho", d)] for d, p in enumerate(prof)]
    prof = [np.asarray(p, dtype) for p in prof]
    return _rho_jit(sharding)(*prof, np.asarray(a, dtype),
                              np.asarray(mean, dtype))


@functools.lru_cache(maxsize=None)
def _rho_jit(sharding):
    import jax

    def rho(fx, fy, fz, a, mean):
        return a * fx[:, None, None] * fy[None, :, None] * fz[None, None, :] \
            - mean

    return jax.jit(rho, out_shardings=sharding)


@functools.lru_cache(maxsize=None)
def _zeros_jit(shapes, dtype, sharding):
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda: State(*(jnp.zeros(s, dtype) for s in shapes)),
                   out_shardings=State(*(sharding,) * 4) if sharding
                   else None)


def make_fields(geom, params, seed, *, dtype="float32", sharding=None):
    """(State at rest, Rho) in the blocked layout, on the device."""
    shapes = tuple(geom.blocked_shape(f) for f in FIELDS)
    return (_zeros_jit(shapes, dtype, sharding)(),
            make_rho(geom, params, seed, blocked=True, dtype=dtype,
                     sharding=sharding))


# --------------------------------------------------------------------------
# The plain reference (the iteration on the periodic reference layout)
# --------------------------------------------------------------------------

def _ops(geom, params, dtype):
    """The discrete operators of the reference in `dtype`: backward and
    forward differences (periodic, by `jnp.roll`), the divergence and the
    momentum residuals.  Every operand and intermediate is held in
    `dtype` (the control runs it lower)."""
    import jax.numpy as jnp

    dt_ = jnp.dtype(dtype)
    s = pseudo_steps(geom, params)

    def c(v):
        return jnp.asarray(v, dt_)

    dx, dy, dz, mu = c(s["dx"]), c(s["dy"]), c(s["dz"]), c(s["mu"])

    def back(A, d):         # A[i] - A[i-1]
        return A - jnp.roll(A, 1, d)

    def fwd(A, d):          # A[i+1] - A[i]
        return jnp.roll(A, -1, d) - A

    def div(Vx, Vy, Vz):
        return fwd(Vx, 0) / dx + fwd(Vy, 1) / dy + fwd(Vz, 2) / dz

    def momentum(P, Vx, Vy, Vz, Rho, dv):
        """Rx, Ry, Rz on every face: deviatoric normal stresses at
        centres, shear stresses on edges, their divergence less the
        pressure gradient, plus the buoyancy averaged onto z faces."""
        txx = c(2.0) * mu * (fwd(Vx, 0) / dx - dv / c(3.0))
        tyy = c(2.0) * mu * (fwd(Vy, 1) / dy - dv / c(3.0))
        tzz = c(2.0) * mu * (fwd(Vz, 2) / dz - dv / c(3.0))
        txy = mu * (back(Vx, 1) / dy + back(Vy, 0) / dx)
        txz = mu * (back(Vx, 2) / dz + back(Vz, 0) / dx)
        tyz = mu * (back(Vy, 2) / dz + back(Vz, 1) / dy)
        rx = (back(txx, 0) / dx + fwd(txy, 1) / dy + fwd(txz, 2) / dz
              - back(P, 0) / dx)
        ry = (back(tyy, 1) / dy + fwd(txy, 0) / dx + fwd(tyz, 2) / dz
              - back(P, 1) / dy)
        rz = (back(tzz, 2) / dz + fwd(txz, 0) / dx + fwd(tyz, 1) / dy
              - back(P, 2) / dz + c(0.5) * (Rho + jnp.roll(Rho, 1, 2)))
        return rx, ry, rz

    return c, s, div, momentum


def reference_iters(geom, params, n_iters, dtype="float32"):
    """A jitted `(State, Rho) -> State` over the reference layout
    advancing `n_iters` pseudo-transient iterations: P -= dtP*div(V),
    then V += dtV*R(V, P) with the updated P, on every cell and face of
    the periodic box."""
    import jax
    from jax import lax

    c, s, div, momentum = _ops(geom, params, dtype)
    dtP, dtV = c(s["dtP"]), c(s["dtV"])

    def one(S, Rho):
        P, Vx, Vy, Vz = S
        dv = div(Vx, Vy, Vz)
        P = P - dtP * dv
        rx, ry, rz = momentum(P, Vx, Vy, Vz, Rho, dv)
        return State(P, Vx + dtV * rx, Vy + dtV * ry, Vz + dtV * rz)

    @jax.jit
    def run(S, Rho):
        S = State(*(a.astype(dtype) for a in S))
        Rho = Rho.astype(dtype)
        return lax.fori_loop(0, n_iters, lambda _, x: one(x, Rho), S)

    return run


def reference_residual(geom, params, dtype="float32"):
    """A jitted `(State, Rho) -> (max|div V|, max|Rx|, max|Ry|, max|Rz|)`
    over the reference layout in `dtype`, the pressure read as given."""
    import jax
    import jax.numpy as jnp

    _, _, div, momentum = _ops(geom, params, dtype)

    @jax.jit
    def norms(S, Rho):
        S = State(*(a.astype(dtype) for a in S))
        Rho = Rho.astype(dtype)
        dv = div(S.Vx, S.Vy, S.Vz)
        return tuple(jnp.max(jnp.abs(r))
                     for r in (dv, *momentum(*S, Rho, dv)))

    return norms


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
    gap to the reference as a share of the field's max|reference|, and
    their largest (`max_rel_err`, the compared one)."""
    rel = [float(v) for v in _rel_fn()(tuple(got), tuple(want))]
    out = {f"rel_{f}": v for f, v in zip(FIELDS, rel)}
    out["max_rel_err"] = max(rel)
    return out


NORMS = ("div", "Rx", "Ry", "Rz")


def compare_norms(got, want):
    """The numbers of one convergence check: for each residual norm the
    program read, its gap to the reference's norm of the same state as a
    share of that norm, and their largest (`residual_rel_err`)."""
    # A norm of the state at rest is 0 (a broken step can return it).
    rel = [abs(g - w) / w if w else float(g != w)
           for g, w in zip(got, want)]
    out = {f"rel_{n}": v for n, v in zip(NORMS, rel)}
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
    of blocked states the program read and returned, and compares.  Where
    the convergence check read `after`'s residual norms (`Stepped.norms`),
    they are compared with the reference's norms of `after`'s owned cells
    too.  With `control=True` the reference in the nearest precision below
    the configuration's (`LOWER`) takes the program's place in both."""

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

        Rho = jax.device_put(make_rho(self.geom, self.params, seed,
                                      blocked=False, dtype=self.dtype),
                             self.device)
        out = []
        for before, after in samples:
            b = to_reference(self.geom, before, self.device)
            a = to_reference(self.geom, after, self.device)
            nums = compare(self.low(b, Rho) if control else a,
                           self.ref(b, Rho))
            norms = getattr(after, "norms", None)
            if norms is not None:
                ref = [float(v) for v in self.norms(a, Rho)]
                if control:
                    norms = [float(v) for v in self.low_norms(a, Rho)]
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
    (`stokes3d.make_iteration`, whatever rung its ladder admits)."""

    family = "stokes3d"

    def __init__(self, config, traffic, devices, *, interpret=False,
                 log=print):
        import jax

        import igg
        from igg.models import stokes3d as st

        if not hasattr(st, "residual"):
            raise RuntimeError("this igg has no stokes3d.residual, which "
                               "the cell's convergence checks call")
        self.config, self.traffic = config, traffic
        self.geom = g = Geometry.of(config)
        igg.init_global_grid(
            *g.local, dimx=g.dims[0], dimy=g.dims[1], dimz=g.dims[2],
            periodx=1, periody=1, periodz=1, overlapx=OVERLAP,
            overlapy=OVERLAP, overlapz=OVERLAP, devices=list(devices),
            quiet=True)
        nxyz_g = tuple(igg.get_global_grid().nxyz_g)
        self.sharding = igg.sharding_for(3)
        self.params = st.Params(**config["params"])
        if nxyz_g != g.ref_shape:
            raise RuntimeError(f"the program's global grid {nxyz_g} is not "
                               f"the benchmark's {g.ref_shape}")
        mine, theirs = pseudo_steps(g, config["params"]), \
            st._pseudo_steps(self.params)
        if any(not np.isclose(mine[k], theirs[k], rtol=1e-12, atol=0)
               for k in mine):
            raise RuntimeError(f"the program's pseudo-transient steps "
                               f"{theirs} are not the reference's {mine}")
        it = st.make_iteration(self.params, n_inner=int(traffic["n_inner"]),
                               pallas_interpret=interpret)
        self.step = lambda S, Rho: stepped(it(*S, Rho))
        self._residual = st.residual
        self._copy = jax.jit(lambda S: State(*(x.copy() for x in S)),
                             out_shardings=State(*(self.sharding,) * 4))
        self._probe = jax.jit(lambda x: x[:1, :1, :1] + 0)
        self.rho = None

    def fields(self, seed):
        S, self.rho = make_fields(self.geom, self.config["params"], seed,
                                  dtype=self.config["dtype"],
                                  sharding=self.sharding)
        return stepped(S), self.rho

    def served(self):
        import igg.degrade

        return igg.degrade.active().get(self.family), igg.degrade.status()

    def copy(self, S):
        """A device copy of the state, with the norms read from it."""
        return stepped(self._copy(S), getattr(S, "norms", None))

    def probe(self, S):
        """A one-element read of P on its first device, ordered after the
        dispatch that made it (the state itself is donated to the next
        one)."""
        return self._probe(S.P.addressable_shards[0].data)

    def frame(self, S, kind):
        """The convergence check of the published solver: the four
        residual norms over the whole grid of this dispatch's output
        (`stokes3d.residual`), read to host floats before the next
        dispatch is enqueued, and kept on the state (`Stepped.norms`)."""
        if kind != "residual":
            raise ValueError(f"unknown frame kind {kind!r}")
        S.norms = self._residual(*S, self.rho, self.params)
        return S.norms

    def compulsory_bytes(self):
        return compulsory_bytes(self.config, self.traffic)

    def close(self):
        import igg

        self.rho = None
        if igg.grid_is_initialized():
            igg.finalize_global_grid()


# --------------------------------------------------------------------------
# Faults: the timed path broken on purpose, to show the check catches it
# --------------------------------------------------------------------------

def faults(config, traffic):
    """name -> wrap(step) -> a broken step `(State, Rho) -> State` on the
    blocked layout: the state returned unchanged; half of the grid (x <
    X/2 of each blocked field) left unstepped; one answer altered where it
    is produced (one owned P cell off by 1% of max|P|); and Vz returned
    from before the dispatch (every field is compared, not only P)."""
    import jax
    import jax.numpy as jnp

    geom = Geometry.of(config)

    @jax.jit
    def keep_half(before, after):
        def half(b, a):
            x = jnp.arange(b.shape[0])[:, None, None]
            return jnp.where(x < b.shape[0] // 2, b, a)
        return State(*(half(b, a) for b, a in zip(before, after)))

    mid = tuple(int(geom.owner("P", d)[geom.global_size(d) // 2])
                for d in range(3))

    @jax.jit
    def alter(after):
        P = after.P.at[mid].add(0.01 * jnp.max(jnp.abs(after.P)))
        return after._replace(P=P)

    def half(step):
        def broken(S, Rho):
            before = State(*(x.copy() for x in S))
            return stepped(keep_half(before, step(S, Rho)))
        return broken

    def stale_vz(step):
        def broken(S, Rho):
            vz = S.Vz.copy()
            return stepped(step(S, Rho)._replace(Vz=vz))
        return broken

    def altered(step):
        return lambda S, Rho: stepped(alter(step(S, Rho)))

    return {"unchanged": lambda step: lambda S, Rho: S,
            "half": half,
            "altered": altered,
            "stale_vz": stale_vz}


def build(config, traffic, devices, **kw):
    return Case(config, traffic, devices, **kw)
