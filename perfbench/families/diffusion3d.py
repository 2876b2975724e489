"""diffusion3d: seeded fields, the entry the window drives, the plain
reference, its lower-precision control and the compulsory byte count.

Everything a cell of this family needs that is not generic harness code
lives here.  Only `Case` (made by `build`) touches the program (`igg`);
the generator, the layout maps, the reference, the faults and the byte
count import nothing of it.

Layout.  The program holds a field as one "blocked" array: each device's
local block (halo planes included) stacked along each dimension, so block
`b`, local index `l` of a dimension with local size `n` sits at blocked
index `q = b*n + l`.  With overlap 2, `l = 0` and `l = n-1` are the halo
planes and global index `g = b*(n-2) + l`.  The reference holds the same
physical field without duplicates:

- open dimension: `N = dims*(n-2) + 2` cells, index `g`; cells 0 and N-1
  are the frozen edges.
- periodic dimension: `G = dims*(n-2)` cells, index `(g-1) mod G`.

`blocked_to_ref` maps every blocked index (halos too) to its reference
cell; `owner` maps every reference cell to the one blocked index that owns
it (an interior cell of its block, or a frozen edge).
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

OVERLAP = 2
DTYPE_BYTES = {"float64": 8, "float32": 4}
LOWER = {"float64": "float32", "float32": "bfloat16"}   # the control's


# --------------------------------------------------------------------------
# Geometry (no program code)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Geometry:
    local: tuple        # per-device block, halos included
    dims: tuple         # devices per dimension
    periods: tuple      # 1 periodic, 0 open

    @classmethod
    def of(cls, config):
        return cls(tuple(config["local_shape"]), tuple(config["dims"]),
                   tuple(config["periods"]))

    @property
    def chips(self):
        return int(np.prod(self.dims))

    def global_size(self, d):
        """The reference's `nx_g`: dims*(n-ol) + ol*(period == 0)."""
        n, k, p = self.local[d], self.dims[d], self.periods[d]
        return k * (n - OVERLAP) + OVERLAP * (p == 0)

    @property
    def ref_shape(self):
        """Unique cells per dimension (periodic: the wrap is not
        repeated)."""
        return tuple(self.global_size(d) for d in range(3))

    def blocked_to_ref(self, d):
        n, k = self.local[d], self.dims[d]
        q = np.arange(k * n)
        g = (q // n) * (n - OVERLAP) + q % n
        if self.periods[d]:
            return (g - 1) % self.global_size(d)
        return g

    def owner(self, d):
        n, k, size = self.local[d], self.dims[d], self.global_size(d)
        inner = n - OVERLAP
        if self.periods[d]:
            p = np.arange(size)
            return (p // inner) * n + p % inner + 1
        p = np.arange(size)
        b = np.clip((p - 1) // inner, 0, k - 1)
        return b * n + (p - b * inner)

    def frozen(self):
        """Boolean mask of the reference cells that are frozen edges (on
        open dimensions), broadcast from three 1-D masks; call it inside
        traced code so the full mask is never a host constant."""
        import jax.numpy as jnp

        m = []
        for d in range(3):
            v = np.zeros(self.global_size(d), bool)
            if not self.periods[d]:
                v[0] = v[-1] = True
            m.append(jnp.asarray(v))
        return (m[0][:, None, None] | m[1][None, :, None]
                | m[2][None, None, :])


def spacing(geom, params):
    """dx = l/(n_g - 1), the reference examples' convention."""
    ls = (params["lx"], params["ly"], params["lz"])
    return tuple(ls[d] / (geom.global_size(d) - 1) for d in range(3))


def timestep(geom, params):
    dx = spacing(geom, params)
    return min(h * h for h in dx) * params["cp_min"] / params["lam"] / 8.1


def compulsory_bytes(config, traffic):
    """Least HBM traffic of one dispatch on one device: every field the
    dispatch reads is read once (T, Cp) and every field it returns is
    written once (T), whatever implements the stencil and however many
    steps it fuses."""
    cells = int(np.prod(config["local_shape"]))
    return 3 * cells * DTYPE_BYTES[config["dtype"]]


# --------------------------------------------------------------------------
# Seeded fields: init_fields' Gaussian-anomaly form, drawn from the seed
# --------------------------------------------------------------------------

def anomalies(seed, params):
    """Centres, widths and amplitudes of the two T anomalies and the two
    Cp anomalies (init_fields places them at fixed fractions of the box;
    here they are drawn from the seed, near the same places)."""
    rng = np.random.default_rng(int(seed))
    L = np.array([params["lx"], params["ly"], params["lz"]])

    def draw(amp_lo, amp_hi, w_lo, w_hi):
        return dict(c=L * rng.uniform(0.3, 0.7, 3),
                    w=rng.uniform(w_lo, w_hi, 3),
                    a=rng.uniform(amp_lo, amp_hi))

    return dict(T=[draw(50, 100, 1.5, 2.5) for _ in range(2)],
                Cp=[draw(3, 7, 0.8, 1.2) for _ in range(2)])


def _coords(geom, params, d, blocked):
    """Physical coordinate of each blocked (or reference) index along
    dimension d, float64 on the host; the reference cell index times dx."""
    h = spacing(geom, params)[d]
    if blocked:
        return geom.blocked_to_ref(d) * h
    return np.arange(geom.global_size(d)) * h


def _fields(cp_min, x, y, z, t_draws, cp_draws):
    """T and Cp from the anomalies, each draw a (centres (k, 3), widths
    (k, 3), amplitudes (k,)) triple of arrays: traced arguments, so every
    seed runs the one compiled program."""
    import jax.numpy as jnp

    x, y, z = x[:, None, None], y[None, :, None], z[None, None, :]

    def field(c, w, a):
        out = 0.0
        for i in range(a.shape[0]):
            out = out + a[i] * jnp.exp(-((x - c[i, 0]) / w[i, 0]) ** 2
                                       - ((y - c[i, 1]) / w[i, 1]) ** 2
                                       - ((z - c[i, 2]) / w[i, 2]) ** 2)
        return out

    T = field(*t_draws)
    Cp = cp_min + field(*cp_draws)
    return T.astype(x.dtype), Cp.astype(x.dtype)


def make_fields(geom, params, seed, *, blocked, dtype="float32",
                sharding=None):
    """(T, Cp) in `dtype` on the device in one jitted call: the blocked
    layout with `sharding` for the program, or the reference layout on
    one device."""
    draws = anomalies(seed, params)

    def arrays(ds):
        return tuple(np.asarray([d[k] for d in ds], dtype)
                     for k in ("c", "w", "a"))

    xyz = [np.asarray(_coords(geom, params, d, blocked), dtype)
           for d in range(3)]
    return _fields_jit(float(params["cp_min"]), sharding)(
        *xyz, arrays(draws["T"]), arrays(draws["Cp"]))


@functools.lru_cache(maxsize=None)
def _fields_jit(cp_min, sharding):
    import jax

    return jax.jit(functools.partial(_fields, cp_min),
                   out_shardings=(sharding, sharding) if sharding else None)


# --------------------------------------------------------------------------
# The plain reference (flux form of the reference example, jax.numpy)
# --------------------------------------------------------------------------

def reference_steps(geom, params, n_steps, dtype="float32", *, dx=None,
                    dt=None):
    """A jitted `(T, Cp) -> T` over the reference layout advancing
    `n_steps` steps of the reference example's update
    (diffusion3D_multigpu_CuArrays_novis.jl): staggered Fourier fluxes
    q = -lam*diff(T)/dx, dT/dt = (1/Cp)*(-diff(qx)/dx - diff(qy)/dy -
    diff(qz)/dz), T += dt*dT/dt on the interior; open edges are never
    written, periodic dimensions wrap.  `dtype` is the precision every
    operand and intermediate is held in (the control runs it lower).
    `dx`, `dt` default to the geometry's own."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    dt_ = jnp.dtype(dtype)
    dx = dx or spacing(geom, params)
    dt = dt or timestep(geom, params)
    lam = params["lam"]

    def c(v):
        return jnp.asarray(v, dt_)

    def one(T, inv_cp, frozen):
        # Each face flux is formed from T's own differences, so the step
        # is one elementwise pass over T and its six neighbours, read from
        # T padded by one cell: wrapped on periodic dimensions, the edge
        # repeated on open ones (only frozen cells read those).
        P = T
        for d in range(3):
            P = jnp.pad(P, [(1, 1) if e == d else (0, 0) for e in range(3)],
                        mode="wrap" if geom.periods[d] else "edge")

        def at(d, k):
            return P[tuple(slice(1 + k * (e == d), P.shape[e] - 1
                                 + k * (e == d)) for e in range(3))]

        dTdt = jnp.zeros_like(T)
        for d in range(3):
            up, down = at(d, 1), at(d, -1)
            q_up = -c(lam) * (up - T) / c(dx[d])       # q at i+1/2
            q_down = -c(lam) * (T - down) / c(dx[d])   # q at i-1/2
            dTdt = dTdt - (q_up - q_down) / c(dx[d])
        Tn = T + c(dt) * (inv_cp * dTdt)
        return jnp.where(frozen, T, Tn)

    @jax.jit
    def run(T, Cp):
        T = T.astype(dt_)
        inv_cp = c(1.0) / Cp.astype(dt_)
        frozen = geom.frozen()
        return lax.fori_loop(0, n_steps,
                             lambda _, t: one(t, inv_cp, frozen), T)

    return run


# --------------------------------------------------------------------------
# Numbers compared
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _numbers_fn(geom):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def numbers(got, want, before):
        got = got.astype(want.dtype)
        rel = jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want))
        drift = jnp.max(jnp.where(geom.frozen(), jnp.abs(
            got - before.astype(want.dtype)), 0.0))
        return rel, drift

    return numbers


def compare(got, want, before, geom):
    """The numbers one checked dispatch gives: the widest gap to the
    reference as a share of max|reference|, and (open dimensions) the
    largest change of a frozen edge cell over the dispatch, which must be
    exactly 0."""
    rel, drift = _numbers_fn(geom)(got, want, before)
    out = {"max_rel_err": float(rel)}
    if not all(geom.periods):
        out["edge_drift"] = float(drift)
    return out


# --------------------------------------------------------------------------
# The cell as the harness drives it
# --------------------------------------------------------------------------

class Case:
    """One cell of this family: grid, fields and the entry the window
    drives, built through the user's path with the library defaults."""

    family = "diffusion3d"

    def __init__(self, config, traffic, devices, *, interpret=False,
                 log=print):
        import jax

        import igg
        from igg.models import diffusion3d as d3

        if config["dtype"] == "float64":
            jax.config.update("jax_enable_x64", True)
        self.config, self.traffic = config, traffic
        self.geom = Geometry.of(config)
        self.params = config["params"]
        self.n_inner = int(traffic["n_inner"])
        self.devices = list(devices)
        g = self.geom
        igg.init_global_grid(
            *g.local, dimx=g.dims[0], dimy=g.dims[1], dimz=g.dims[2],
            periodx=g.periods[0], periody=g.periods[1],
            periodz=g.periods[2], devices=self.devices, quiet=True)
        self.grid = igg.get_global_grid()
        self.sharding = igg.sharding_for(3)
        log("mesh device order (gx,gy,gz)->device@coords: " + ", ".join(
            f"{idx}->{d.id}@{list(getattr(d, 'coords', []) or [])}"
            for idx, d in np.ndenumerate(self.grid.mesh.devices)))
        p = d3.Params(**self.params)
        self.step = d3.make_multi_step(self.n_inner, p,
                                       pallas_interpret=interpret)
        if tuple(self.grid.nxyz_g) != g.ref_shape:
            raise RuntimeError(f"the program's global grid "
                               f"{self.grid.nxyz_g} is not the "
                               f"benchmark's {g.ref_shape}")
        self._copy = jax.jit(lambda x: x.copy(), out_shardings=self.sharding)
        self._probe = jax.jit(lambda x: x[:1, :1, :1] + 0)

    def fields(self, seed):
        return make_fields(self.geom, self.params, seed, blocked=True,
                           dtype=self.config["dtype"],
                           sharding=self.sharding)

    def served(self):
        import igg.degrade

        return igg.degrade.active().get(self.family), igg.degrade.status()

    def copy(self, T):
        return self._copy(T)

    def probe(self, T):
        """A one-element read of T on its first device, ordered after the
        dispatch that made T; the window waits on it to bound the
        dispatches in flight (T itself is donated to the next one)."""
        return self._probe(T.addressable_shards[0].data)

    def frame(self, T, kind):
        """An output frame to host numpy: the full volume through
        `igg.gather_interior`, or the mid-z slice of it."""
        import igg

        if kind == "gather":
            return igg.gather_interior(T)
        if kind == "slice":
            z = T.shape[2] // 2
            return np.asarray(T[:, :, z])
        raise ValueError(f"unknown frame kind {kind!r}")

    def compulsory_bytes(self):
        return compulsory_bytes(self.config, self.traffic)

    def close(self):
        import igg

        if igg.grid_is_initialized():
            igg.finalize_global_grid()


@functools.lru_cache(maxsize=None)
def _owned_fn(geom):
    import jax
    import jax.numpy as jnp

    ox, oy, oz = (np.asarray(geom.owner(d)) for d in range(3))

    @jax.jit
    def owned(a):
        return a[jnp.asarray(ox)][:, jnp.asarray(oy)][:, :, jnp.asarray(oz)]

    return owned


def to_reference(geom, blocked, device):
    """The owned cells of a blocked array, in the reference layout on
    `device`."""
    import jax

    return _owned_fn(geom)(jax.device_put(blocked, device))


class Checker:
    """The plain reference over the checked dispatches, built once for a
    cell: `numbers(seed, samples)` runs it over each (before, after) pair
    of blocked arrays the program read and returned, and compares.  With
    `control=True` the reference in the nearest precision below the
    configuration's (`LOWER`) takes the program's place."""

    def __init__(self, config, traffic, device):
        self.geom = Geometry.of(config)
        self.params = config["params"]
        self.dtype = config["dtype"]
        self.device = device
        n = int(traffic["n_inner"])
        self.ref = reference_steps(self.geom, self.params, n, self.dtype)
        self.low = reference_steps(self.geom, self.params, n,
                                   LOWER[self.dtype])

    def numbers(self, seed, samples, control=False):
        import jax

        _, Cp = make_fields(self.geom, self.params, seed, blocked=False,
                            dtype=self.dtype)
        Cp = jax.device_put(Cp, self.device)
        out = []
        for before, after in samples:
            b = to_reference(self.geom, before, self.device)
            want = self.ref(b, Cp)
            got = (self.low(b, Cp) if control
                   else to_reference(self.geom, after, self.device))
            out.append(compare(got, want, b, self.geom))
            del b, want, got
        return out


# --------------------------------------------------------------------------
# Faults: the timed path broken on purpose, to show the check catches it
# --------------------------------------------------------------------------

def faults(config, traffic):
    """name -> wrap(step) -> a broken step `(T, Cp) -> T` on the blocked
    layout: the state returned unchanged; half of the grid (x < X/2 of the
    blocked array) left unstepped; one answer altered where it is produced
    (one cell off by 1% of max|T|); and, on meshes, the exchange between
    chips left out (every block stepped on its own, its halo planes never
    refreshed)."""
    import jax
    import jax.numpy as jnp

    geom = Geometry.of(config)
    params = config["params"]
    n = int(traffic["n_inner"])

    @jax.jit
    def keep_half(before, after):
        x = jnp.arange(before.shape[0])[:, None, None]
        return jnp.where(x < before.shape[0] // 2, before, after)

    mid = tuple(int(geom.owner(d)[geom.global_size(d) // 2])
                for d in range(3))    # an owned cell, not a halo copy

    @jax.jit
    def alter(after):
        return after.at[mid].add(0.01 * jnp.max(jnp.abs(after)))

    def half(step):
        def broken(T, Cp):
            before = T.copy()
            return keep_half(before, step(T, Cp))
        return broken

    out = {"unchanged": lambda step: lambda T, Cp: T,
           "half": half,
           "altered": lambda step: lambda T, Cp: alter(step(T, Cp))}
    if geom.chips > 1:
        block = Geometry(geom.local, (1, 1, 1), (0, 0, 0))
        steps = reference_steps(block, params, n, dx=spacing(geom, params),
                                dt=timestep(geom, params))
        per = jax.vmap(jax.vmap(jax.vmap(steps)))
        (kx, ky, kz), (nx, ny, nz) = geom.dims, geom.local

        def blocks(a):
            return a.reshape(kx, nx, ky, ny, kz, nz).transpose(
                0, 2, 4, 1, 3, 5)

        def unblock(b):
            return b.transpose(0, 3, 1, 4, 2, 5).reshape(
                kx * nx, ky * ny, kz * nz)

        @jax.jit
        def alone(T, Cp):
            return unblock(per(blocks(T), blocks(Cp))).astype(T.dtype)

        out["no_exchange"] = lambda step: lambda T, Cp: jax.device_put(
            alone(T, Cp), T.sharding)
    return out


def build(config, traffic, devices, **kw):
    return Case(config, traffic, devices, **kw)
