"""`igg.models.hydromech3d`: the two-phase PT system of ParallelStencil's
HydroMech3D on igg's normal path.

- Both rungs (`hydromech3d.xla`, and `hydromech3d.mosaic` in interpret
  mode and under the TPU interpreter) against the plain f32 reference of
  the benchmark's family (`perfbench/families/hydromech3d.py`: `jnp.roll`
  differences on the periodic box), from seeded states, on one device and
  on a (2,2,1) mesh; three physical steps with the porosity update; the
  five residual norms.
- The Mosaic rung against the XLA rung on open boundaries.
- The ladder falls to `hydromech3d.xla` where admission refuses.
- Stokes's `iteration_core`, now built on the shared momentum chain,
  gives the chain's former outputs bit for bit from the same ops.
- The kernel's margin rows carried in VMEM, at bx 4 over 2, 3 and 4
  slabs, periodic and open x, one device and a mesh split along x,
  under both interpreters: against the same slab arithmetic fed by
  one-row margin blocks (the data movement the carry replaced) and
  against the XLA rung.
- The ping-pong, margin-row and residual counters.
"""

import importlib.util
import os
import sys

import numpy as np
import pytest

import igg
from igg.models import hydromech3d as hm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOCAL = (16, 16, 128)    # the smallest lane-aligned block the kernel slabs


def _family():
    spec = importlib.util.spec_from_file_location(
        "hm3d_family_for_tests",
        os.path.join(ROOT, "perfbench", "families", "hydromech3d.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


FAM = _family()
PARAMS = {k: getattr(hm.Params(), k) for k in hm.Params.__dataclass_fields__}


def _grid(local=LOCAL, dims=(1, 1, 1), periodic=True):
    p = int(periodic)
    igg.init_global_grid(*local, dimx=dims[0], dimy=dims[1], dimz=dims[2],
                         periodx=p, periody=p, periodz=p, overlapx=3,
                         overlapy=3, overlapz=3, quiet=True,
                         devices=__import__("jax").devices()[
                             :int(np.prod(dims))])
    return FAM.Geometry(tuple(local), tuple(dims)) if periodic else None


def _reference_state(shape, seed):
    """A state and porosity on the reference layout of `shape`, with
    values of the scales a solve passes through (the pressures' gap spans
    the viscosity's tanh transition)."""
    rng = np.random.default_rng(seed)

    def field(lo, hi):
        return rng.uniform(lo, hi, shape).astype(np.float32)

    phi0 = PARAMS["phi0"]
    Phi = field(0.6 * phi0, 1.4 * phi0)
    ref = FAM.State(
        Pt=field(-0.02, 0.02), Pf=field(-0.02, 0.02), RPf=field(-0.1, 0.1),
        EtaC=PARAMS["eta_c0"] * phi0 / Phi * field(0.5, 1.5),
        K_muf=Phi / phi0 * field(0.5, 1.5),
        Vx=field(-1e-2, 1e-2), Vy=field(-1e-2, 1e-2), Vz=field(-1e-2, 1e-2),
        dVx=field(-1e-5, 1e-5), dVy=field(-1e-5, 1e-5),
        dVz=field(-1e-5, 1e-5))
    return ref, Phi


def _blocked(geom, ref, Phi):
    """`ref` and `Phi` in the blocked layout of `geom` on the live grid."""
    import jax

    def blocked(a, name):
        a = np.asarray(a)
        return a[np.ix_(*(geom.blocked_to_ref(name, d) for d in range(3)))]

    sh = igg.sharding_for(3)
    return (tuple(jax.device_put(blocked(a, f), sh)
                  for f, a in zip(FAM.FIELDS, ref)),
            jax.device_put(blocked(Phi, "Phi"), sh))


def _seeded(geom, seed):
    ref, Phi = _reference_state(geom.ref_shape, seed)
    return (ref, Phi, *_blocked(geom, ref, Phi))


def _rel(got_blocked, want_ref, geom):
    import jax

    got = FAM.to_reference(geom, got_blocked, jax.devices()[0])
    return {f: float(np.abs(np.asarray(g) - np.asarray(w)).max()
                     / np.abs(np.asarray(w)).max())
            for f, g, w in zip(FAM.FIELDS, got, want_ref)}


# Rounding of the same f32 formulas, associated differently in places
# (the reference divides mus by Phi before scaling it, the rungs scale
# first), on seeded states that are rough on the grid scale: the Darcy
# flux divergence of a rough Pf cancels to a few digits in RPf, and the
# viscosity's tanh transition of width lamPe = 0.01, which the seeded
# pressure gaps span, amplifies a gap's last bit about 50-fold in EtaC.
# Readings here: at most 2.9e-5 (RPf, on the mesh); a wrong term reads
# O(1).
TOL = 1e-4


@pytest.mark.parametrize("rung,dims", [
    ("xla", (1, 1, 1)), ("mosaic", (1, 1, 1)), ("mosaic", (2, 2, 1))])
def test_rung_matches_reference(rung, dims):
    geom = _grid(dims=dims)
    ref, Phi_r, state, Phi = _seeded(geom, 11)
    it = hm.make_iteration(n_inner=3, use_pallas=rung == "mosaic",
                           pallas_interpret=True, donate=False)
    got = it(*state, Phi)
    assert igg.degrade.active()["hydromech3d"] == f"hydromech3d.{rung}"
    want = FAM.reference_iters(geom, PARAMS, 3)(ref, Phi_r)
    rel = _rel(got, want, geom)
    assert max(rel.values()) < TOL, rel
    # every blocked copy of a cell holds the cell's value
    for f, a in zip(FAM.FIELDS, got):
        a = np.asarray(a)
        own = a[np.ix_(*(geom.owner(f, d) for d in range(3)))]
        back = own[np.ix_(*(geom.blocked_to_ref(f, d) for d in range(3)))]
        np.testing.assert_array_equal(a, back, err_msg=f)


def test_mosaic_under_tpu_interpreter_matches_reference():
    # The compiled-mode kernel (VMEM budget, compiler params, the
    # ping-pong's aliased dead state set) run by the TPU interpreter.
    import jax
    from jax.experimental.pallas import tpu as pltpu

    from igg.ops.hydromech_pallas import hydromech_iterations

    geom = _grid()
    ref, Phi_r, state, Phi = _seeded(geom, 5)
    c = hm.pseudo_steps(hm.Params())
    with jax.enable_x64(False), pltpu.force_tpu_interpret_mode():
        got = jax.jit(lambda S, P: hydromech_iterations(S, P, 4, c=c))(
            state, Phi)
        got = [np.asarray(a) for a in got]
    want = FAM.reference_iters(geom, PARAMS, 4)(ref, Phi_r)
    rel = _rel(got, want, geom)
    assert max(rel.values()) < TOL, rel


def test_mosaic_matches_xla_on_open_boundaries():
    # Open edges keep the planes the iteration read (no-write); the mesh
    # moves planes across the inner edges.
    import jax

    _grid(dims=(2, 2, 1), periodic=False)
    rng = np.random.default_rng(3)
    grid = igg.get_global_grid()
    sh = igg.sharding_for(3)
    n = [d * s for d, s in zip(grid.dims, grid.nxyz)]
    shapes = [tuple(n)] * 5 + [tuple(n[d] + grid.dims[d] * (d == i)
                                     for d in range(3)) for i in range(3)] * 2
    scale = [2e-2, 2e-2, 1e-1, 1.0, 1.0, 1e-2, 1e-2, 1e-2, 1e-5, 1e-5, 1e-5]
    state = [jax.device_put((s * rng.uniform(-1, 1, sh_)).astype(np.float32)
                            + (1.0 if i in (3, 4) else 0.0), sh)
             for i, (s, sh_) in enumerate(zip(scale, shapes))]
    Phi = jax.device_put(rng.uniform(0.006, 0.014, n).astype(np.float32), sh)
    out = {}
    for rung in ("xla", "mosaic"):
        it = hm.make_iteration(n_inner=3, use_pallas=rung == "mosaic",
                               pallas_interpret=True, donate=False)
        out[rung] = [np.asarray(a) for a in it(*state, Phi)]
    # Rounding apart (XLA:CPU contracts some products into FMAs, the
    # interpreted kernel does not; 3e-6 of max|RPf| where a rough RPf
    # cancels), the same values: a plane left stale or written from the
    # wrong side reads O(1).
    for f, a, b in zip(hm.STATE, out["xla"], out["mosaic"]):
        np.testing.assert_allclose(b, a, rtol=0, atol=2e-5 * np.abs(a).max(),
                                   err_msg=f)


def test_physical_steps_match_reference():
    # Three physical steps from the seeded anomaly at rest (the benchmark's
    # fields): a PT solve of 20 iterations at fixed porosity on the XLA
    # rung, then the porosity update; the state and Phi agree with the
    # reference's.
    import jax.numpy as jnp

    geom = _grid()
    Phi_r = np.asarray(FAM.make_phi(geom, PARAMS, 23, blocked=False))
    zero = np.zeros_like(Phi_r)
    phi0 = PARAMS["phi0"]
    ref = FAM.State(zero, zero, zero, PARAMS["eta_c0"] * phi0 / Phi_r,
                    PARAMS["k_muf0"] * Phi_r / phi0, *(zero,) * 6)
    state, Phi = _blocked(geom, ref, Phi_r)
    it = hm.make_iteration(n_inner=20, use_pallas=False, donate=False)
    ref_it = FAM.reference_iters(geom, PARAMS, 20)
    update = FAM.porosity_update(geom, PARAMS)
    divV = jnp.zeros_like(Phi)
    divV_r = jnp.zeros(geom.ref_shape, jnp.float32)
    S, Phi0 = state, Phi_r
    for _ in range(3):
        S = it(*S, Phi)
        Phi, divV, dt = hm.physical_step(S, Phi, divV)
        ref = ref_it(ref, Phi_r)
        Phi_r, divV_r = update(Phi_r, divV_r, ref)
        assert dt > 0
    rel = _rel(S, ref, geom)
    assert max(rel.values()) < TOL, rel
    own = np.asarray(Phi)[np.ix_(*(geom.owner("Phi", d) for d in range(3)))]
    change = np.abs(np.asarray(Phi_r) - Phi0).max()
    assert change > 1e-3 * np.abs(Phi0).max()
    assert np.abs(own - np.asarray(Phi_r)).max() < TOL * change


def test_residual_norms_match_reference():
    import jax

    from igg import telemetry

    geom = _grid()
    ref, Phi_r, state, Phi = _seeded(geom, 31)
    it = hm.make_iteration(n_inner=2, use_pallas=False, donate=False)
    S = it(*state, Phi)
    checks = telemetry.counter("igg_residual_checks_total",
                               family="hydromech3d")
    c0 = checks.value
    got = hm.residual(*S, Phi)
    want = [float(v) for v in FAM.reference_residual(geom, PARAMS)(
        FAM.to_reference(geom, S, jax.devices()[0]), Phi_r)]
    assert len(got) == 5 and min(want) > 0
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert checks.value == c0 + 1


def test_residual_reduces_over_the_mesh():
    # The same global state on a (2,2,1) mesh and on one device.
    geom = _grid(dims=(2, 2, 1))
    ref, Phi_r, state, Phi = _seeded(geom, 41)
    mesh = hm.residual(*state, Phi)
    igg.finalize_global_grid()
    geom1 = _grid(local=(29, 29, 128))
    state, Phi = _blocked(geom1, ref, Phi_r)
    one = hm.residual(*state, Phi)
    np.testing.assert_allclose(mesh, one, rtol=1e-6)


@pytest.mark.parametrize("why", ["overlap", "shape", "vmem"])
def test_ladder_falls_to_xla_where_admission_refuses(why, monkeypatch):
    import jax

    from igg.ops import _vmem
    from igg.ops.hydromech_pallas import hydromech_pallas_supported

    if why == "overlap":
        igg.init_global_grid(16, 16, 16, periodx=1, periody=1, periodz=1,
                             quiet=True, devices=jax.devices()[:1])
    elif why == "shape":
        _grid(local=(12, 16, 16))
    else:
        _grid(local=(16, 64, 128))
        monkeypatch.setattr(_vmem, "VMEM_CAP", 8 * 2**20)
    grid = igg.get_global_grid()
    Pt = igg.zeros(tuple(grid.nxyz), dtype=np.float32)
    adm = hydromech_pallas_supported(grid, Pt, interpret=why != "vmem")
    assert not adm and adm.reason
    if why == "vmem":
        assert "VMEM" in adm.reason
        return
    state, Phi = hm.init_fields()
    it = hm.make_iteration(n_inner=2, pallas_interpret=True)
    it(*state, Phi)
    assert igg.degrade.active()["hydromech3d"] == "hydromech3d.xla"
    assert [t.name for t in it.ladder.tiers] == ["hydromech3d.mosaic",
                                                 "hydromech3d.xla"]


def _parent_iteration_core(P, Vx, Vy, Vz, Rho, *, dx, dy, dz, mu, dtP, dtV):
    """`stokes3d.iteration_core` as it stood before the momentum chain was
    shared (buoyancy on z), verbatim."""
    from igg.models.stokes3d import divergence

    divV = divergence(Vx, Vy, Vz, dx=dx, dy=dy, dz=dz)
    P = P - dtP * divV
    txx = 2.0 * mu * ((Vx[1:, :, :] - Vx[:-1, :, :]) / dx - divV / 3.0)
    tyy = 2.0 * mu * ((Vy[:, 1:, :] - Vy[:, :-1, :]) / dy - divV / 3.0)
    tzz = 2.0 * mu * ((Vz[:, :, 1:] - Vz[:, :, :-1]) / dz - divV / 3.0)
    txy = mu * ((Vx[1:-1, 1:, :] - Vx[1:-1, :-1, :]) / dy
                + (Vy[1:, 1:-1, :] - Vy[:-1, 1:-1, :]) / dx)
    txz = mu * ((Vx[1:-1, :, 1:] - Vx[1:-1, :, :-1]) / dz
                + (Vz[1:, :, 1:-1] - Vz[:-1, :, 1:-1]) / dx)
    tyz = mu * ((Vy[:, 1:-1, 1:] - Vy[:, 1:-1, :-1]) / dz
                + (Vz[:, 1:, 1:-1] - Vz[:, :-1, 1:-1]) / dy)
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
    rz = rz + 0.5 * (Rho[1:-1, 1:-1, 1:] + Rho[1:-1, 1:-1, :-1])
    return P, dtV * rx, dtV * ry, dtV * rz


def test_shared_chain_gives_stokes_iteration_core_bitwise():
    import jax

    from igg.models import stokes3d

    rng = np.random.default_rng(0)
    n = (9, 10, 11)
    P, Rho = (rng.standard_normal(n).astype(np.float32) for _ in range(2))
    V = [rng.standard_normal(tuple(s + (d == i) for d, s in enumerate(n)))
         .astype(np.float32) for i in range(3)]
    kw = dict(dx=0.3, dy=0.4, dz=0.5, mu=1.3, dtP=0.7, dtV=0.01)
    with jax.enable_x64(False):
        new = stokes3d.iteration_core(P, *V, Rho, **kw)
        old = _parent_iteration_core(P, *V, Rho, **kw)
        for a, b in zip(new, old):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # the same ops in the same order
        jn = jax.make_jaxpr(lambda *a: stokes3d.iteration_core(*a, **kw))(
            P, *V, Rho)
        jo = jax.make_jaxpr(lambda *a: _parent_iteration_core(*a, **kw))(
            P, *V, Rho)
    assert str(jn) == str(jo)


def test_pingpong_counter_per_dispatch():
    from igg import telemetry

    _grid()
    state, Phi = hm.init_fields()
    labels = dict(family="hydromech3d", tier="hydromech3d.mosaic")
    pingpong = telemetry.counter("igg_pingpong_iterations_total", **labels)
    dispatches = telemetry.counter("igg_tier_dispatch_total", **labels)
    p0, d0 = pingpong.value, dispatches.value
    it = hm.make_iteration(n_inner=5, pallas_interpret=True)
    for k in (1, 2):
        state = it(*state, Phi)
        assert dispatches.value - d0 == k
        assert pingpong.value - p0 == k * 4


def _margin_fetch_iteration(*args, c, bx, interpret):
    """The same slab arithmetic (`hydromech_pallas._slab`) and exchange as
    the fused iteration, with the data movement the VMEM carry replaced:
    independent programs, each reading its slab and its margin rows -1,
    bx (and bx + 1 of Vx) as one-row blocks through modular index maps."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from igg.ops import hydromech_pallas as hp
    from igg.ops.diffusion_pallas import _wrap_dims, _wrap_set

    *state, Phi = args
    grid = igg.get_global_grid()
    nb = state[0].shape[0] // bx
    f = dict(zip(hm.STATE, state), Phi=Phi)
    operands, in_specs, counts = [], [], []
    for name, after in hp._MARGINS:
        F = f[name]
        rows = (-1, "c", *range(bx, bx + after))
        counts.append(len(rows))
        for r in rows:
            operands.append(F)
            if r == "c":
                in_specs.append(pl.BlockSpec((bx, *F.shape[1:]),
                                             lambda i: (i, 0, 0)))
            else:
                in_specs.append(pl.BlockSpec(
                    (1, *F.shape[1:]),
                    lambda i, r=r, s=F.shape[0]: ((i * bx + r) % s, 0, 0)))
    for name in hp._SLAB_ONLY:
        operands.append(f[name])
        in_specs.append(pl.BlockSpec((bx, *f[name].shape[1:]),
                                     lambda i: (i, 0, 0)))
    wy, wz = _wrap_dims(grid)
    ols = [tuple(grid.ol_of_local(d, f[n].shape) for d in (1, 2))
           for n in hm.EXCHANGED]

    def kernel(*refs):
        wins, k = [], 0
        for n in counts:
            wins.append(jnp.concatenate([r[:] for r in refs[k:k + n]],
                                        axis=0))
            k += n
        cRPf, *cdV = refs[k:k + len(hp._SLAB_ONLY)]
        hp._slab(*wins, cRPf, cdV, refs[k + len(hp._SLAB_ONLY):], bx=bx, c=c,
                 ols=ols, wrap_y=wy, wrap_z=wz)

    new = list(pl.pallas_call(
        kernel, grid=(nb,), in_specs=in_specs,
        out_specs=[pl.BlockSpec((bx, *F.shape[1:]), lambda i: (i, 0, 0))
                   for F in state],
        out_shape=[jax.ShapeDtypeStruct(F.shape, F.dtype) for F in state],
        interpret=interpret)(*operands))
    idx = [hm.STATE.index(n) for n in hm.EXCHANGED]
    done = hp._exchange([new[i] for i in idx], [state[i] for i in idx],
                        grid, _wrap_set((wy, wz)))
    for i, F in zip(idx, done):
        new[i] = F
    return tuple(new)


@pytest.mark.parametrize("interpreter", ["generic", "tpu"])
@pytest.mark.parametrize("nx,dims,periodic", [
    (8, (1, 1, 1), True),      # 2 slabs: two sequences of one
    (16, (1, 1, 1), True),     # 4 slabs: two sequences of two
    (12, (1, 1, 1), True),     # 3 slabs: one sequence
    (16, (1, 1, 1), False),    # open x
    (12, (2, 1, 1), True),     # a mesh split along x, 3 slabs a device
    (16, (2, 1, 1), False),    # ... open, 4 slabs a device
], ids=["nb2", "nb4", "nb3", "nb4-open", "x2-nb3", "x2-nb4-open"])
def test_carry_matches_margin_fetch(nx, dims, periodic, interpreter):
    import contextlib

    import jax
    from jax.experimental.pallas import tpu as pltpu

    from igg.ops.hydromech_pallas import fused_hydromech_iteration

    _grid(local=(nx, 8, 128), dims=dims, periodic=periodic)
    grid = igg.get_global_grid()
    rng = np.random.default_rng(nx + 10 * dims[0] + periodic)
    sh = igg.sharding_for(3)
    n = [d * s for d, s in zip(grid.dims, grid.nxyz)]
    shapes = [tuple(n)] * 5 + [tuple(n[d] + grid.dims[d] * (d == i)
                                     for d in range(3)) for i in range(3)] * 2
    scale = [2e-2, 2e-2, 1e-1, 1.0, 1.0, 1e-2, 1e-2, 1e-2, 1e-5, 1e-5, 1e-5]
    state = [jax.device_put((s * rng.uniform(-1, 1, sh_)).astype(np.float32)
                            + (1.0 if i in (3, 4) else 0.0), sh)
             for i, (s, sh_) in enumerate(zip(scale, shapes))]
    Phi = jax.device_put(rng.uniform(0.006, 0.014, n).astype(np.float32), sh)
    c = hm.pseudo_steps(hm.Params())
    generic = interpreter == "generic"
    ctx = (contextlib.nullcontext if generic
           else pltpu.force_tpu_interpret_mode)

    def run(fn):
        with jax.enable_x64(False), ctx():
            out = igg.sharded(fn, check_vma=False)(*state, Phi)
            return [np.asarray(a) for a in out]

    got = run(lambda *A: fused_hydromech_iteration(*A, c=c, bx=4,
                                                   interpret=generic))
    ref = run(lambda *A: _margin_fetch_iteration(*A, c=c, bx=4,
                                                 interpret=generic))
    xla = run(lambda *A: hm.local_iteration(*A[:-1], Phi=A[-1], c=c))
    # The same f32 formulas on the same values: bitwise on the chip
    # (PERF.md §6).  XLA:CPU fuses the two interpreted programs apart and
    # contracts different products into FMAs, so here a cell may differ
    # by an ulp or two of its value; a margin row from the wrong place
    # reads O(1).
    for f, a, b, x in zip(hm.STATE, got, ref, xla):
        top = np.abs(b).max()
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-6 * top, err_msg=f)
        np.testing.assert_allclose(a, x, rtol=0, atol=2e-5 * top, err_msg=f)


def test_margin_row_counters_per_dispatch():
    from igg import telemetry
    from igg.ops.hydromech_pallas import margin_rows

    _grid(local=(24, 16, 128))     # bx 8, 3 slabs: one sequence
    state, Phi = hm.init_fields()
    labels = dict(family="hydromech3d", tier="hydromech3d.mosaic")
    carried = telemetry.counter("igg_margin_rows_carried_total", **labels)
    fetched = telemetry.counter("igg_margin_rows_fetched_total", **labels)
    c0, f0 = carried.value, fetched.value
    it = hm.make_iteration(n_inner=5, pallas_interpret=True)
    for k in (1, 2):
        state = it(*state, Phi)
        # 17 margin rows a slab: two slabs' from the ring, the
        # sequence's edge rows from HBM, five iterations a dispatch
        assert carried.value - c0 == k * 5 * 34
        assert fetched.value - f0 == k * 5 * 17
    assert margin_rows(3) == (34, 17)
    # 256^3 at bx 4: 64 slabs in two sequences
    c, f = margin_rows(64)
    assert (c, f) == (1054, 34) and round(c / (c + f), 3) == 0.969
