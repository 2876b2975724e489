"""Fused Pallas Stokes iteration vs the XLA composition (interpret mode).

The compiled kernel matches the XLA path to ~1e-7 relative on real TPU
(pinned by tests/test_mega_tpu.py::test_stokes_kernel_compiled_matches_xla;
the round-4 mesh-capable rewrite recomputes send/fallback planes from thin
windows, so Mosaic-vs-XLA reassociation differences of a few ulp are
expected).  Interpret mode on CPU executes the same program structure and
must agree to float32 rounding.
"""

import numpy as np
import pytest

import igg
from igg.models import stokes3d


@pytest.fixture
def selfwrap_grid():
    igg.init_global_grid(16, 8, 8, dimx=1, dimy=1, dimz=1,
                         periodx=1, periody=1, periodz=1,
                         overlapx=3, overlapy=3, overlapz=3, quiet=True)
    yield igg.get_global_grid()
    igg.finalize_global_grid()


def _fields():
    import jax.numpy as jnp

    params = stokes3d.Params()
    P, Vx, Vy, Vz, Rho = stokes3d.init_fields(params, dtype=np.float32)
    mk = lambda A, f, s: f(jnp.arange(A.size, dtype=np.float32)
                           .reshape(A.shape) * s)
    return (mk(P, jnp.sin, 1.0), mk(Vx, jnp.cos, 0.01),
            mk(Vy, jnp.sin, 0.02), mk(Vz, jnp.cos, 0.03), Rho)


def test_supported(selfwrap_grid):
    from igg.ops import stokes_pallas_supported

    import jax
    P = jax.ShapeDtypeStruct((16, 8, 8), np.float32)
    assert stokes_pallas_supported(selfwrap_grid, P)


def test_not_supported_wrong_overlap():
    from igg.ops import stokes_pallas_supported

    import jax
    igg.init_global_grid(16, 8, 8, dimx=1, dimy=1, dimz=1,
                         periodx=1, periody=1, periodz=1, quiet=True)
    P = jax.ShapeDtypeStruct((16, 8, 8), np.float32)
    assert not stokes_pallas_supported(igg.get_global_grid(), P)
    igg.finalize_global_grid()


def test_supported_open_boundary_and_mesh():
    """Round 4: the kernel is mesh-capable — open boundaries and multi-
    device decompositions are in scope (the exchange engine handles them)."""
    from igg.ops import stokes_pallas_supported

    import jax
    igg.init_global_grid(16, 8, 8, dimx=1, dimy=1, dimz=1,
                         periodx=1, periody=0, periodz=1,
                         overlapx=3, overlapy=3, overlapz=3, quiet=True)
    P = jax.ShapeDtypeStruct((16, 8, 8), np.float32)
    assert stokes_pallas_supported(igg.get_global_grid(), P)
    igg.finalize_global_grid()
    igg.init_global_grid(16, 8, 8, overlapx=3, overlapy=3, overlapz=3,
                         quiet=True)   # 8 devices, open boundaries
    assert stokes_pallas_supported(igg.get_global_grid(), P)
    igg.finalize_global_grid()


def test_use_pallas_on_unsupported_grid_raises():
    igg.init_global_grid(16, 8, 8, dimx=1, dimy=1, dimz=1,
                         periodx=1, periody=1, periodz=1, quiet=True)  # ol 2
    params = stokes3d.Params()
    kw = stokes3d._pseudo_steps(params)
    fields = _fields()
    with pytest.raises(igg.GridError, match="fused Stokes"):
        stokes3d.local_iteration(*fields, **kw, use_pallas=True,
                                 pallas_interpret=True)
    igg.finalize_global_grid()


def test_matches_xla_one_iteration(selfwrap_grid):
    params = stokes3d.Params()
    kw = stokes3d._pseudo_steps(params)
    fields = _fields()
    ref = stokes3d.local_iteration(*fields, **kw)
    out = stokes3d.local_iteration(*fields, **kw, use_pallas=True,
                                   pallas_interpret=True)
    for name, a, b in zip(("P", "Vx", "Vy", "Vz"), ref, out):
        a, b = np.asarray(a), np.asarray(b)
        rel = np.max(np.abs(a - b)) / (np.max(np.abs(a)) + 1e-30)
        assert rel < 1e-6, (name, rel)


def test_make_iteration_pallas_through_sharded(selfwrap_grid):
    """The compiled entry (igg.sharded / shard_map + fori_loop): interpret
    kernels under shard_map need the check_vma workaround — this is the path
    the benchmark and driver dryrun use."""
    params = stokes3d.Params()
    it_x = stokes3d.make_iteration(params, n_inner=2, donate=False,
                                   use_pallas=False)
    it_p = stokes3d.make_iteration(params, n_inner=2, donate=False,
                                   use_pallas=True, pallas_interpret=True)
    fields = _fields()
    ref = it_x(*fields)
    out = it_p(*fields)
    for name, a, b in zip(("P", "Vx", "Vy", "Vz"), ref, out):
        a, b = np.asarray(a), np.asarray(b)
        rel = np.max(np.abs(a - b)) / (np.max(np.abs(a)) + 1e-30)
        assert rel < 1e-5, (name, rel)


def test_matches_xla_chained(selfwrap_grid):
    """Five chained iterations: halo invariants carried by the kernel feed
    the next iteration's windows."""
    params = stokes3d.Params()
    kw = stokes3d._pseudo_steps(params)
    fields = _fields()
    r = o = fields[:4]
    Rho = fields[4]
    for _ in range(5):
        r = stokes3d.local_iteration(*r, Rho, **kw)
        o = stokes3d.local_iteration(*o, Rho, **kw, use_pallas=True,
                                     pallas_interpret=True)
    for name, a, b in zip(("P", "Vx", "Vy", "Vz"), r, o):
        a, b = np.asarray(a), np.asarray(b)
        rel = np.max(np.abs(a - b)) / (np.max(np.abs(a)) + 1e-30)
        assert rel < 1e-5, (name, rel)


def _mesh_compare(grid_kw, n_iters=3, tol=2e-5):
    """Shared body: fused kernel (interpret) vs the overlap-semantics XLA
    path on a sharded 8-device CPU mesh."""
    igg.init_global_grid(16, 8, 8, overlapx=3, overlapy=3, overlapz=3,
                         quiet=True, **grid_kw)
    params = stokes3d.Params(lx=4.0, ly=4.0, lz=4.0)
    fields = stokes3d.init_fields(params, dtype=np.float32)
    ref = stokes3d.make_iteration(params, donate=False, use_pallas=False,
                                  overlap=True, n_inner=n_iters)
    pal = stokes3d.make_iteration(params, donate=False, use_pallas=True,
                                  pallas_interpret=True, n_inner=n_iters)
    r = ref(*fields)
    o = pal(*fields)
    for name, a, b in zip(("P", "Vx", "Vy", "Vz"), r, o):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        rel = np.max(np.abs(a - b)) / (np.max(np.abs(a)) + 1e-30)
        assert rel < tol, (name, rel, grid_kw)
    igg.finalize_global_grid()


def test_mesh_222_periodic_matches_overlap_path():
    """VERDICT round-3 item 1: the fused Stokes iteration on the (2,2,2)
    sharded CPU mesh must reproduce the overlap-semantics XLA path."""
    _mesh_compare(dict(periodx=1, periody=1, periodz=1))


def test_mesh_222_open_matches_overlap_path():
    """Open boundaries: stale-halo no-write at edge devices."""
    _mesh_compare({})


def test_mesh_421_mixed_wrap_matches_overlap_path():
    """(4,2,1) mesh: z wrapped in-VMEM, x/y exchanged, mixed periodicity."""
    _mesh_compare(dict(dimx=4, dimy=2, dimz=1, periodx=1, periodz=1))


def test_mesh_811_matches_overlap_path():
    """(8,1,1): y/z wrapped, only x exchanged, open x boundary."""
    _mesh_compare(dict(dimx=8, dimy=1, dimz=1, periody=1, periodz=1))


def test_decomposition_invariance_open_boundaries():
    """Round-4 regression: the fused iteration on an open-boundary (2,2,2)
    mesh must reproduce the PLAIN single-device physics on the gathered
    interior.  Pins the open-boundary fallback-plane semantics: the
    full-shape pressure update writes the outermost global planes, and the
    fallback must preserve those computed values (slab-computed planes),
    not revert them to pre-iteration values."""
    results = {}
    for tag, kw, local in (("multi", {}, (16, 8, 8)),
                           ("single", dict(dimx=1, dimy=1, dimz=1),
                            (29, 13, 13))):
        igg.init_global_grid(*local, overlapx=3, overlapy=3, overlapz=3,
                             quiet=True, **kw)
        params = stokes3d.Params(lx=4.0, ly=4.0, lz=4.0)
        P, Vx, Vy, Vz, Rho = stokes3d.init_fields(params, dtype=np.float32)
        it = stokes3d.make_iteration(
            params, donate=False, n_inner=2,
            use_pallas=(tag == "multi"),
            pallas_interpret=(tag == "multi"))
        S = (P, Vx, Vy, Vz)
        for _ in range(3):
            S = it(*S, Rho)
        results[tag] = tuple(np.asarray(igg.gather_interior(F), np.float64)
                             for F in S)
        igg.finalize_global_grid()
    for i, name in enumerate(("P", "Vx", "Vy", "Vz")):
        a, b = results["multi"][i], results["single"][i]
        assert a.shape == b.shape, (name, a.shape, b.shape)
        scale = max(np.abs(b).max(), 1e-30)
        assert np.abs(a - b).max() <= 1e-5 * scale, name


_PINGPONG_GRIDS = {
    "selfwrap": dict(dimx=1, dimy=1, dimz=1, periodx=1, periody=1,
                     periodz=1),
    "openx211": dict(dimx=2, dimy=1, dimz=1, periody=1, periodz=1),
}


@pytest.mark.parametrize("grid, n, entry", [
    *[(g, n, "stokes_iterations") for g in sorted(_PINGPONG_GRIDS)
      for n in (0, 1, 2, 3, 4, 7)],
    *[(g, 5, "make_iteration") for g in sorted(_PINGPONG_GRIDS)]])
def test_pingpong_matches_sequential_bitwise(grid, n, entry):
    """The HBM ping-pong (`stokes_iterations`: fresh buffers first, then
    each iteration into the state set the one before it read) equals `n`
    sequential `fused_stokes_iteration` dispatches bit for bit, on the
    self-wrap grid and a (2,1,1) mesh with open x, and so does the
    factory's program with donated inputs.  (Where y or z is open too, the
    edge lines of those planes come from XLA-compiled windows that XLA:CPU
    compiles 1 ulp apart in different programs: a plain `fori_loop` of the
    kernel already differs from sequential dispatches there.)"""
    import jax

    from igg.ops import fused_stokes_iteration
    from igg.ops.stokes_pallas import stokes_iterations

    kw_grid = _PINGPONG_GRIDS[grid]
    igg.init_global_grid(16, 8, 8, overlapx=3, overlapy=3, overlapz=3,
                         quiet=True, devices=jax.devices()[:kw_grid["dimx"]],
                         **kw_grid)
    params = stokes3d.Params()
    kw = dict(stokes3d._pseudo_steps(params), interpret=True)
    *S, Rho = _fields()
    one = igg.sharded(lambda P, Vx, Vy, Vz, Rho: fused_stokes_iteration(
        P, Vx, Vy, Vz, Rho, **kw), check_vma=False)
    want = tuple(S)
    for _ in range(n):
        want = one(*want, Rho)
    if entry == "make_iteration":
        it = stokes3d.make_iteration(params, n_inner=n, donate=True,
                                     use_pallas=True, pallas_interpret=True,
                                     trapezoid=False)
        got = it(*S, Rho)
        assert it.ladder.tiers[0].name == "stokes3d.trapezoid"
        assert igg.degrade.active()["stokes3d"] == "stokes3d.mosaic"
    else:
        got = igg.sharded(
            lambda P, Vx, Vy, Vz, Rho: stokes_iterations(
                (P, Vx, Vy, Vz), Rho, n, **kw), check_vma=False)(*S, Rho)
    for name, a, b in zip(("P", "Vx", "Vy", "Vz"), got, want):
        assert np.array_equal(np.asarray(a), np.asarray(b)), name


def test_pingpong_counter_per_dispatch(selfwrap_grid):
    """`igg_pingpong_iterations_total` grows by `n_inner - 1` a dispatch
    of the Mosaic rung, the iterations that wrote into the partner set,
    beside `igg_tier_dispatch_total`'s one."""
    from igg import telemetry

    n_inner = 5
    it = stokes3d.make_iteration(n_inner=n_inner, donate=False,
                                 use_pallas=True, pallas_interpret=True,
                                 trapezoid=False)
    labels = dict(family="stokes3d", tier="stokes3d.mosaic")
    pingpong = telemetry.counter("igg_pingpong_iterations_total", **labels)
    dispatches = telemetry.counter("igg_tier_dispatch_total", **labels)
    p0, d0 = pingpong.value, dispatches.value
    *S, Rho = _fields()
    for k in (1, 2):
        S = it(*S, Rho)
        assert dispatches.value - d0 == k
        assert pingpong.value - p0 == k * (n_inner - 1)
