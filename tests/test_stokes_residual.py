"""`stokes3d.residual`: the four max-norms the pseudo-transient solver
reads to decide when to stop, in one SPMD program with a global `pmax`.

- On one device they are the max-norms of `divergence` and of
  `iteration_core`'s momentum residuals over the block, computed here
  directly from the arrays.
- On a 2x2x2 mesh they equal the one-device norms of the same global
  problem, periodic and open (the reduction sees every block).
- Every op of the program carries the `igg.residual` scope, and each
  check is counted.
"""

import numpy as np
import pytest

import igg
from igg.models import stokes3d


def _state(local, dims, periodic, n_iters=3):
    """init_fields on a fresh overlap-3 grid, then `n_iters` iterations of
    the XLA path, so the velocities and pressure are non-zero."""
    p = int(periodic)
    igg.init_global_grid(*local, dimx=dims[0], dimy=dims[1], dimz=dims[2],
                         periodx=p, periody=p, periodz=p, overlapx=3,
                         overlapy=3, overlapz=3, quiet=True)
    params = stokes3d.Params()
    P, Vx, Vy, Vz, Rho = stokes3d.init_fields(params, dtype=np.float32)
    it = stokes3d.make_iteration(params, n_inner=n_iters, use_pallas=False,
                                 overlap=False, donate=False)
    return (*it(P, Vx, Vy, Vz, Rho), Rho), params


@pytest.mark.parametrize("periodic", [True, False])
def test_residual_is_the_max_norms_of_the_iteration(periodic):
    import jax.numpy as jnp

    (P, Vx, Vy, Vz, Rho), params = _state((16, 8, 8), (1, 1, 1), periodic)
    got = stokes3d.residual(P, Vx, Vy, Vz, Rho, params)
    kw = stokes3d._pseudo_steps(params)
    scal = dict(dx=kw["dx"], dy=kw["dy"], dz=kw["dz"])
    div = stokes3d.divergence(Vx, Vy, Vz, **scal)
    _, rx, ry, rz = stokes3d.iteration_core(P, Vx, Vy, Vz, Rho, **scal,
                                            mu=kw["mu"], dtP=0.0, dtV=1.0)
    want = [float(jnp.max(jnp.abs(a))) for a in (div, rx, ry, rz)]
    assert all(isinstance(v, float) for v in got)
    assert min(want) > 0
    np.testing.assert_allclose(got, want, rtol=1e-6)


@pytest.mark.parametrize("periodic", [True, False])
def test_residual_reduces_over_the_mesh(eight_devices, periodic):
    # (8, 8, 8) blocks on 2x2x2 hold the global grid of one (13, 13, 13)
    # block at overlap 3: 2*(8-3) cells per dimension (+3 when open).
    (P, Vx, Vy, Vz, Rho), params = _state((8, 8, 8), (2, 2, 2), periodic)
    mesh = stokes3d.residual(P, Vx, Vy, Vz, Rho, params)
    igg.finalize_global_grid()
    (P, Vx, Vy, Vz, Rho), params = _state((13, 13, 13), (1, 1, 1), periodic)
    one = stokes3d.residual(P, Vx, Vy, Vz, Rho, params)
    np.testing.assert_allclose(mesh, one, rtol=1e-5)


def test_residual_ops_are_scoped_and_counted():
    from igg import parallel, profiling, telemetry

    (P, Vx, Vy, Vz, Rho), params = _state((16, 8, 8), (1, 1, 1), True)
    key = 'igg_residual_checks_total{family="stokes3d"}'
    before = telemetry.snapshot().get(key, {}).get("value", 0)
    parallel.free_sharded_cache()
    stokes3d.residual(P, Vx, Vy, Vz, Rho, params)
    stokes3d.residual(P, Vx, Vy, Vz, Rho, params)
    assert telemetry.snapshot()[key]["value"] == before + 2

    (text,) = parallel.compiled_texts()
    work = [i for i in profiling.instructions(text)
            if i.opcode in ("fusion", "reduce", "all-reduce")]
    assert work and all(i.scope == profiling.RESIDUAL for i in work), work
    scopes = profiling.op_scopes()
    assert profiling.RESIDUAL in {s.scope for s in scopes.values()}
