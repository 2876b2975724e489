"""The HydroMech3D cell (`hydro3-256-periodic.ptcheck`) at a size a test
can hold:

- the program agrees with the plain reference within the configuration's
  limits, its state and its residual norms, on one device and on a 2x2x1
  mesh, and every blocked copy of a cell holds the cell's value; the
  control (the reference in bfloat16) does not;
- each fault of the family (`no_darcy` among them), and a convergence
  check that reads zeros or the previous check's norms, makes `correct`
  false through a whole run of the harness, and the run with the path
  whole is `correct`;
- the program's residual norms equal the reference's on the same state;
- the porosity has the background's mean, so the box carries no net
  force;
- the compulsory bytes at 256^3.

Interpret-mode kernels on CPU devices, the local block cut to (16, 16,
128), where the ladder admits the same rung as at 256^3 (the fused
per-iteration kernel), and 10 iterations a dispatch."""

import time

import numpy as np
import pytest

from perfbench import harness

CELL = "hydro3-256-periodic.ptcheck"
TINY = (16, 16, 128)


def cell_config(**kw):
    cell = harness.Cell(CELL)
    config = dict(cell.config, **{"local_shape": list(TINY), **kw})
    return cell, config, dict(cell.traffic, n_inner=10, frame_every=10)


def run(**kw):
    """A whole run of the harness, a convergence check after every
    dispatch as in the cell."""
    traffic = cell_config()[2]
    return harness.run(CELL, 2**31 + 101, 0.5, 0, t0=time.perf_counter(),
                       local_shape=TINY, traffic=traffic, interpret=True,
                       need_tpu=False, **kw)[0]


@pytest.mark.parametrize("dims", [(1, 1, 1), (2, 2, 1)])
def test_program_within_limit_control_over_it(dims):
    import jax

    cell, config, traffic = cell_config(dims=list(dims))
    fam = cell.family
    cell.chips = int(np.prod(dims))
    case, S, Phi, _ = harness.setup(cell, config, traffic, jax.devices(), 7,
                                    interpret=True)
    assert case.served()[0] in config["tiers"]
    before = case.copy(S)
    S = case.step(S, Phi)
    geom = fam.Geometry.of(config)
    for f, a in zip(fam.FIELDS, S):
        # Every blocked copy holds its cell's value: the halo planes
        # exactly, the cells two blocks compute alike to rounding.
        a = np.asarray(a)
        own = a[np.ix_(*(geom.owner(f, d) for d in range(3)))]
        back = own[np.ix_(*(geom.blocked_to_ref(f, d) for d in range(3)))]
        np.testing.assert_allclose(a, back, rtol=0,
                                   atol=1e-6 * np.abs(a).max())
    case.frame(S, "residual")
    checker = fam.Checker(config, traffic, jax.devices()[0])
    prog = checker.numbers(7, [(before, S)])[0]
    ctl = checker.numbers(7, [(before, S)], control=True)[0]
    case.close()
    for name, limit in config["check"].items():
        assert prog[name] < limit < ctl[name], name


def test_whole_run_is_correct():
    r = run()
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 2
    assert set(r["metrics"]) == {"ms_per_step", "setup_s"}
    assert set(r["checks"]) == {"max_rel_err", "accum_rel_err",
                                "residual_rel_err"}


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered",
                                   "stale_pf", "no_darcy"])
def test_broken_path_is_not_correct(fault):
    cell, config, traffic = cell_config()
    wrap = cell.family.faults(config, traffic)[fault]
    r = run(step_wrapper=wrap)
    assert not r["correct"]
    c = r["checks"]["max_rel_err"]
    assert c["value"] > c["limit"]      # the check itself catches it


def _zeros(*args, **kw):
    return (0.0,) * 5


def _stale():
    from igg.models import hydromech3d

    real, last = hydromech3d.residual, []

    def check(*args, **kw):
        last.append(real(*args, **kw))
        return last[-2] if len(last) > 1 else last[-1]
    return check


@pytest.mark.parametrize("check", ["zeros", "stale"])
def test_broken_check_is_not_correct(monkeypatch, check):
    # The check the window runs reads zeros, or the norms of the state
    # before (one dispatch behind).
    from igg.models import hydromech3d

    monkeypatch.setattr(hydromech3d, "residual",
                        _zeros if check == "zeros" else _stale())
    r = run()
    assert not r["correct"]
    c = r["checks"]["residual_rel_err"]
    assert c["value"] > c["limit"]
    assert r["checks"]["max_rel_err"]["value"] <= \
        r["checks"]["max_rel_err"]["limit"]


def test_residual_matches_the_reference():
    import jax

    cell, config, traffic = cell_config()
    fam = cell.family
    case, S, Phi, _ = harness.setup(cell, config, traffic, jax.devices(),
                                    11, interpret=True)
    geom = fam.Geometry.of(config)
    ref = fam.to_reference(geom, S, jax.devices()[0])
    got = case.frame(S, "residual")
    assert case.copy(S).norms == got
    phi = fam.make_phi(geom, config["params"], 11, blocked=False)
    want = [float(v) for v in fam.reference_residual(geom, config["params"])(
        ref, phi)]
    case.close()
    assert min(want) > 0
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_phi_has_no_net_force():
    cell, config, _ = cell_config(local_shape=[64, 64, 64])
    fam = cell.family
    geom = fam.Geometry.of(config)
    p = config["params"]
    phi = np.asarray(fam.make_phi(geom, p, 2**31 + 5, blocked=False),
                     np.float64)
    assert phi.shape == (61, 61, 61)
    assert phi.min() > 0 and phi.max() > 1.2 * p["phi0"]
    rog = p["rho_fg"] * phi + p["rho_sg"] * (1 - phi) \
        - fam.constants(geom, p)["rho_gbg"]
    assert abs(rog.mean()) < 1e-6 * np.abs(rog).max()


def test_compulsory_bytes_at_256():
    cell = harness.Cell(CELL)
    # Six 256^3 centre fields read (Pt, Pf, RPf, EtaC, K_muf, Phi) and five
    # written, six face fields of 256^2 * 257 read and written, f32.
    cells, faces = 256**3, 3 * 256**2 * 257
    assert cell.family.compulsory_bytes(cell.config, cell.traffic) == \
        (11 * cells + 4 * faces) * 4 == 1_546_649_600
