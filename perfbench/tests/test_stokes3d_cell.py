"""The Stokes cell (`st3-256-periodic.ptcheck`) at a size a test can hold:

- the program agrees with the plain reference within the configuration's
  limits, its state and its residual norms, on one device and on a 2x2x1
  mesh, and every blocked copy of a cell holds the cell's value; the
  control (the reference in bfloat16) does not;
- each fault of the family, and a convergence check that reads zeros or
  the previous check's norms, makes `correct` false through a whole run
  of the harness, and the run with the path whole is `correct`;
- the program's residual norms equal the reference's on the same state;
- the residual reader against a synthetic trace and scope map, and silent
  where the program names nothing for it.

Interpret-mode kernels on CPU devices, the local block cut to (16, 16,
16), where the ladder admits the same rung as at 256^3 (the per-iteration
fused kernel: the resident chunk tier refuses both shapes, and the banded
rung stays off while that kernel keeps its full slab height), and 10
iterations a dispatch."""

import os
import time

import numpy as np
import pytest

from perfbench import harness
from perfbench import trace as tr

CELL = "st3-256-periodic.ptcheck"
TINY = (16, 16, 16)


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
    case, S, Rho, _ = harness.setup(cell, config, traffic, jax.devices(), 7,
                                    interpret=True)
    assert case.served()[0] in config["tiers"]
    before = case.copy(S)
    S = case.step(S, Rho)
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
    assert set(r["checks"]) == {"max_rel_err", "residual_rel_err"}


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered",
                                   "stale_vz"])
def test_broken_path_is_not_correct(fault):
    cell, config, traffic = cell_config()
    wrap = cell.family.faults(config, traffic)[fault]
    r = run(step_wrapper=wrap)
    assert not r["correct"]
    c = r["checks"]["max_rel_err"]
    assert c["value"] > c["limit"]      # the check itself catches it


def _zeros(*args, **kw):
    return (0.0,) * 4


def _stale():
    from igg.models import stokes3d

    real, last = stokes3d.residual, []

    def check(*args, **kw):
        last.append(real(*args, **kw))
        return last[-2] if len(last) > 1 else last[-1]
    return check


@pytest.mark.parametrize("check", ["zeros", "stale"])
def test_broken_check_is_not_correct(monkeypatch, check):
    # The check the window runs reads zeros, or the norms of the state
    # before (one dispatch behind).
    from igg.models import stokes3d

    monkeypatch.setattr(stokes3d, "residual",
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
    case, S, Rho, _ = harness.setup(cell, config, traffic, jax.devices(),
                                    11, interpret=True)
    geom = fam.Geometry.of(config)
    ref = fam.to_reference(geom, S, jax.devices()[0])
    got = case.frame(S, "residual")
    assert case.copy(S).norms == got
    rho = fam.make_rho(geom, config["params"], 11, blocked=False)
    want = [float(v) for v in fam.reference_residual(geom, config["params"])(
        ref, rho)]
    case.close()
    assert min(want) > 0
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_rho_has_no_net_force():
    cell, config, _ = cell_config(local_shape=[64, 64, 64])
    fam = cell.family
    geom = fam.Geometry.of(config)
    rho = np.asarray(fam.make_rho(geom, config["params"], 2**31 + 5,
                                  blocked=False))
    assert rho.shape == (61, 61, 61)
    assert abs(rho.mean()) < 1e-6 * np.abs(rho).max()


def test_compulsory_bytes_at_256():
    cell = harness.Cell(CELL)
    # P and Rho 256^3, each velocity 256^2 * 257, f32: five read, four
    # written.
    assert cell.family.compulsory_bytes(cell.config, cell.traffic) == \
        605_552_640


# --------------------------------------------------------------------------
# The residual reader
# --------------------------------------------------------------------------

F = "f32[256,256,256]{2,1,0:T(8,128)}"
KERNEL = f"%chunk_streaming.1 = ({F}, {F}) custom-call({F} %p.1, {F} %p.2)"
PER_IT = f"%stokes3d_iteration.2 = ({F}, {F}) custom-call({F} %p.3)"
LAYOUT = f"%slice.5 = {F} slice({F} %p.4)"
NORMS = "%fusion.7 = f32[4]{0} fusion(" + F + " %p.5)"
REDUCE = "%all-reduce.1 = f32[4]{0} all-reduce(f32[4]{0} %fusion.7)"
MAP = {KERNEL: ("igg.stencil", "chunk_streaming"),
       PER_IT: ("igg.halo", "stokes3d_iteration"),
       LAYOUT: ("igg.stencil", None),
       NORMS: ("igg.residual", None),
       REDUCE: ("igg.residual", None)}


def synthetic():
    ops = {0: [(KERNEL, 0, 500, "compute"), (PER_IT, 500, 600, "compute"),
               (LAYOUT, 600, 640, "compute"), (NORMS, 700, 720, "compute"),
               (REDUCE, 720, 725, "collective"),
               ("%copy.9 = " + F + " copy(" + F + " %x)", 730, 800,
                "compute")]}
    return tr.Trace(ops, [("window", 0, 1000)])


def ctx():
    return dict(trace=synthetic(), steps=10, dispatches=2, chips=1,
                compulsory_bytes=819, peak={"hbm_bytes_per_s": 819e9})


def read(name, c):
    return harness.load_module(os.path.join(harness.HERE, "metrics",
                                            name + ".py"),
                               "st3_" + name).read(c)


@pytest.fixture
def scope_map(monkeypatch):
    from igg import profiling

    monkeypatch.setattr(profiling, "op_scopes", lambda: dict(MAP))


def test_residual_reader(scope_map):
    # 25 ns of residual ops (the norms and their reduction) over 10 steps
    assert read("residual_ms_per_step", ctx()) == pytest.approx(25e-6 / 10)


def test_residual_reader_silent_without_its_scope(monkeypatch):
    from igg import profiling

    older = {k: v for k, v in MAP.items() if v[0] != "igg.residual"}
    monkeypatch.setattr(profiling, "op_scopes", lambda: older)
    assert read("residual_ms_per_step", ctx()) is None
