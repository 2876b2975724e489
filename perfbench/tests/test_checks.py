"""`correct` comes out false when it should, at a size a test can hold:

- the control (the plain reference in bfloat16, in the program's place)
  fails the limit of each cell's configuration while the program passes;
- a whole run of the harness, with the look for a chip skipped and the
  timed path broken underneath, reports `correct` false for each fault the
  cell can have, and true with the path whole.

Interpret-mode kernels on CPU devices, the cells' own layouts and
traffic, the local block cut to (16, 16, 128) and 10 steps a dispatch."""

import time

import pytest

from perfbench import harness

TINY = (16, 16, 128)
CELLS = ["d3-512-open.steady", "d3-256pc-periodic.mesh221"]


def run(workload, **kw):
    return harness.run(workload, 2**31 + 101, 0.5, 0, t0=time.perf_counter(),
                       local_shape=TINY, n_inner=10, interpret=True,
                       need_tpu=False, **kw)[0]


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails_program_passes(workload):
    import jax

    cell = harness.Cell(workload)
    config = dict(cell.config, local_shape=list(TINY))
    traffic = dict(cell.traffic, n_inner=10)
    case, T, Cp, _ = harness.setup(cell, config, traffic, jax.devices(),
                                   7, interpret=True)
    before = case.copy(T)
    T = case.step(T, Cp)
    checker = cell.family.Checker(config, traffic, jax.devices()[0])
    limit = config["check"]["max_rel_err"]
    prog = checker.numbers(7, [(before, T)])[0]["max_rel_err"]
    ctl = checker.numbers(7, [(before, T)], control=True)[0]["max_rel_err"]
    case.close()
    assert prog < limit < ctl


@pytest.mark.parametrize("workload", CELLS)
def test_whole_run_is_correct(workload):
    r = run(workload)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 2
    assert set(r["metrics"]) == {"ms_per_step", "setup_s"}
    assert list(r)[-1] == "checks"


FAULTS = [(w, f) for w in CELLS for f in
          ("unchanged", "half", "altered", "no_exchange")
          if f != "no_exchange" or w.endswith("mesh221")]


@pytest.mark.parametrize("workload,fault", FAULTS)
def test_broken_path_is_not_correct(workload, fault):
    cell = harness.Cell(workload)
    config = dict(cell.config, local_shape=list(TINY))
    traffic = dict(cell.traffic, n_inner=10)
    wrap = cell.family.faults(config, traffic)[fault]
    r = run(workload, step_wrapper=wrap)
    assert not r["correct"]
    c = r["checks"]["max_rel_err"]
    assert c["value"] > c["limit"]      # the check itself catches it


def test_raising_dispatch_is_failed():
    def wrap(step):
        calls = []

        def broken(T, Cp):
            calls.append(1)
            if len(calls) > 4:       # past set-up's two warm dispatches
                raise RuntimeError("planted")
            return step(T, Cp)
        return broken

    r = run(CELLS[0], step_wrapper=wrap)
    assert not r["correct"] and r["failed"] == 1


@pytest.mark.parametrize("kind", ["gather", "slice"])
def test_frames_traffic_rehearses(kind):
    import json
    import os

    traffic = json.load(open(os.path.join(harness.HERE, "traffic",
                                          "frames.json")))
    traffic.update(n_inner=10, frame_every=20, frame_kind=kind)
    r = run(CELLS[0], traffic=traffic)
    assert r["correct"] and r["failed"] == 0


def test_float64_config_is_data_only():
    """A float64 configuration (ISSUE 22's open question 3) needs no code:
    the family makes f64 fields, runs the f64 reference and the f32
    control, and the config names the XLA rung as the tier that serves."""
    import jax

    cell = harness.Cell(CELLS[0])
    config = dict(cell.config, local_shape=list(TINY), dtype="float64",
                  periods=[1, 1, 1], tiers=["diffusion3d.xla"])
    config["check"] = {"max_rel_err": 1e-10}
    traffic = dict(cell.traffic, n_inner=10)
    was = jax.config.jax_enable_x64
    try:
        case, T, Cp, _ = harness.setup(cell, config, traffic,
                                       jax.devices(), 7, interpret=True)
        assert T.dtype == "float64" and case.served()[0] == "diffusion3d.xla"
        w = harness.window(case, T, Cp, 0.3, traffic, 7, config["tiers"])
        case.close()
        checker = cell.family.Checker(config, traffic, jax.devices()[0])
        prog = checker.numbers(7, w["samples"])
        ctl = checker.numbers(7, w["samples"], control=True)
        assert w["failed"] == 0
        assert max(n["max_rel_err"] for n in prog) < 1e-12
        assert min(n["max_rel_err"] for n in ctl) > 1e-8
    finally:
        jax.config.update("jax_enable_x64", was)


def test_calibrate_reads_program_control_and_faults(tmp_path, capsys):
    """`calibrate.py` (run on the chip at the cells' own sizes to set the
    limits) at a tiny size: the program reads under the limit, the control
    and every fault over it."""
    import json

    from perfbench import calibrate

    out = tmp_path / "cal.jsonl"
    calibrate.main(["--workload", CELLS[1], "--seeds", "2", "--cpu",
                    "--out", str(out)])
    rows = [json.loads(line) for line in out.read_text().splitlines()]
    limit = harness.Cell(CELLS[1]).config["check"]["max_rel_err"]
    for r in rows:
        assert r["program"]["max_rel_err"] < limit
        for key in ("control", "unchanged", "half", "altered",
                    "no_exchange"):
            assert r[key]["max_rel_err"] > limit, key
