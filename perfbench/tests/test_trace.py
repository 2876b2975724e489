"""The reduction from a profiler trace to the per-layer metrics, on two
small traces recorded on the chip (PR 22): three-quarters of a second of
d3-512-open.steady (the xplane as the profiler wrote it) and four
dispatches of d3-256pc-periodic.mesh221 (the reduced events, gzipped)."""

import gzip
import json
import os

import pytest

from perfbench import harness
from perfbench import trace as tr

DATA = os.path.join(harness.HERE, "testdata")
PEAK = {"hbm_bytes_per_s": 819e9}


def read(name, ctx):
    return harness.load_module(os.path.join(harness.HERE, "metrics",
                                            name + ".py"), "t_" + name).read(ctx)


def test_split_and_kind():
    cp = ("%collective-permute-start.3 = (f32[272,9,256]{2,0,1}, "
          "f32[272,9,256]{2,0,1}) collective-permute-start(f32[272,9,256] "
          "%copy.263), source_target_pairs={{0,2},{1,3}}")
    assert tr.split(cp) == ("collective-permute-start.3",
                            "collective-permute-start")
    assert tr.kind(cp) == "collective"
    k = ("%pallas_steps.1 = (f32[8,128]{1,0}, f32[8,128]{1,0}) "
         "custom-call(f32[8,128]{1,0} %T.1), custom_call_target=\"x\"")
    assert tr.split(k) == ("pallas_steps.1", "custom-call")
    assert tr.kind(k) == "compute"
    assert tr.kind("%ar = f32[] all-reduce(f32[] %x), to_apply=%add") \
        == "collective"
    assert tr.kind("%s = (f32[4], u32[], token[]) send(f32[4] %x, "
                   "token[] %t), is_host_transfer=true") == "host"
    assert tr.kind("%i = ((f32[4]), token[]) infeed(token[] %t)") == "host"
    assert tr.kind("%slice.1 = f32[1,1,1]{2,1,0} slice(f32[8,8,8] %x)") \
        == "compute"


def test_intervals():
    assert tr.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert tr.length([(0, 2), (1, 3), (5, 7)]) == 5
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
    assert tr.clip([(0, 4), (5, 9)], 2, 6) == [(2, 4), (5, 6)]


def test_leaves_drop_containers():
    ev = [("while", 0, 10, "compute"), ("a", 1, 3, "compute"),
          ("b", 4, 9, "compute"), ("c", 11, 12, "compute")]
    assert [e[0] for e in tr.leaves(ev)] == ["a", "b", "c"]


@pytest.fixture(scope="module")
def open512():
    return tr.load(os.path.join(DATA, "d3-512-open.steady.xplane.pb"))


@pytest.fixture(scope="module")
def mesh221():
    with gzip.open(os.path.join(DATA, "d3-256pc-periodic.mesh221."
                                      "trace.json.gz"), "rt") as f:
        return tr.Trace.from_json(json.load(f))


def ctx(trace, steps, dispatches, cbytes):
    return dict(trace=trace, steps=steps, dispatches=dispatches, chips=4,
                compulsory_bytes=cbytes, peak=PEAK, first_dispatch_s=2.5)


def test_open512_reduction(open512):
    assert list(open512.ops) == [0] and not open512.inflight
    kinds = {k for _, _, _, k in open512.ops[0]}
    assert kinds == {"compute"}
    c = ctx(open512, 900, 9, 3 * 512 ** 3 * 4)
    assert read("kernel_ms_per_step", c) == pytest.approx(2.5708270, 1e-6)
    assert read("device_idle_pct", c) == pytest.approx(0.0991885, 1e-5)
    assert read("stencil_roofline", c) == pytest.approx(0.7649523, 1e-6)
    assert read("step_mfu", c) == pytest.approx(0.7641936, 1e-6)
    assert read("halo_ms_per_step", c) is None
    assert read("exposed_comm_pct", c) is None
    assert read("first_dispatch_s", c) == 2.5
    b = tr.breakdown(open512)
    assert b["device_ops"][0][0] == "pallas_steps.1 (custom-call)"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_mesh221_reduction(mesh221):
    assert sorted(mesh221.ops) == [0, 1, 2, 3]
    assert list(mesh221.inflight) == [0]
    lo, hi = mesh221.window()
    c = ctx(mesh221, 400, 4, 3 * 256 ** 3 * 4)
    kernel = read("kernel_ms_per_step", c)
    halo = read("halo_ms_per_step", c)
    idle = read("device_idle_pct", c)
    window_ms = (hi - lo) * 1e-6 / 400
    assert 0 < halo < kernel < window_ms
    assert kernel + halo <= window_ms * (1 - idle / 100) * 1.0001
    exposed = read("exposed_comm_pct", c)
    assert 0 < exposed < 100
    roof, mfu = read("stencil_roofline", c), read("step_mfu", c)
    assert 0 < mfu <= roof < 100
    assert (kernel, halo, exposed) == pytest.approx(
        (0.3698939, 0.00329490, 6.8376051), rel=1e-5)
