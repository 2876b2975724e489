"""The readers of the program's own names: `halo_pack_ms_per_step` and
`layout_ms_per_step` against a synthetic trace and scope map (the
benchmark's probe and snapshot-copy ops never attributed, a cut-short
event name still matched), `dispatch_host_ms` and `lower_s` against the
program's counters, and all four silent on a program without them."""

import os

import pytest

from perfbench import harness
from perfbench import scopes as sc
from perfbench import trace as tr

F = "f32[256,256,256]{2,1,0:T(8,128)}"
S = "f32[256,9,256]{2,1,0:T(8,128)}"
KERNEL = (f"%diffusion3d_trapezoid_chunk.1 = ({F}, {F}) custom-call({F} "
          f"%fusion.3, {F} %param.1)")
HALO_SLICE = f"%slice.811 = {S} slice({F} %get-tuple-element.5)"
CENTRAL = f"%slice.813 = {F} slice({F} %get-tuple-element.9)"
SEND = f"%collective-permute-start.1 = ({S}, {S}) collective-permute-start({S} %slice.811)"
MAP = {KERNEL: ("igg.stencil", "diffusion3d_trapezoid_chunk"),
       HALO_SLICE: ("igg.halo", None),
       CENTRAL: ("igg.stencil", None),
       SEND: ("igg.halo", None)}
# The benchmark's own programs: the same instruction names, other operands.
PROBE = "%slice.811 = f32[1,1,1]{2,1,0:T(1,128)} slice(" + F + " %x.1)"
SNAPSHOT = f"%copy.2 = {F} copy({F} %x.2)"


def event(key, rest=", slice={[0:256], [0:9], [0:256]}"):
    return key + rest


def synthetic():
    ops = {}
    for dev in (0, 1):
        ops[dev] = [
            (event(KERNEL, ', custom_call_target="tpu_custom_call"'),
             0, 600, "compute"),
            (event(HALO_SLICE), 600, 650, "compute"),
            (event(SEND, ""), 650, 660, "collective"),
            (event(CENTRAL)[:40], 660, 700, "compute"),   # name cut short
            (PROBE, 700, 710, "compute"),
            (SNAPSHOT, 710, 800, "compute")]
    return tr.Trace(ops, [("window", 0, 1000)])


def read(name, ctx):
    return harness.load_module(os.path.join(harness.HERE, "metrics",
                                            name + ".py"), "s_" + name).read(ctx)


@pytest.fixture
def scope_map(monkeypatch):
    from igg import profiling

    monkeypatch.setattr(profiling, "op_scopes", lambda: dict(MAP))


def ctx():
    return dict(trace=synthetic(), steps=100, dispatches=1, chips=2)


def test_lookup_matches_whole_and_cut_short_names(scope_map):
    got = sc.load()
    assert got.lookup(event(KERNEL)) == MAP[KERNEL]
    assert got.lookup(event(CENTRAL)[:40]) == MAP[CENTRAL]
    assert got.lookup(PROBE) is None and got.lookup(SNAPSHOT) is None
    assert got.lookup("%fusion.99 = f32[] fusion()") is None


def test_scope_readers(scope_map):
    c = ctx()
    # per chip: halo 50 ns of non-collective ops; layout 40 ns (the kernel,
    # the collective, the probe and the snapshot copy excluded)
    assert read("halo_pack_ms_per_step", c) == pytest.approx(50e-6 / 100)
    assert read("layout_ms_per_step", c) == pytest.approx(40e-6 / 100)
    kernel = read("kernel_ms_per_step", c)
    mosaic = sc.ms_per_step(c, lambda s, k, kind: k is not None)
    own = (mosaic + read("halo_pack_ms_per_step", c)
           + read("layout_ms_per_step", c))
    probe_and_copy = 100e-6 / 100
    assert kernel == pytest.approx(own + probe_and_copy)


def test_scope_readers_silent_without_a_map(monkeypatch):
    from igg import profiling

    monkeypatch.delattr(profiling, "op_scopes")
    assert read("halo_pack_ms_per_step", ctx()) is None
    assert read("layout_ms_per_step", ctx()) is None
    monkeypatch.setattr(profiling, "op_scopes", lambda: {}, raising=False)
    assert read("layout_ms_per_step", ctx()) is None


def test_counter_readers(monkeypatch):
    from igg import telemetry

    monkeypatch.setattr(telemetry, "snapshot", lambda: {})
    assert read("dispatch_host_ms", ctx()) is None
    assert read("lower_s", ctx()) is None

    def hist(n, total):
        return {"type": "histogram", "count": n, "sum": total}

    snap = {'igg_dispatch_host_seconds{family="diffusion3d"}': hist(4, 2e-3),
            'igg_compile_seconds{stage="trace"}': hist(1, 1.25),
            'igg_compile_seconds{stage="lower"}': hist(1, 0.5),
            'igg_compile_seconds{stage="compile"}': hist(1, 0.75)}
    monkeypatch.setattr(telemetry, "snapshot", lambda: snap)
    assert read("dispatch_host_ms", ctx()) == pytest.approx(0.5)
    assert read("lower_s", ctx()) == pytest.approx(1.75)
