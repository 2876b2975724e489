"""The program's own names for its work: the `igg.stencil`/`igg.halo`
scopes and kernel names in the compiled programs of both benchmark cells
(chipless v5e compiles, `igg.profiling.instructions`/`op_scopes`), the
per-dispatch profiler spans, and the compile and dispatch counters."""

import glob

import numpy as np
import pytest

import igg
from igg import profiling
from igg import telemetry as tel

from test_chip_compile import (_no_persistent_cache,  # noqa: F401
                               diffusion_trapezoid_2x2, mega_512_open, topo)

# Instructions that do no device work of their own.
TRIVIAL = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast",
           "while", "conditional", "call", "after-all", "opt-barrier"}
# What the compiler inserts with no op_name: loop-carried, donated and
# prefetched buffer copies.
INSERTED = {"copy", "copy-start", "copy-done", "fusion"}


@pytest.fixture(autouse=True)
def _clean_telemetry():
    tel.reset_metrics()
    tel._ring().clear()
    yield
    tel.reset_metrics()


def _compiled(topo, case):
    import jax

    with jax.enable_x64(False):
        try:
            return case(topo)
        finally:
            if igg.grid_is_initialized():
                igg.finalize_global_grid()


@pytest.mark.parametrize("case, kernels", [
    (mega_512_open, {"diffusion3d_mega_wavefront"}),
    (diffusion_trapezoid_2x2, {"diffusion3d_trapezoid_wavefront",
                               "diffusion3d_step"})],
    ids=["mega_512_open", "diffusion_trapezoid_2x2"])
def test_cell_programs_name_every_op(topo, case, kernels):
    compiled = _compiled(topo, case)
    ins = profiling.instructions(compiled.as_text())
    by_name = {i.name: i for i in ins}
    scopes = profiling.op_scopes(compiled)
    assert len(scopes) == len(ins)

    mosaic = [i for i in ins if i.kernel is not None]
    assert {i.kernel for i in mosaic} == kernels
    for i in mosaic:
        assert i.name.startswith(i.kernel) and i.scope == profiling.STENCIL
        assert scopes[i.key] == (profiling.STENCIL, i.kernel)

    def last(i):
        return (i.op_name or "").rsplit("/", 1)[-1]

    permutes = [i for i in ins if i.opcode.startswith("collective-permute")]
    assert bool(permutes) == ("trapezoid" in case.__name__)
    for i in permutes:
        assert i.scope == profiling.HALO, i
        if i.opcode == "collective-permute-start":
            # the slab it sends is cut by the halo engine
            src = by_name[profiling._OPERAND.findall(i.key)[0]]
            assert src.scope == profiling.HALO, src
    for i in ins:
        if last(i) in ("concatenate", "ppermute"):
            assert i.scope == profiling.HALO, i
        if last(i) == "pad":
            assert i.scope == profiling.STENCIL, i

    # the central-window slice reads the chunk kernel's output: one slice
    # of x and y together
    outs = {i.name for i in ins
            if i.kernel == "diffusion3d_trapezoid_wavefront"}
    central = [i for i in ins if i.opcode == "slice"
               and set(profiling._OPERAND.findall(i.key)) & outs]
    assert len(central) == ("trapezoid" in case.__name__)
    assert all(i.scope == profiling.STENCIL for i in central)

    for i in ins:
        if i.opcode in TRIVIAL:
            continue
        assert i.scope in profiling.SCOPES, i
        if i.op_name is None:
            assert i.opcode in INSERTED, i
        else:
            assert profiling.STENCIL in i.op_name, i


def _grid_and_step():
    import jax

    from igg.models import diffusion3d as d3

    igg.init_global_grid(16, 16, 16, dimx=2, dimy=2, dimz=1, periodx=1,
                         periody=1, periodz=1, devices=jax.devices()[:4],
                         quiet=True)
    T, Cp = d3.init_fields(d3.Params(), dtype=np.float32)
    return T, Cp, d3.make_multi_step(2, d3.Params())


def _host_events(logdir):
    from jax.profiler import ProfileData

    path = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    pd = ProfileData.from_file(path[0])
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats)) for e in line.events
                    if e.name.startswith("igg.")]
    return sorted(out, key=lambda e: e[1])


def test_dispatch_spans_nest_and_leave_the_bus_alone(tmp_path):
    import jax

    T, Cp, step = _grid_and_step()
    T = step(T, Cp)
    T.block_until_ready()
    tel._ring().clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            T = step(T, Cp)
        T.block_until_ready()
    finally:
        jax.profiler.stop_trace()
    assert tel.flight_recorder() == []

    ev = _host_events(tmp_path)
    dispatches = [e for e in ev if e[0] == "igg.dispatch"]
    assert len(dispatches) == 3
    stamps = [d[3]["stamp"] for d in dispatches]
    assert stamps == sorted(stamps) and len(set(stamps)) == 3
    for name, a, b, meta in dispatches:
        assert meta["family"] == "diffusion3d"
        assert meta["tier"] == igg.degrade.active()["diffusion3d"]
        inner = {e[0] for e in ev if a <= e[1] and e[2] <= b and e[0] != name}
        assert {"igg.admit", "igg.sharded"} <= inner
        assert "igg.compile" not in inner


def test_counters_book_compiles_then_dispatches():
    import jax

    T, Cp, step = _grid_and_step()

    def snap():   # the served-tier counter moves with every dispatch
        return {k: v for k, v in tel.snapshot().items()
                if not k.startswith("igg_tier_dispatch_total")}

    T = step(T, Cp)
    first = snap()
    stages = {k for k in first if k.startswith("igg_compile_seconds")}
    assert 'igg_compile_seconds{stage="trace"}' in stages
    assert all(first[k]["count"] == 1 and first[k]["sum"] > 0
               for k in stages)
    misses = [k for k in first if k.startswith("igg_sharded_compiles_total")]
    assert misses and "xla_steps" in misses[0]
    assert not any(k.startswith("igg_dispatch_host_seconds") for k in first)

    for _ in range(2):
        T = step(T, Cp)
    T.block_until_ready()
    later = snap()
    host = later['igg_dispatch_host_seconds{family="diffusion3d"}']
    assert host["count"] == 2 and host["sum"] > 0
    assert {k: v for k, v in later.items()
            if not k.startswith("igg_dispatch_host_seconds")} == first

    # The scope map's re-lowering and a program igg did not compile book
    # nothing.
    scopes = profiling.op_scopes()
    assert {s.scope for s in scopes.values()} >= {profiling.STENCIL,
                                                   profiling.HALO}
    jax.jit(lambda x: x * 3 + 1)(np.arange(5.0)).block_until_ready()
    assert snap() == later
