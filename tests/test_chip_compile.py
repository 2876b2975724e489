"""Chipless compiles of the main path's kernels for the TPU v5e.

The TPU compiler is installed next to the CPU backend, and it compiles for
a chip that is described (`topologies.get_topology_desc`) but not
attached.  Each case lowers one program that `chip_smoke.py` (or its
Stokes sibling) runs, at its real size, and asserts the Mosaic kernel
survived into the compiled program (`tpu_custom_call`): a refused slice,
a VMEM limit over the chip's or a kernel that cannot be partitioned fails
here, at no chip time.  Nothing runs, so nothing here is a timing.

The topology is described inside a module fixture, never at import or
collection: only one process may load libtpu at a time, and every xdist
worker imports this file.
"""

import os

import numpy as np
import pytest

import igg

N_INNER = 100    # chip_smoke.py's steps per dispatch


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these out of it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(local, shapes, out_specs):
    """The program `igg.sharded` builds for `local` on the live grid's
    mesh (`jit(shard_map(local))` under the `igg.stencil` scope), compiled
    from shapes alone; returns the compiled program."""
    import jax
    from jax.sharding import NamedSharding

    from igg import parallel

    grid = igg.get_global_grid()
    args = [jax.ShapeDtypeStruct(s, np.float32,
                                 sharding=NamedSharding(
                                     grid.mesh, igg.spec_for(len(s))))
            for s in shapes]
    jfn = parallel._build(local, args, grid, donate_argnums=(0,),
                          out_specs=out_specs, check_vma=True)
    return jfn.lower(*args).compile()


def _grid(topo, n, dims, periodic, **kw):
    p = int(periodic)
    ndev = dims[0] * dims[1] * dims[2]
    igg.init_global_grid(*n, dimx=dims[0], dimy=dims[1], dimz=dims[2],
                         periodx=p, periody=p, periodz=p,
                         devices=list(topo.devices[:ndev]), quiet=True,
                         **kw)
    grid = igg.get_global_grid()
    return tuple(d * s for d, s in zip(grid.dims, grid.nxyz))


def _diffusion_steps(n_inner):
    """The diffusion3d Mosaic tier's local program (`make_multi_step`'s
    `build_pallas_steps`), with the bx it picks."""
    from igg.models import diffusion3d as d3
    from igg.ops import fused_diffusion_steps

    p = d3.Params()
    dx, dy, dz = p.spacing()
    bx = d3._best_bx(igg.get_global_grid().nxyz[0])
    return lambda T, Cp: fused_diffusion_steps(
        T, Cp, n_inner=n_inner, dx=dx, dy=dy, dz=dz, dt=p.timestep(),
        lam=p.lam, bx=bx)


def _kernels(compiled):
    from igg import profiling

    return {i.kernel for i in profiling.instructions(compiled.as_text())
            if i.kernel}


def _loop_field_copies(compiled, shapes):
    """The whole-field copies (`copy`, `copy-start`) of an operand of one
    of `shapes` inside the bodies of the program's `while` loops: each runs
    once a trip, as when a loop result must be moved back into its
    parameter's buffer."""
    import re

    from igg import profiling

    comps, comp = {}, None
    for line in compiled.as_text().splitlines():
        m = profiling._INSTRUCTION.match(line)
        if m and comp is not None:
            comps[comp].append(profiling._split_instruction(m.group(2)))
            continue
        c = profiling._COMPUTATION.match(line)
        if c:
            comp = c.group(1)
            comps[comp] = []
    fields = {"f32[" + ",".join(map(str, s)) + "]" for s in shapes}
    bodies = {b for rows in comps.values() for _, op, _, attrs in rows
              if op == "while"
              for b in re.findall(r"\bbody=%([\w.\-]+)", attrs)}
    assert bodies, "the program has no while loop"
    return [(b, op, shape) for b in sorted(bodies)
            for shape, op, _, _ in comps[b]
            if op in ("copy", "copy-start")
            and any(f in shape for f in fields)]


def mega_512_open(topo):
    """Phase A: the streamed-coefficient frozen-edge mega kernel, as the
    open-x wavefront (D steps per HBM sweep)."""
    from igg.ops.diffusion_mega import mega_supported, wavefront_plan

    g = _grid(topo, (512,) * 3, (1, 1, 1), periodic=False)
    assert mega_supported(g, 8, N_INNER, False, dtype=np.float32)
    assert wavefront_plan(g, N_INNER, ("frozen",) * 3, np.float32)
    compiled = _compile(_diffusion_steps(N_INNER), [g, g], igg.spec_for(3))
    assert _kernels(compiled) == {"diffusion3d_mega_wavefront"}
    return compiled


def mega_512_periodic(topo):
    """The streamed-coefficient mega kernel with wrap x, one step per
    sweep (the wavefront serves open x only)."""
    from igg.ops.diffusion_mega import (mega_supported, resident_a_fits,
                                        wavefront_plan)

    g = _grid(topo, (512,) * 3, (1, 1, 1), periodic=True)
    assert mega_supported(g, 8, N_INNER, False, dtype=np.float32)
    assert not resident_a_fits(g, 8, np.float32)
    assert not wavefront_plan(g, N_INNER, ("wrap",) * 3, np.float32)
    compiled = _compile(_diffusion_steps(N_INNER), [g, g], igg.spec_for(3))
    assert _kernels(compiled) == {"diffusion3d_mega"}
    return compiled


def mega_256_periodic(topo):
    """Phase B: the resident self-wrapping mega kernel."""
    from igg.ops.diffusion_mega import mega_supported

    g = _grid(topo, (256,) * 3, (1, 1, 1), periodic=True)
    assert mega_supported(g, 8, N_INNER, False, dtype=np.float32)
    return _compile(_diffusion_steps(N_INNER), [g, g], igg.spec_for(3))


def perstep_with_halo_writer(topo):
    """The per-step fused kernel, then a standalone halo update through
    the in-place Pallas writers (phase B's `igg.update_halo`)."""
    from igg.models import diffusion3d as d3
    from igg.ops import fused_diffusion_step

    g = _grid(topo, (256,) * 3, (1, 1, 1), periodic=True)
    p = d3.Params()
    dx, dy, dz = p.spacing()

    def local(T, Cp):
        T = fused_diffusion_step(T, Cp, dx=dx, dy=dy, dz=dz,
                                 dt=p.timestep(), lam=p.lam, bx=8)
        return igg.update_halo_local(T, assembly="pallas")

    return _compile(local, [g, g], igg.spec_for(3))


def diffusion_trapezoid_2x2(topo):
    """`--chips 4`: the K-step trapezoid chunks with slab ppermutes, each
    chunk one wavefront sweep of its extended window, beside the warm-up
    and remainder steps' per-step kernel.  The trace counts the steps the
    chunks ran (`count_per_dispatch`): 12 chunks of 8 of the 99 after the
    warm-up step."""
    from igg import parallel
    from igg.ops.diffusion_trapezoid import trapezoid_supported

    g = _grid(topo, (256,) * 3, (2, 2, 1), periodic=True)
    assert trapezoid_supported(igg.get_global_grid(), (256,) * 3, 8,
                               N_INNER - 1, np.float32, allow_open=True)
    counts = {}
    parallel._tracing.append(counts)
    try:
        compiled = _compile(_diffusion_steps(N_INNER), [g, g],
                            igg.spec_for(3))
    finally:
        parallel._tracing.pop()
    assert counts == {"igg_chunk_wavefront_steps_total": 96}
    assert _kernels(compiled) == {"diffusion3d_trapezoid_wavefront",
                                  "diffusion3d_step"}
    return compiled


def stokes_trapezoid_4x1(topo):
    """The Stokes K-iteration chunk tier (warm-up iteration, chunks,
    per-iteration remainder) over the same four chips at 128^3: its
    VMEM budget admits the chunk only there and only on an (N,1,1)
    ring (`fit_stokes_K` is 0 on (2,2,1) at every size).  The local
    program is the ladder's own; at 16 iterations K 8 leaves a remainder
    of 7, whose loop must move no field (the HBM ping-pong)."""
    from igg.models import stokes3d
    from igg.ops.stokes_trapezoid import fit_stokes_K

    n, n_inner = 128, 16
    g = _grid(topo, (n,) * 3, (4, 1, 1), periodic=True, overlapx=3,
              overlapy=3, overlapz=3)
    assert fit_stokes_K(igg.get_global_grid(), (n,) * 3, n_inner - 1,
                        np.float32) == 8, "the Stokes chunk tier's K moved"
    tiers = {t.name: t for t in
             stokes3d.make_iteration(n_inner=n_inner).ladder.tiers}
    local = tiers["stokes3d.trapezoid"].build().__wrapped__
    dims = igg.get_global_grid().dims
    stag = [tuple(dims[d] * (n + (d == i)) for d in range(3))
            for i in range(3)]
    compiled = _compile(local, [g, *stag, g], (igg.spec_for(3),) * 4)
    assert _kernels(compiled) == {"stokes3d_iteration",
                                  "chunk_resident"}
    local_shapes = [(n,) * 3] + [tuple(n + (d == i) for d in range(3))
                                 for i in range(3)]
    assert _loop_field_copies(compiled, local_shapes) == []
    return compiled


def stokes_mosaic_256_periodic(topo):
    """The rung `stokes3d.make_iteration`'s ladder serves for the Stokes
    cell's 256^3 periodic one-chip block: the per-iteration fused kernel
    (the resident chunk tier's K-bound refuses the shape).  The local
    program is the ladder's own."""
    import jax

    from igg.models import stokes3d

    n = 256
    g = _grid(topo, (n,) * 3, (1, 1, 1), periodic=True, overlapx=3,
              overlapy=3, overlapz=3)
    ladder = stokes3d.make_iteration(n_inner=N_INNER).ladder
    P = (jax.ShapeDtypeStruct(g, np.float32),)
    tiers = {t.name: t for t in ladder.tiers}
    assert not tiers["stokes3d.trapezoid"].admit(P)
    assert tiers["stokes3d.mosaic"].admit(P)
    local = tiers["stokes3d.mosaic"].build().__wrapped__
    stag = [tuple(n + (d == i) for d in range(3)) for i in range(3)]
    compiled = _compile(local, [g, *stag, g], (igg.spec_for(3),) * 4)
    assert _kernels(compiled) == {"stokes3d_iteration"}
    assert _loop_field_copies(compiled, [g, *stag]) == []
    return compiled


@pytest.mark.parametrize("case", [
    mega_512_open, mega_512_periodic, mega_256_periodic,
    perstep_with_halo_writer,
    diffusion_trapezoid_2x2, stokes_trapezoid_4x1,
    stokes_mosaic_256_periodic],
    ids=lambda f: f.__name__)
def test_compiles_for_v5e(topo, case):
    import jax

    with jax.enable_x64(False):
        try:
            compiled = case(topo)
        finally:
            if igg.grid_is_initialized():
                igg.finalize_global_grid()
    assert "tpu_custom_call" in compiled.as_text()
