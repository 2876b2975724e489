"""Both cells' step programs compile for the TPU v5e at their real size,
against the described `v5e:2x2` topology: the local program the
`diffusion3d.mosaic` tier builds for the cell's grid (`make_multi_step`'s
fused steps), wrapped in `shard_map` over the cell's mesh.  Nothing runs,
so nothing here is a timing."""

import os

import numpy as np
import pytest

from perfbench import harness

CELLS = ["d3-512-open.steady", "d3-256pc-periodic.mesh221"]


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
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.mark.parametrize("workload", CELLS)
def test_cell_compiles_for_v5e(topo, workload):
    import jax
    from jax.sharding import NamedSharding

    import igg
    from igg.models import diffusion3d as d3
    from igg.ops import fused_diffusion_steps

    cell = harness.Cell(workload)
    c = cell.config
    n_inner = int(cell.traffic["n_inner"])
    igg.init_global_grid(
        *c["local_shape"], dimx=c["dims"][0], dimy=c["dims"][1],
        dimz=c["dims"][2], periodx=c["periods"][0],
        periody=c["periods"][1], periodz=c["periods"][2],
        devices=list(topo.devices[:cell.chips]), quiet=True)
    grid = igg.get_global_grid()
    p = d3.Params(**c["params"])
    dx, dy, dz = p.spacing()
    bx = d3._best_bx(grid.nxyz[0])

    def local(T, Cp):
        return fused_diffusion_steps(T, Cp, n_inner=n_inner, dx=dx, dy=dy,
                                     dz=dz, dt=p.timestep(), lam=p.lam,
                                     bx=bx)

    spec = igg.spec_for(3)
    shape = tuple(d * n for d, n in zip(c["dims"], c["local_shape"]))
    arg = jax.ShapeDtypeStruct(shape, np.float32,
                               sharding=NamedSharding(grid.mesh, spec))
    sm = jax.shard_map(local, mesh=grid.mesh, in_specs=(spec, spec),
                       out_specs=spec)
    with jax.enable_x64(False):
        compiled = jax.jit(sm, donate_argnums=(0,)).lower(arg, arg).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    if cell.chips > 1:
        assert "collective-permute" in text
