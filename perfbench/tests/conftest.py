"""The benchmark's own tests: on the CPU, four virtual devices (the mesh
cell's), float32 as the cells run.  Run them with

    python -m pytest perfbench/tests -q
"""

import os
import sys

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _no_grid():
    import igg

    yield
    if igg.grid_is_initialized():
        igg.finalize_global_grid()
