"""Test configuration: run the whole suite on a virtual 8-device CPU mesh.

This is the faithful analog of the reference's "multi-node without a cluster"
test strategy (`/root/reference/test/test_update_halo.jl:1-3`): the reference
runs its halo tests on one MPI process with periodic dims (self-neighbor
path), and transparently with any number of processes.  Here, 8 virtual CPU
devices exercise the real shard_map/ppermute code path — the same program
that runs on a TPU slice — without TPU hardware.
"""

import os

# Must happen before any JAX backend initializes.  XLA_FLAGS is read lazily
# at CPU-client creation; jax_platforms keeps a TPU, where there is one, out
# of backend selection.
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

import jax  # noqa: E402

if os.environ.get("IGG_TPU_TESTS") == "1":
    # Escape hatch for the TPU-only tests (tests/test_mega_tpu.py): leave
    # the real backend in place.  Only run the TPU-marked files this way —
    # the rest of the suite expects the 8-device CPU mesh below.
    pass
else:
    jax.config.update("jax_platforms", "cpu")
    # The reference test suite works in Float64 (Julia default); enable x64
    # so the golden values transfer verbatim.  Library code itself is
    # dtype-agnostic.
    jax.config.update("jax_enable_x64", True)

import pytest  # noqa: E402

import igg  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_grid():
    """Each test starts and ends without an initialized grid (the reference
    re-runs each test file in a fresh process for the same reason,
    `/root/reference/test/runtests.jl:24`)."""
    if igg.grid_is_initialized():
        igg.finalize_global_grid()
    yield
    if igg.grid_is_initialized():
        igg.finalize_global_grid()


@pytest.fixture
def eight_devices():
    assert len(jax.devices()) == 8, (
        "test suite expects 8 virtual CPU devices; got "
        f"{len(jax.devices())} ({jax.devices()[0].platform})")
    return jax.devices()
