"""The open-x wavefront of the diffusion mega kernel on the CPU: the plan
that picks its depth D and slab height bx from the VMEM footprint model,
and the kernel itself under the TPU interpreter
(`pltpu.force_tpu_interpret_mode`, which runs its DMAs and semaphores),
bit for bit against the one-step-per-sweep kernel.  On the chip the same
equality is pinned by tests/test_mega_tpu.py."""

import numpy as np
import pytest

from igg.ops.diffusion_mega import (_MAX_DEPTH, _VMEM_BUDGET,
                                    _wavefront_vmem, wavefront_plan)

OPEN = ("frozen", "frozen", "frozen")


def test_plan_512_open_fits_budget():
    D, bx = wavefront_plan((512,) * 3, 100, OPEN, np.float32)
    assert D >= 4 and 100 % D == 0
    assert _wavefront_vmem((512,) * 3, D, bx, 4, False) <= _VMEM_BUDGET


@pytest.mark.parametrize("n_inner, D", [(100, 5), (13, 8), (3, 3), (2, 2),
                                        (12, 6)])
def test_plan_prefers_a_divisor_of_n_inner(n_inner, D):
    """The deepest divisor above half the deepest fit, else the deepest
    fit (the remainder runs one shorter sweep)."""
    assert wavefront_plan((64, 64, 128), n_inner, OPEN, np.float32) == (D, 4)


def test_plan_depth_follows_the_footprint():
    """A 1024x1024 f32 plane is 4 MiB: the model gives up slab height,
    then depth, to stay within the budget."""
    shape = (1024, 1024, 1024)
    D, bx = wavefront_plan(shape, 63, OPEN, np.float32)
    assert _wavefront_vmem(shape, D, bx, 4, False) <= _VMEM_BUDGET
    assert _wavefront_vmem(shape, D + 1, 2, 4, False) > _VMEM_BUDGET
    assert _wavefront_vmem(shape, D, 4, 4, False) > _VMEM_BUDGET
    assert bx == 2 and D < _MAX_DEPTH


@pytest.mark.parametrize("shape, n_inner, modes, kw", [
    ((512,) * 3, 100, ("wrap", "frozen", "frozen"), {}),
    ((512,) * 3, 100, ("wrap", "wrap", "wrap"), {}),
    ((512,) * 3, 1, OPEN, {}),
    ((512,) * 3, 0, OPEN, {}),
    ((512,) * 3, 100, OPEN, {"interpret": True}),
    ((512, 512, 500), 100, OPEN, {}),      # z not a lane multiple
    ((512, 500, 512), 100, OPEN, {}),      # y not a sublane multiple
    ((4, 64, 128), 100, OPEN, {}),         # under three slabs of 2
    ((13, 64, 128), 100, OPEN, {}),        # no slab height divides x
    ((64, 8192, 8192), 100, OPEN, {}),     # a 256 MiB plane: nothing fits
], ids=["wrap_x_open_yz", "wrap_x", "one_step", "no_steps", "interpret",
        "z_unaligned", "y_unaligned", "two_slabs", "x_odd", "plane_too_big"])
def test_plan_refuses(shape, n_inner, modes, kw):
    assert wavefront_plan(shape, n_inner, modes, np.float32, **kw) == 0


@pytest.mark.parametrize("modes, n_inner, streamed", [
    (OPEN, 5, False),
    (("frozen", "wrap", "wrap"), 11, True),
    (("frozen", "wrap", "frozen"), 10, False),
    (("frozen", "frozen", "wrap"), 3, True),
], ids=["open_one_sweep", "wrap_yz_remainder_streamed",
        "wrap_y_two_sweeps", "wrap_z_streamed"])
def test_wavefront_matches_per_step_sweep_interpreted(modes, n_inner,
                                                      streamed):
    """(12, 8, 128): three slabs of 4 rows.  n_inner 11 runs D=8 plus a
    3-step remainder sweep (two drain programs each: D > bx); 10 runs two
    sweeps of 5."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    from igg.ops.diffusion_mega import fused_diffusion_megasteps

    shape = (12, 8, 128)
    assert wavefront_plan(shape, n_inner, modes, np.float32)
    rng = np.random.default_rng(n_inner)
    T = rng.standard_normal(shape).astype(np.float32)
    A = (0.05 + 0.1 * rng.random(shape)).astype(np.float32)
    kw = dict(n_inner=n_inner, bx=4, rdx2=1.0, rdy2=1.1, rdz2=0.9,
              modes=modes, force_streamed=streamed)
    with jax.enable_x64(False), pltpu.force_tpu_interpret_mode():
        out = fused_diffusion_megasteps(jnp.array(T), jnp.array(A), **kw)
        ref = fused_diffusion_megasteps(jnp.array(T), jnp.array(A), **kw,
                                        _per_step_sweep=True)
        out, ref = np.asarray(out), np.asarray(ref)
    assert np.array_equal(out, ref), np.abs(out - ref).max()
    assert not np.array_equal(out, T)
    # the open x edge rows keep their frozen-dim cells
    for e in (0, -1):
        assert np.array_equal(out[e, 1:-1, 1:-1], T[e, 1:-1, 1:-1])
