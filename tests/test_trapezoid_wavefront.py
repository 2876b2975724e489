"""The trapezoid chunk as wavefront sweeps on the CPU: `_chunk_call` runs
the chunk's K steps as D-step sweeps of its extended window
(`diffusion_mega._wavefront_kernel`) where the plan admits it, under the
TPU interpreter (`pltpu.force_tpu_interpret_mode`, which runs its DMAs
and semaphores), bit for bit against the one-step-per-sweep chunk kernel
on the central window; and the admission that keeps the one-step kernel
for open mesh edges and windows the plan refuses.  On the chip the same
equality is pinned by tests/test_mega_tpu.py."""

import numpy as np
import pytest

from igg.ops.diffusion_trapezoid import (_wavefront_modes, _wavefront_plan,
                                         _window_shape)

K = 8


@pytest.mark.parametrize("modes, block", [
    (("ext", "ext", "wrap"), (8, 8, 128)),
    (("ext", "wrap", "wrap"), (8, 24, 128)),
    (("ext", "ext", "ext"), (8, 8, 40)),
    (("frozen", "ext", "wrap"), (16, 8, 128)),
], ids=["mesh_cell", "x_ring", "torus_lane_padded", "open_x"])
def test_wavefront_chunk_matches_per_step_sweep_interpreted(modes, block):
    """The window is the block plus K rows on each side of every extended
    dim ((24, 24, 128) for the mesh cell's modes); the z wrap halo is made
    fresh, as every chunk entry has it (a frozen row keeps its entry
    values in the one kernel and re-wraps them in the other)."""
    assert _wavefront_plan(_window_shape(block, K, modes), K, modes,
                           np.float32) == (8, 4)
    _check_chunk(modes, block)


def test_wavefront_chunk_remainder_sweep_interpreted(monkeypatch):
    """A depth that does not divide K (D 3: two sweeps, then one of 2)."""
    from igg.ops import diffusion_trapezoid

    monkeypatch.setattr(diffusion_trapezoid, "wavefront_plan",
                        lambda *a: (3, 4))
    _check_chunk(("ext", "ext", "wrap"), (8, 8, 128))


def _check_chunk(modes, block):
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    from igg.ops.diffusion_trapezoid import _chunk_call

    shape = tuple(s + (2 * K if m == "ext" else 0)
                  for s, m in zip(block, modes))
    rng = np.random.default_rng(sum(shape))
    Text = rng.standard_normal(shape).astype(np.float32)
    A_ext = (0.05 + 0.1 * rng.random(shape)).astype(np.float32)
    if modes[2] == "wrap":
        Text[:, :, 0], Text[:, :, -1] = Text[:, :, -2], Text[:, :, 1]
    kw = dict(K=K, bx=K, modes=modes, grid=None, rdx2=1.0, rdy2=1.1,
              rdz2=0.9)
    with jax.enable_x64(False), pltpu.force_tpu_interpret_mode():
        out = _chunk_call(jnp.array(Text), jnp.array(A_ext), block, **kw)
        ref = _chunk_call(jnp.array(Text), jnp.array(A_ext), block, **kw,
                          _per_step_sweep=True)
        out, ref = np.asarray(out), np.asarray(ref)
    assert out.shape == ref.shape == block
    assert np.array_equal(out, ref), np.abs(out - ref).max()
    centre = tuple(slice(K, K + b) if m == "ext" else slice(None)
                   for b, m in zip(block, modes))
    assert not np.array_equal(out, Text[centre])


@pytest.mark.parametrize("modes, block", [
    (("oext", "ext", "wrap"), (256, 256, 256)),
    (("ext", "oext", "wrap"), (256, 256, 256)),
    (("frozen", "frozen", "oext"), (256, 256, 256)),
    (("ext", "ext", "wrap"), (256, 4096, 4096)),   # a 64 MiB plane
    (("ext", "ext", "wrap"), (256, 260, 256)),     # y off the sublane tile
], ids=["oext_x", "oext_y", "oext_z", "plane_too_big", "y_unaligned"])
def test_wavefront_admission_keeps_the_per_step_sweep(modes, block):
    assert not _wavefront_plan(_window_shape(block, K, modes), K, modes,
                               np.float32)


def test_mesh_cell_window_plan():
    """The mesh cell's window: 272 x 272 x 256, extended x and y, wrap z,
    one sweep of D 8 advances the whole chunk."""
    modes = ("ext", "ext", "wrap")
    window = _window_shape((256,) * 3, K, modes)
    assert window == (272, 272, 256)
    assert _wavefront_modes(modes) == ("frozen", "frozen", "wrap")
    assert _wavefront_plan(window, K, modes, np.float32) == (8, 4)
