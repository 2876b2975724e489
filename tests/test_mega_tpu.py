"""TPU-only: the K-step mega-kernel must match the per-step fused kernel.

The mega-kernel uses manual TPU DMA/semaphores, which have no interpret
mode, so this test can only run against real TPU hardware.  The suite's
conftest pins the CPU backend; run this file with the escape hatch:

    IGG_TPU_TESTS=1 python -m pytest tests/test_mega_tpu.py -q

Run it in one process (no xdist): the chip belongs to the process that
holds it.  `chip_smoke.py` also runs the mega path on every driver check.
"""

import numpy as np
import pytest

import igg


@pytest.fixture(autouse=True)
def _needs_tpu():
    """Skip off the chip.  Asked inside a test, not at collection, so the
    backend is first touched by the process that runs the tests."""
    import jax

    if jax.devices()[0].platform != "tpu":
        pytest.skip("needs a real TPU chip")


def test_mega_matches_per_step_kernel():
    import jax.numpy as jnp

    from igg.models import diffusion3d as d3
    from igg.ops.diffusion_mega import fused_diffusion_megasteps, \
        mega_supported

    igg.init_global_grid(64, 64, 128, dimx=1, dimy=1, dimz=1,
                         periodx=1, periody=1, periodz=1, quiet=True)
    params = d3.Params()
    T, Cp = d3.init_fields(params, dtype=np.float32)
    dx, dy, dz = params.spacing()
    scal = dict(rdx2=1.0 / (dx * dx), rdy2=1.0 / (dy * dy),
                rdz2=1.0 / (dz * dz))
    A = float(params.timestep() * params.lam) / Cp
    assert mega_supported(T.shape, 8, 6, interpret=False, dtype=T.dtype)

    out = fused_diffusion_megasteps(T, A, n_inner=6, bx=8, **scal)

    from igg.ops import fused_diffusion_step
    import jax
    dt = params.timestep()
    ref = T
    step = jax.jit(lambda T: fused_diffusion_step(
        T, Cp, dx=dx, dy=dy, dz=dz, dt=dt, lam=params.lam, bx=8))
    for _ in range(6):
        ref = step(ref)
    scale = float(jnp.max(jnp.abs(ref)))
    assert float(jnp.max(jnp.abs(out - ref))) <= 4e-7 * scale


@pytest.mark.parametrize("per_step_sweep", [False, True],
                         ids=["wavefront", "one_step_sweep"])
def test_trapezoid_matches_per_step_kernel(per_step_sweep):
    """The K-step trapezoidal chunk kernel (x-exchanged ring; here the
    1-device self-ring) must match K applications of the per-step fused
    kernel on the same block, as wavefront sweeps of the extended window
    and as one HBM sweep a step; the two agree bit for bit (identical
    arithmetic per cell, only the order of the rows changes)."""
    import jax
    import jax.numpy as jnp

    from igg.models import diffusion3d as d3
    from igg.ops.diffusion_trapezoid import (
        fused_diffusion_trapezoid_steps, trapezoid_supported)

    igg.init_global_grid(64, 64, 128, dimx=1, dimy=1, dimz=1,
                         periodx=1, periody=1, periodz=1, quiet=True)
    grid = igg.get_global_grid()
    params = d3.Params()
    T, Cp = d3.init_fields(params, dtype=np.float32)
    # The trapezoid's validity argument requires exchange-fresh halos at
    # chunk entry (any state after update_halo or a model step qualifies;
    # raw init_fields coordinates do not).
    T = igg.update_halo(T)
    dx, dy, dz = params.spacing()
    scal = dict(rdx2=1.0 / (dx * dx), rdy2=1.0 / (dy * dy),
                rdz2=1.0 / (dz * dz))
    A = float(params.timestep() * params.lam) / Cp
    bx = 8
    assert trapezoid_supported(grid, T.shape, bx, 2 * bx, T.dtype)

    def chunks(pin):
        return jax.jit(
            lambda T, A: fused_diffusion_trapezoid_steps(
                T, A, n_inner=2 * bx, bx=bx, grid=grid, **scal,
                _per_step_sweep=pin))(T, A)

    out, done = chunks(per_step_sweep)
    assert done == 2 * bx
    twin, _ = chunks(not per_step_sweep)
    assert np.array_equal(np.asarray(out), np.asarray(twin))

    from igg.ops import fused_diffusion_step
    dt = params.timestep()
    ref = T
    step = jax.jit(lambda T: fused_diffusion_step(
        T, Cp, dx=dx, dy=dy, dz=dz, dt=dt, lam=params.lam, bx=bx))
    for _ in range(2 * bx):
        ref = step(ref)
    scale = float(jnp.max(jnp.abs(ref)))
    assert float(jnp.max(jnp.abs(out - ref))) <= 4e-7 * scale


def test_trapezoid_wavefront_counter_per_dispatch(monkeypatch):
    """One dispatch of `make_multi_step(100)` through the exchanged-mesh
    path (the single-device mega kernel turned off, so the one chip takes
    the mesh cell's route: a warm-up step, 12 chunks of K 8, 3 per-step
    remainder steps) adds 96 to `igg_chunk_wavefront_steps_total`, beside
    `igg_tier_dispatch_total`'s one."""
    from igg import telemetry
    from igg.models import diffusion3d as d3
    from igg.ops import diffusion_pallas

    monkeypatch.setattr(diffusion_pallas, "_single_device_modes",
                        lambda grid: None)
    igg.init_global_grid(64, 64, 128, dimx=1, dimy=1, dimz=1,
                         periodx=1, periody=1, periodz=1, quiet=True)
    try:
        params = d3.Params()
        T, Cp = d3.init_fields(params, dtype=np.float32)
        step = d3.make_multi_step(100, params, donate=False,
                                  use_pallas=True, bx=8)
        labels = dict(family="diffusion3d", tier="diffusion3d.mosaic")
        chunked = telemetry.counter("igg_chunk_wavefront_steps_total",
                                    **labels)
        dispatches = telemetry.counter("igg_tier_dispatch_total", **labels)
        c0, d0 = chunked.value, dispatches.value
        np.asarray(step(T, Cp))
        assert dispatches.value - d0 == 1
        assert chunked.value - c0 == 96
    finally:
        igg.finalize_global_grid()


def test_trapezoid_2d_kernel_matches_window():
    """The y-extended (N,M,1) chunk kernel against the pure-XLA window
    dynamics on the same doubly-extended buffer (the window-vs-per-step
    equivalence is pinned on the CPU torus by tests/test_trapezoid.py)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from igg.models import diffusion3d as d3
    from igg.ops.diffusion_pallas import _u_rows
    from igg.ops.diffusion_trapezoid import _chunk_call, _extend_dim

    igg.init_global_grid(64, 64, 128, dimx=1, dimy=1, dimz=1,
                         periodx=1, periody=1, periodz=1, quiet=True)
    grid = igg.get_global_grid()
    params = d3.Params()
    T, Cp = d3.init_fields(params, dtype=np.float32)
    T = igg.update_halo(T)
    dx, dy, dz = params.spacing()
    scal = dict(rdx2=1.0 / (dx * dx), rdy2=1.0 / (dy * dy),
                rdz2=1.0 / (dz * dz))
    A = float(params.timestep() * params.lam) / Cp
    K = bx = 8

    def extend2(F):
        F = _extend_dim(F, K, 2, grid, 0)
        return _extend_dim(F, K, 2, grid, 1)

    Text = jax.jit(extend2)(T)
    A_ext = jax.jit(extend2)(A)

    out = jax.jit(lambda Text, A_ext: _chunk_call(
        Text, A_ext, T.shape, K=K, bx=bx,
        modes=("ext", "ext", "wrap"), grid=grid,
        **scal))(Text, A_ext)

    def window(Text, A_ext):
        def step(_, U):
            S2 = U.shape[2]
            U = U.at[1:-1, 1:-1, 1:-1].set(
                _u_rows(U[:-2], U[1:-1], U[2:], A_ext[1:-1], **scal))
            U = U.at[:, :, 0].set(U[:, :, S2 - 2])
            U = U.at[:, :, S2 - 1].set(U[:, :, 1])
            return U
        U = lax.fori_loop(0, K, step, Text)
        return U[K:K + T.shape[0], K:K + T.shape[1]]

    ref = jax.jit(window)(Text, A_ext)
    scale = float(jnp.max(jnp.abs(ref)))
    assert float(jnp.max(jnp.abs(out - ref))) <= 4e-7 * scale


def test_trapezoid_3d_kernel_matches_window():
    """The triply-extended (N,M,K) 3-D-torus chunk kernel against the
    pure-XLA window dynamics on the same extended buffer (VERDICT round-3
    item 2; the window-vs-per-step equivalence is pinned on the CPU (2,2,2)
    torus by tests/test_trapezoid.py)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from igg.models import diffusion3d as d3
    from igg.ops.diffusion_pallas import _u_rows
    from igg.ops.diffusion_trapezoid import _chunk_call, _extend_dim

    igg.init_global_grid(64, 64, 128, dimx=1, dimy=1, dimz=1,
                         periodx=1, periody=1, periodz=1, quiet=True)
    grid = igg.get_global_grid()
    params = d3.Params()
    T, Cp = d3.init_fields(params, dtype=np.float32)
    T = igg.update_halo(T)
    dx, dy, dz = params.spacing()
    scal = dict(rdx2=1.0 / (dx * dx), rdy2=1.0 / (dy * dy),
                rdz2=1.0 / (dz * dz))
    A = float(params.timestep() * params.lam) / Cp
    K = bx = 8

    def extend3(F):
        F = _extend_dim(F, K, 2, grid, 0)
        F = _extend_dim(F, K, 2, grid, 1)
        return _extend_dim(F, K, 2, grid, 2)

    Text = jax.jit(extend3)(T)
    A_ext = jax.jit(extend3)(A)

    out = jax.jit(lambda Text, A_ext: _chunk_call(
        Text, A_ext, T.shape, K=K, bx=bx,
        modes=("ext", "ext", "ext"), grid=grid,
        **scal))(Text, A_ext)

    def window(Text, A_ext):
        def step(_, U):
            return U.at[1:-1, 1:-1, 1:-1].set(
                _u_rows(U[:-2], U[1:-1], U[2:], A_ext[1:-1], **scal))
        U = lax.fori_loop(0, K, step, Text)
        return U[K:K + T.shape[0], K:K + T.shape[1], K:K + T.shape[2]]

    ref = jax.jit(window)(Text, A_ext)
    scale = float(jnp.max(jnp.abs(ref)))
    assert float(jnp.max(jnp.abs(out - ref))) <= 4e-7 * scale


@pytest.mark.parametrize("periods", [(0, 0, 0), (0, 1, 1), (1, 0, 1),
                                     (1, 1, 0)])
def test_trapezoid_open_modes_match_per_step_kernel(periods):
    """Round 6: the open-boundary (frozen-edge) chunk kernel modes vs 2K
    applications of the per-step fused kernel — the reference-default
    boundary condition on the compiled K-step tier.  On one chip the open
    dims run "frozen" (periodic dims "ext"/"wrap"), exercising the
    edge-freeze planes, the SMEM flags, and the off=0 frozen-x program
    layout; the multi-device "oext" flag gating is pinned on the 8-device
    interpret meshes (tests/test_trapezoid.py::test_open_*) and by
    test_trapezoid_oext_kernel_matches_window below."""
    import jax
    import jax.numpy as jnp

    from igg.models import diffusion3d as d3
    from igg.ops import fused_diffusion_step
    from igg.ops.diffusion_trapezoid import (
        fused_diffusion_trapezoid_steps, trapezoid_supported)

    igg.init_global_grid(64, 64, 128, dimx=1, dimy=1, dimz=1,
                         periodx=periods[0], periody=periods[1],
                         periodz=periods[2], quiet=True)
    grid = igg.get_global_grid()
    params = d3.Params()
    T, Cp = d3.init_fields(params, dtype=np.float32)
    # Exchange-fresh entry state (frozen dims need nothing; wrap/ext dims
    # need their self-wrap halos fresh, like every trapezoid entry).
    T = igg.update_halo(T)
    dx, dy, dz = params.spacing()
    scal = dict(rdx2=1.0 / (dx * dx), rdy2=1.0 / (dy * dy),
                rdz2=1.0 / (dz * dz))
    A = float(params.timestep() * params.lam) / Cp
    bx = 8
    assert trapezoid_supported(grid, T.shape, bx, 2 * bx, T.dtype,
                               allow_open=True)

    out, done = jax.jit(
        lambda T, A: fused_diffusion_trapezoid_steps(
            T, A, n_inner=2 * bx, bx=bx, grid=grid, **scal))(T, A)
    assert done == 2 * bx

    dt = params.timestep()
    ref = T
    step = jax.jit(lambda T: fused_diffusion_step(
        T, Cp, dx=dx, dy=dy, dz=dz, dt=dt, lam=params.lam, bx=bx))
    for _ in range(2 * bx):
        ref = step(ref)
    scale = float(jnp.max(jnp.abs(ref)))
    assert float(jnp.max(jnp.abs(out - ref))) <= 4e-7 * scale
    # Frozen boundary planes must match BITWISE: both paths leave them
    # untouched (no-write), so they carry the entry values exactly.
    outn, refn, Tn = np.asarray(out), np.asarray(ref), np.asarray(T)
    for d, p in enumerate(periods):
        if p:
            continue
        for edge in (slice(0, 1), slice(-1, None)):
            sl = [slice(None)] * 3
            sl[d] = edge
            assert np.array_equal(outn[tuple(sl)], refn[tuple(sl)]), d
            assert np.array_equal(outn[tuple(sl)], Tn[tuple(sl)]), d
    igg.finalize_global_grid()


def test_trapezoid_oext_kernel_matches_window():
    """Round 6: the multi-device open program shape ("oext" — extended by
    non-wrapping permutes, global-edge devices re-freeze their boundary
    plane from SMEM-flag-gated VMEM freeze planes) against the pure-XLA
    window realization on the same extended buffer.  On the 1-chip mesh
    the single device is BOTH global edges, so both freeze planes and
    both `axis_index` flags are exercised; the window realization is
    itself pinned per-step-equivalent on 8-device open meshes by
    tests/test_trapezoid.py."""
    import jax.numpy as jnp

    from igg.models import diffusion3d as d3
    from igg.ops.diffusion_trapezoid import _chunk_call, _extend

    igg.init_global_grid(64, 64, 128, dimx=1, dimy=1, dimz=1,
                         periodx=0, periody=1, periodz=1, quiet=True)
    grid = igg.get_global_grid()
    params = d3.Params()
    T, Cp = d3.init_fields(params, dtype=np.float32)
    T = igg.update_halo(T)
    dx, dy, dz = params.spacing()
    scal = dict(rdx2=1.0 / (dx * dx), rdy2=1.0 / (dy * dy),
                rdz2=1.0 / (dz * dz))
    A = float(params.timestep() * params.lam) / Cp
    K = bx = 8
    modes = ("oext", "ext", "wrap")
    shape = T.shape

    @igg.sharded
    def kernel_chunk(T, A):
        Text = _extend(T, K, grid, shape, modes)
        A_ext = _extend(A, K, grid, shape, modes)
        return _chunk_call(Text, A_ext, shape, K=K, bx=bx, modes=modes,
                           grid=grid, **scal)

    @igg.sharded
    def window_chunk(T, A):
        Text = _extend(T, K, grid, shape, modes)
        A_ext = _extend(A, K, grid, shape, modes)
        return _chunk_call(Text, A_ext, shape, K=K, bx=bx, modes=modes,
                           grid=grid, **scal, interpret=True)

    out = np.asarray(kernel_chunk(T, A))
    ref = np.asarray(window_chunk(T, A))
    scale = max(abs(ref).max(), 1e-30)
    assert abs(out - ref).max() <= 4e-7 * scale
    igg.finalize_global_grid()


def test_stokes_kernel_compiled_matches_xla():
    """Round 4: the mesh-capable fused Stokes kernel COMPILED on the chip
    (engine-routed x planes, staggered per-field halo modes) vs the XLA
    composition — the interpret-mode equivalence is pinned on CPU by
    tests/test_stokes_pallas.py; this pins the Mosaic lowering."""
    import jax.numpy as jnp

    from igg.models import stokes3d

    igg.init_global_grid(64, 64, 64, dimx=1, dimy=1, dimz=1,
                         periodx=1, periody=1, periodz=1,
                         overlapx=3, overlapy=3, overlapz=3, quiet=True)
    params = stokes3d.Params()
    fields = stokes3d.init_fields(params, dtype=np.float32)
    it_x = stokes3d.make_iteration(params, n_inner=2, donate=False,
                                   use_pallas=False)
    it_p = stokes3d.make_iteration(params, n_inner=2, donate=False,
                                   use_pallas=True)
    Sx = Sp = fields[:4]
    Rho = fields[4]
    for _ in range(2):
        Sx = it_x(*Sx, Rho)
        Sp = it_p(*Sp, Rho)
    for name, a, b in zip(("P", "Vx", "Vy", "Vz"), Sx, Sp):
        d = float(jnp.max(jnp.abs(a - b)))
        s = float(jnp.max(jnp.abs(a))) + 1e-30
        assert d / s < 1e-5, (name, d, s)
    igg.finalize_global_grid()


def test_hm3d_kernel_compiled_matches_xla():
    """Round 4: the mesh-capable fused HM3D kernel COMPILED on the chip
    (engine-routed x planes, single-step emit_slabs=False and the
    slab-carry multi-step) vs the XLA composition."""
    import jax.numpy as jnp

    from igg.models import hm3d

    igg.init_global_grid(64, 64, 128, dimx=1, dimy=1, dimz=1,
                         periodx=1, periody=1, periodz=1, quiet=True)
    params = hm3d.Params()
    Pe, phi = hm3d.init_fields(params, dtype=np.float32)
    ref = hm3d.make_step(params, n_inner=3, donate=False, use_pallas=False)
    pal = hm3d.make_step(params, n_inner=3, donate=False, use_pallas=True)
    Sr = ref(Pe, phi)
    Sp = pal(Pe, phi)
    for name, a, b in zip(("Pe", "phi"), Sr, Sp):
        d = float(jnp.max(jnp.abs(a - b)))
        s = float(jnp.max(jnp.abs(a))) + 1e-30
        assert d / s < 1e-5, (name, d, s)
    igg.finalize_global_grid()


def test_hm3d_mega_matches_per_step_kernel():
    """The two-field K-step HM3D mega-kernel (manual DMA, HBM ping-pong for
    both fields) must match K applications of the per-step fused kernel."""
    import jax
    import jax.numpy as jnp

    from igg.models import hm3d
    from igg.ops.hm3d_mega import fused_hm3d_megasteps, hm3d_mega_supported
    from igg.ops.hm3d_pallas import fused_hm3d_step

    igg.init_global_grid(64, 64, 128, dimx=1, dimy=1, dimz=1,
                         periodx=1, periody=1, periodz=1, quiet=True)
    params = hm3d.Params()
    Pe, phi = hm3d.init_fields(params, dtype=np.float32)
    Pe, phi = igg.update_halo(Pe, phi)
    dx, dy, dz = params.spacing()
    kw = dict(dx=dx, dy=dy, dz=dz, dt=params.timestep(), phi0=params.phi0,
              npow=params.npow, eta=params.eta)
    assert hm3d_mega_supported(Pe.shape, 8, 6, False, Pe.dtype)

    out = jax.jit(lambda Pe, phi: fused_hm3d_megasteps(
        Pe, phi, n_inner=6, bx=8, **kw))(jnp.array(Pe), jnp.array(phi))

    rp, rf = jnp.array(Pe), jnp.array(phi)
    step = jax.jit(lambda Pe, phi: fused_hm3d_step(Pe, phi, **kw, bx=8))
    for _ in range(6):
        rp, rf = step(rp, rf)
    for name, a, b in (("Pe", out[0], rp), ("phi", out[1], rf)):
        d = float(jnp.max(jnp.abs(a - b)))
        s = float(jnp.max(jnp.abs(b))) + 1e-30
        assert d / s < 1e-6, (name, d, s)
    igg.finalize_global_grid()


def _expected_halo(host, periods):
    """The reference's update semantics on one device: periodic dims
    copy the opposite inner planes into the halo, open dims keep their
    stale planes (no-write)."""
    exp = host.copy()
    for d in range(host.ndim):
        if not periods[d]:
            continue
        n = host.shape[d]
        sl_first, sl_last, src_first, src_last = (
            [slice(None)] * host.ndim for _ in range(4))
        sl_first[d], src_first[d] = 0, n - 2
        sl_last[d], src_last[d] = n - 1, 1
        exp[tuple(sl_first)] = exp[tuple(src_first)]
        exp[tuple(sl_last)] = exp[tuple(src_last)]
    return exp


@pytest.mark.parametrize("periods", [(1, 1, 1), (1, 1, 0)])
def test_f64_halo_oracle_on_chip(periods):
    """Float64 (the reference's default element type) halo exchange on
    real hardware: the barrier-fenced op-mix plans ('select' lane-active,
    'dus64' otherwise — see igg.halo._assembly_plan) must reproduce the
    reference's update semantics exactly, in the device representation.

    The oracle encodes coordinates as small integers, which the x64
    rewriter's float-float pairs represent exactly, so equality is
    bitwise."""
    import jax
    import jax.numpy as jnp

    n = 64
    with jax.enable_x64(True):
        igg.init_global_grid(n, n, n, dimx=1, dimy=1, dimz=1,
                             periodx=periods[0], periody=periods[1],
                             periodz=periods[2], quiet=True)
        i, j, k = np.meshgrid(np.arange(n), np.arange(n), np.arange(n),
                              indexing="ij")
        host = (i * n * n + j * n + k).astype(np.float64)

        out = np.asarray(igg.update_halo(jnp.asarray(host)))

        exp = _expected_halo(host, periods)
        assert np.array_equal(out, exp), (
            periods, np.argwhere(out != exp)[:5])
        igg.finalize_global_grid()


def test_complex64_halo_oracle_on_chip():
    """complex64 halo exchange on the chip, bit for bit against the
    reference's update semantics (docs/migration.md's complex envelope).
    complex128 is not probed: XLA:TPU's lowering aborts the whole process
    on it (a fatal `Check failed ... ByteSizeOfPrimitiveType=16`), so on
    the chip it is carried as re/im real field pairs."""
    import jax.numpy as jnp

    n = 64
    igg.init_global_grid(n, n, 2 * n, dimx=1, dimy=1, dimz=1,
                         periodx=1, periody=1, periodz=1, quiet=True)
    i, j, k = np.meshgrid(np.arange(n), np.arange(n), np.arange(2 * n),
                          indexing="ij")
    idx = (i * n * 2 * n + j * 2 * n + k).astype(np.float32)
    host = (idx - 1j * idx).astype(np.complex64)
    out = np.asarray(igg.update_halo(jnp.asarray(host)))
    exp = _expected_halo(host, (1, 1, 1))
    assert np.array_equal(out, exp), np.argwhere(out != exp)[:5]
    igg.finalize_global_grid()


def test_mega_streamed_a_matches_resident():
    """The slab-streamed coefficient pipeline (round 5 — the mode that
    unlocks local blocks whose A cannot stay VMEM-resident, e.g. the
    512^3 headline) must be bitwise identical to the resident mode: same
    arithmetic, different A sourcing."""
    import jax.numpy as jnp

    from igg.models import diffusion3d as d3
    from igg.ops.diffusion_mega import fused_diffusion_megasteps

    igg.init_global_grid(64, 64, 128, dimx=1, dimy=1, dimz=1,
                         periodx=1, periody=1, periodz=1, quiet=True)
    params = d3.Params()
    T, Cp = d3.init_fields(params, dtype=np.float32)
    dx, dy, dz = params.spacing()
    scal = dict(rdx2=1.0 / (dx * dx), rdy2=1.0 / (dy * dy),
                rdz2=1.0 / (dz * dz))
    A = float(params.timestep() * params.lam) / Cp

    res = fused_diffusion_megasteps(jnp.array(T), A, n_inner=6, bx=8, **scal)
    stw = fused_diffusion_megasteps(jnp.array(T), A, n_inner=6, bx=8, **scal,
                                    force_streamed=True)
    assert np.array_equal(np.asarray(res), np.asarray(stw))
    igg.finalize_global_grid()


@pytest.mark.parametrize("periods", [(0, 0, 0), (0, 1, 1), (1, 1, 0),
                                     (1, 0, 1), (0, 0, 1)])
@pytest.mark.parametrize("streamed", [False, True])
def test_mega_frozen_modes_match_per_step_kernel(periods, streamed):
    """Open-boundary (frozen-edge) mega modes vs K applications of the
    per-step fused kernel, which realizes the no-write halo semantics
    through the engine's stale planes — including the all-open case of
    the reference's published 510^3 headline workload."""
    import jax
    import jax.numpy as jnp

    from igg.models import diffusion3d as d3
    from igg.ops import fused_diffusion_step
    from igg.ops.diffusion_mega import fused_diffusion_megasteps

    igg.init_global_grid(64, 64, 128, dimx=1, dimy=1, dimz=1,
                         periodx=periods[0], periody=periods[1],
                         periodz=periods[2], quiet=True)
    params = d3.Params()
    T, Cp = d3.init_fields(params, dtype=np.float32)
    dx, dy, dz = params.spacing()
    dt = params.timestep()
    scal = dict(rdx2=1.0 / (dx * dx), rdy2=1.0 / (dy * dy),
                rdz2=1.0 / (dz * dz))
    A = float(dt * params.lam) / Cp
    modes = tuple("wrap" if p else "frozen" for p in periods)

    out = fused_diffusion_megasteps(jnp.array(T), A, n_inner=6, bx=8,
                                    **scal, modes=modes,
                                    force_streamed=streamed)

    step = jax.jit(lambda T: fused_diffusion_step(
        T, Cp, dx=dx, dy=dy, dz=dz, dt=dt, lam=params.lam, bx=8))
    ref = jnp.array(T)
    for _ in range(6):
        ref = step(ref)
    scale = float(jnp.max(jnp.abs(ref)))
    assert float(jnp.max(jnp.abs(out - ref))) <= 4e-7 * scale
    # Frozen boundary rows must match the per-step path BITWISE (their
    # interior + frozen-dim cells never change; wrap-dim halo cells of a
    # frozen row are rewritten once from within the row — both paths do
    # it identically), and their frozen-dim interiors must equal the
    # untouched initial values.
    outn, refn, Tn = np.asarray(out), np.asarray(ref), np.asarray(T)
    inner = [slice(1, -1)] * 3
    for d, p in enumerate(periods):
        if p:
            continue
        for edge in (slice(0, 1), slice(-1, None)):
            sl = [slice(None)] * 3
            sl[d] = edge
            assert np.array_equal(outn[tuple(sl)], refn[tuple(sl)]), d
            sli = list(inner)
            sli[d] = edge
            assert np.array_equal(outn[tuple(sli)], Tn[tuple(sli)]), d
    igg.finalize_global_grid()


@pytest.mark.parametrize("shape, periods, n_inner, streamed", [
    ((64, 64, 128), (0, 0, 0), 10, False),
    ((64, 64, 128), (0, 0, 0), 11, True),
    ((64, 64, 128), (0, 1, 1), 10, True),
    ((64, 64, 128), (0, 1, 1), 11, False),
    ((64, 64, 128), (0, 1, 0), 10, False),
    ((64, 64, 128), (0, 0, 1), 11, True),
    ((12, 64, 128), (0, 1, 0), 7, True),
    ((512, 512, 512), (0, 0, 0), 10, False),
], ids=["open_2x5", "open_8+3_streamed", "wrapyz_2x5_streamed",
        "wrapyz_8+3", "wrapy_2x5", "wrapz_8+3_streamed",
        "three_slabs_7_streamed", "512_open_2x5"])
def test_mega_wavefront_matches_per_step_sweep(shape, periods, n_inner,
                                               streamed):
    """The open-x wavefront (D steps per HBM sweep) against the kernel
    that sweeps HBM once per step, bit for bit: identical arithmetic per
    cell, only the order of the rows changes.  n_inner 10 runs two sweeps
    of 5, 11 one of 8 plus a 3-step remainder sweep; 512^3 streams its
    coefficient whatever `streamed` says."""
    import jax.numpy as jnp

    from igg.ops.diffusion_mega import (fused_diffusion_megasteps,
                                        wavefront_plan)

    modes = tuple("wrap" if p else "frozen" for p in periods)
    assert wavefront_plan(shape, n_inner, modes, np.float32)
    rng = np.random.default_rng(n_inner)
    T = jnp.asarray(rng.standard_normal(shape, dtype=np.float32))
    A = jnp.asarray((0.05 + 0.1 * rng.random(shape, dtype=np.float32)))
    kw = dict(n_inner=n_inner, bx=8 if shape[0] >= 16 else 4, rdx2=1.0,
              rdy2=1.1, rdz2=0.9, modes=modes, force_streamed=streamed)
    out = np.asarray(fused_diffusion_megasteps(jnp.array(T), A, **kw))
    ref = np.asarray(fused_diffusion_megasteps(jnp.array(T), A, **kw,
                                               _per_step_sweep=True))
    assert np.array_equal(out, ref), np.abs(out - ref).max()
    assert not np.array_equal(out, np.asarray(T))


def test_f64_rank4_halo_oracle_on_chip():
    """Rank-4 component-stacked Float64 fields on real hardware: the halo
    dims (0,1,2) miss the lane (component) axis, so this exercises the
    pair-emulated 'dus64' sequential path end-to-end with trailing
    unsharded dims (the reference's rank-generic GGArray in its default
    dtype)."""
    import jax
    import jax.numpy as jnp

    n, C = 32, 2
    with jax.enable_x64(True):
        igg.init_global_grid(n, n, n, dimx=1, dimy=1, dimz=1,
                             periodx=1, periody=1, periodz=1, quiet=True)
        i, j, k, c = np.meshgrid(np.arange(n), np.arange(n), np.arange(n),
                                 np.arange(C), indexing="ij")
        host = (((i * n + j) * n + k) * C + c).astype(np.float64)

        out = np.asarray(igg.update_halo(jnp.asarray(host)))

        exp = host.copy()
        for d in range(3):
            sl_first = [slice(None)] * 4
            sl_last = [slice(None)] * 4
            src_first = [slice(None)] * 4
            src_last = [slice(None)] * 4
            sl_first[d] = 0
            src_first[d] = n - 2
            sl_last[d] = n - 1
            src_last[d] = 1
            exp[tuple(sl_first)] = exp[tuple(src_first)]
            exp[tuple(sl_last)] = exp[tuple(src_last)]
        assert np.array_equal(out, exp), np.argwhere(out != exp)[:5]
        igg.finalize_global_grid()


@pytest.mark.parametrize("periods", [(1, 1, 1), (0, 0, 0)],
                         ids=["selfwrap", "open_frozen"])
def test_stokes_trapezoid_matches_per_iteration(periods):
    """The K-iteration Stokes chunk kernel (compiled VMEM-resident bands,
    `igg.ops.stokes_trapezoid._kernel`) against the per-iteration fused
    kernel on the 1-device 128^3 grid — periodic self-wrap (the headline
    benchmark config, x self-extended) and all-open (frozen velocity
    boundary planes).  The window-vs-composition equivalence is pinned on
    CPU meshes by tests/test_stokes_trapezoid.py; this pins the Mosaic
    banded realization against the shipped per-iteration tier."""
    import jax.numpy as jnp

    from igg.models import stokes3d
    from igg.ops.stokes_trapezoid import fit_stokes_K

    igg.init_global_grid(128, 128, 128, dimx=1, dimy=1, dimz=1,
                         periodx=periods[0], periody=periods[1],
                         periodz=periods[2],
                         overlapx=3, overlapy=3, overlapz=3, quiet=True)
    grid = igg.get_global_grid()
    params = stokes3d.Params()
    P, Vx, Vy, Vz, Rho = stokes3d.init_fields(params, dtype=np.float32)
    # Overlap-consistent nontrivial entry (the chunk tier's contract):
    # evolve the coordinate init by a few per-iteration kernel steps.
    pre = stokes3d.make_iteration(params, donate=False, n_inner=3,
                                  trapezoid=False)
    P, Vx, Vy, Vz = pre(P, Vx, Vy, Vz, Rho)

    n_inner = 9          # warm-up + one K=8 chunk
    assert fit_stokes_K(grid, (128, 128, 128), n_inner - 1,
                        np.float32) == 8

    ref = stokes3d.make_iteration(params, donate=False, n_inner=n_inner,
                                  trapezoid=False)
    chk = stokes3d.make_iteration(params, donate=False, n_inner=n_inner,
                                  trapezoid=True)
    r = ref(P, Vx, Vy, Vz, Rho)
    o = chk(P, Vx, Vy, Vz, Rho)
    for name, a, b in zip(("P", "Vx", "Vy", "Vz"), r, o):
        scale = float(jnp.max(jnp.abs(a))) + 1e-30
        rel = float(jnp.max(jnp.abs(a - b))) / scale
        assert rel < 1e-4, (name, rel, periods)
    igg.finalize_global_grid()


@pytest.mark.parametrize("periods", [(1, 1, 1), (0, 0, 0)],
                         ids=["selfwrap", "open_frozen"])
def test_hm3d_trapezoid_matches_per_step_kernel(periods):
    """The K-step HM3D chunk kernel (the chunk engine's generic
    VMEM-resident banded kernel, `igg.ops.chunk_engine._resident_kernel`,
    instantiated by `igg.ops.hm3d_trapezoid`) against the per-step fused
    kernel on the 1-device 128^3 grid — periodic self-wrap and all-open
    (both fields' boundary planes frozen).  The window-vs-composition
    equivalence is pinned on CPU meshes by tests/test_chunk_engine.py;
    this pins the compiled banded realization on hardware."""
    import jax.numpy as jnp

    from igg.models import hm3d
    from igg.ops.hm3d_trapezoid import fit_hm3d_K

    igg.init_global_grid(128, 128, 128, dimx=1, dimy=1, dimz=1,
                         periodx=periods[0], periody=periods[1],
                         periodz=periods[2], quiet=True)
    grid = igg.get_global_grid()
    params = hm3d.Params()
    Pe, phi = hm3d.init_fields(params, dtype=np.float32)

    n_inner = 9          # warm-up + one K=8 chunk
    assert fit_hm3d_K(grid, (128, 128, 128), n_inner - 1, np.float32) == 8

    ref = hm3d.make_step(params, donate=False, n_inner=n_inner,
                         trapezoid=False)
    chk = hm3d.make_step(params, donate=False, n_inner=n_inner,
                         trapezoid=True)
    r = ref(Pe, phi)
    o = chk(Pe, phi)
    assert igg.degrade.active().get("hm3d") == "hm3d.trapezoid"
    for name, a, b in zip(("Pe", "phi"), r, o):
        scale = float(jnp.max(jnp.abs(a))) + 1e-30
        rel = float(jnp.max(jnp.abs(a - b))) / scale
        assert rel < 1e-4, (name, rel, periods)
    igg.finalize_global_grid()


@pytest.mark.parametrize("periods", [(1, 1), (0, 0)],
                         ids=["periodic", "open"])
def test_wave2d_mosaic_compiled_matches_xla(periods):
    """The fused wave2d per-step kernel, COMPILED (Mosaic whole-block
    program), against the XLA composition on a 1-device grid."""
    import jax.numpy as jnp

    from igg.models import wave2d

    igg.init_global_grid(512, 512, 1, periodx=periods[0],
                         periody=periods[1], quiet=True)
    params = wave2d.Params()
    fields = wave2d.init_fields(params, dtype=np.float32)
    ref = wave2d.make_step(params, donate=False, n_inner=5,
                           use_pallas=False)
    pal = wave2d.make_step(params, donate=False, n_inner=5,
                           use_pallas=True, chunk=False)
    r = ref(*fields)
    o = pal(*fields)
    assert igg.degrade.active().get("wave2d") == "wave2d.mosaic"
    for name, a, b in zip(("P", "Vx", "Vy"), r, o):
        scale = float(jnp.max(jnp.abs(a))) + 1e-30
        rel = float(jnp.max(jnp.abs(a - b))) / scale
        assert rel < 1e-5, (name, rel, periods)
    igg.finalize_global_grid()


def test_wave2d_chunk_compiled_matches_per_step():
    """The K-step wave2d chunk kernel (compiled whole-window resident
    program, `igg.ops.wave2d_pallas._chunk_kernel`) against the per-step
    fused kernel on a 1-device periodic grid."""
    import jax.numpy as jnp

    from igg.models import wave2d
    from igg.ops.wave2d_pallas import fit_wave2d_K

    igg.init_global_grid(512, 512, 1, periodx=1, periody=1, quiet=True)
    grid = igg.get_global_grid()
    params = wave2d.Params()
    fields = wave2d.init_fields(params, dtype=np.float32)
    pre = wave2d.make_step(params, donate=False, n_inner=3,
                           use_pallas=True, chunk=False)
    fields = pre(*fields)

    n_inner = 9          # warm-up + one K=8 chunk
    assert fit_wave2d_K(grid, (512, 512), n_inner - 1, np.float32) == 8

    ref = wave2d.make_step(params, donate=False, n_inner=n_inner,
                           use_pallas=True, chunk=False)
    chk = wave2d.make_step(params, donate=False, n_inner=n_inner,
                           use_pallas=True, chunk=True)
    r = ref(*fields)
    o = chk(*fields)
    assert igg.degrade.active().get("wave2d") == "wave2d.chunk"
    for name, a, b in zip(("P", "Vx", "Vy"), r, o):
        scale = float(jnp.max(jnp.abs(a))) + 1e-30
        rel = float(jnp.max(jnp.abs(a - b))) / scale
        assert rel < 1e-4, (name, rel)
    igg.finalize_global_grid()
