"""Sweep the fused Pallas kernel's slab size (bx) and both execution paths
at 256^3 on the real TPU chip; records ms/step and achieved GB/s against the
ideal-fusion traffic model (read T + Cp, write T = 3 * n^3 * 4 bytes).

VERDICT round-1 items 3-4: the recorded bx sweep behind the default slab
size, emitted as provenance-stamped JSON lines.

Usage: `python benchmarks/pallas_sweep.py [n] [nt] [n_inner]`.
"""

from __future__ import annotations

import sys

import numpy as np

from common import emit, median_of, note


def main():
    import jax

    import igg
    from igg.models import diffusion3d as d3

    platform = jax.devices()[0].platform
    n = int(sys.argv[1]) if len(sys.argv) > 1 else (256 if platform == "tpu" else 64)
    nt = int(sys.argv[2]) if len(sys.argv) > 2 else (12 if platform == "tpu" else 2)
    n_inner = int(sys.argv[3]) if len(sys.argv) > 3 else (100 if platform == "tpu" else 5)

    cells = float(n) ** 3
    ideal_bytes = 3 * cells * 4

    igg.init_global_grid(n, n, n, periodx=1, periody=1, periodz=1, quiet=True)
    grid = igg.get_global_grid()
    note(f"platform={platform} devices={grid.nprocs} local={n}^3")
    params = d3.Params()

    configs = [("xla", dict(use_pallas=False))]
    if platform == "tpu":
        # bx=64 double-buffers 3x 16MB windows and exceeds the 128MB VMEM.
        for bx in (4, 8, 16, 32):
            configs.append((f"pallas_bx{bx}", dict(use_pallas=True, bx=bx)))
    for tag, kw in configs:
        try:
            sec = median_of(lambda: d3.run(nt, params, dtype=np.float32,
                                           n_inner=n_inner, **kw)[1])
        except Exception as e:  # e.g. VMEM overflow at large bx
            note(f"{tag}: FAILED {str(e)[:200]}")
            continue
        emit({
            "metric": "pallas_sweep_ms_per_step", "config": tag, "local": n,
            "value": round(sec * 1e3, 4), "unit": "ms",
            "gbps_ideal_traffic": round(ideal_bytes / sec / 1e9, 1),
            "platform": platform,
        })

    if platform == "tpu":
        # The x-EXCHANGED (N,1,1) program shape, exercised on the 1-device
        # self-ring (bit-identical collectives/window structure; real
        # meshes add ICI latency the K-deep chunks amortize by 1/K):
        # K-step trapezoidal chunks vs the per-step kernel in a fori loop.
        from jax import lax

        from igg.ops import fused_diffusion_step
        from igg.ops.diffusion_trapezoid import (
            fused_diffusion_trapezoid_steps, trapezoid_supported)
        from igg.timing import time_steps

        dx, dy, dz = params.spacing()
        dt = params.timestep()
        scal = dict(rdx2=1.0 / (dx * dx), rdy2=1.0 / (dy * dy),
                    rdz2=1.0 / (dz * dz))

        def fresh():
            T, Cp = d3.init_fields(params, dtype=np.float32)
            return igg.update_halo(T), Cp

        def measure(tag, fn, T, steps):
            _, sec = time_steps(lambda T: (fn(T),), (T,), n1=nt, n2=3 * nt)
            sec /= steps   # divide by the steps the program ACTUALLY runs
            emit({
                "metric": "pallas_sweep_ms_per_step", "config": tag,
                "local": n, "value": round(sec * 1e3, 4), "unit": "ms",
                "gbps_ideal_traffic": round(ideal_bytes / sec / 1e9, 1),
                "platform": platform,
            })

        for bx, y_ext, z_ext in ((8, False, False), (16, False, False),
                                 (8, True, False), (8, True, True)):
            T, Cp = fresh()
            A = float(dt * params.lam) / Cp
            if not trapezoid_supported(grid, T.shape, bx, n_inner,
                                       T.dtype, force_y_ext=y_ext,
                                       force_z_ext=z_ext):
                note(f"trapezoid bx={bx}: unsupported at {n}^3")
                continue
            steps = (n_inner // bx) * bx
            fn = jax.jit(
                lambda T, bx=bx, A=A, s=steps, ye=y_ext, ze=z_ext:
                fused_diffusion_trapezoid_steps(
                    T, A, n_inner=s, bx=bx, grid=grid, force_y_ext=ye,
                    force_z_ext=ze, **scal)[0],
                donate_argnums=0)
            tag = "torus3d" if z_ext else ("torus" if y_ext else "ring")
            measure(f"trapezoid_{tag}_bx{bx}", fn, T, steps)

        T, Cp = fresh()
        step = lambda T: fused_diffusion_step(
            T, Cp, dx=dx, dy=dy, dz=dz, dt=dt, lam=params.lam, bx=16)
        fn = jax.jit(lambda T: lax.fori_loop(0, n_inner,
                                             lambda _, T: step(T), T),
                     donate_argnums=0)
        measure("perstep_ring_bx16", fn, T, n_inner)

    if platform == "tpu":
        # OPEN boundaries — the reference's default (its examples are
        # non-periodic) — on the compiled K-step chunk tier (round 6): on
        # one chip every open dim runs the "frozen" edge-freeze mode
        # (multi-device grids run "oext"; same kernel, flag-gated), vs the
        # per-step kernel on the same open grid.
        igg.finalize_global_grid()
        igg.init_global_grid(n, n, n, quiet=True)   # all dims open
        grid = igg.get_global_grid()

        def fresh_open():
            T, Cp = d3.init_fields(params, dtype=np.float32)
            return igg.update_halo(T), Cp

        for bx in (8, 16):
            T, Cp = fresh_open()
            A = float(dt * params.lam) / Cp
            if not trapezoid_supported(grid, T.shape, bx, n_inner,
                                       T.dtype, allow_open=True):
                note(f"trapezoid open bx={bx}: unsupported at {n}^3")
                continue
            steps = (n_inner // bx) * bx
            fn = jax.jit(
                lambda T, bx=bx, A=A, s=steps:
                fused_diffusion_trapezoid_steps(
                    T, A, n_inner=s, bx=bx, grid=grid, **scal)[0],
                donate_argnums=0)
            measure(f"trapezoid_open_bx{bx}", fn, T, steps)

        T, Cp = fresh_open()
        step = lambda T: fused_diffusion_step(
            T, Cp, dx=dx, dy=dy, dz=dz, dt=dt, lam=params.lam, bx=16)
        fn = jax.jit(lambda T: lax.fori_loop(0, n_inner,
                                             lambda _, T: step(T), T),
                     donate_argnums=0)
        measure("perstep_open_bx16", fn, T, n_inner)

    if platform == "tpu":
        # K-iteration Stokes trapezoid chunk tier (round 7) at 128^3 —
        # the VMEM-admissible headline size (the resident working set
        # gates 160^3+ out; docs/stokes_roofline.md carries the K-bound
        # accounting).  Rows: the per-iteration fused kernel baseline
        # (the 0.143 ms/iter tier) and the chunk kernel's steady-state
        # chunk rate over a K sweep, periodic self-wrap AND all-open
        # (frozen velocity boundary planes).
        from jax import lax

        from igg.models import stokes3d
        from igg.ops import fused_stokes_iteration
        from igg.ops.stokes_trapezoid import (
            fused_stokes_trapezoid_iters, stokes_trapezoid_supported)
        from igg.timing import time_steps as _ts

        igg.finalize_global_grid()
        ns = 128
        sparams = stokes3d.Params()
        for bc, periods in (("", (1, 1, 1)), ("open_", (0, 0, 0))):
            igg.init_global_grid(ns, ns, ns, dimx=1, dimy=1, dimz=1,
                                 periodx=periods[0], periody=periods[1],
                                 periodz=periods[2], overlapx=3,
                                 overlapy=3, overlapz=3, quiet=True)
            grid = igg.get_global_grid()
            kwp = stokes3d._pseudo_steps(sparams)

            def fresh_stokes():
                # Overlap-consistent nontrivial entry (the chunk tier's
                # contract): the coordinate init evolved a few kernel
                # iterations.
                P, Vx, Vy, Vz, Rho = stokes3d.init_fields(
                    sparams, dtype=np.float32)
                pre = stokes3d.make_iteration(sparams, donate=False,
                                              n_inner=3, trapezoid=False)
                return (*pre(P, Vx, Vy, Vz, Rho), Rho)

            def smeasure(tag, fn, state, iters):
                _, sec = _ts(fn, state, n1=nt, n2=3 * nt)
                sec /= iters
                emit({
                    "metric": "pallas_sweep_ms_per_step",
                    "config": tag, "local": ns,
                    "value": round(sec * 1e3, 4), "unit": "ms",
                    "platform": platform,
                })

            state = fresh_stokes()
            periter = jax.jit(
                lambda P, Vx, Vy, Vz, Rho: (*lax.fori_loop(
                    0, n_inner,
                    lambda _, S: fused_stokes_iteration(*S, Rho, **kwp),
                    (P, Vx, Vy, Vz)), Rho),
                donate_argnums=(0, 1, 2, 3))
            smeasure(f"stokes_{bc}periter_fused", periter, state, n_inner)

            for Kc in (4, 8):
                if not stokes_trapezoid_supported(grid, (ns, ns, ns), Kc,
                                                  n_inner, np.float32):
                    note(f"stokes_trapezoid {bc}K={Kc}: unsupported at "
                         f"{ns}^3")
                    continue
                steps = (n_inner // Kc) * Kc
                fn = jax.jit(
                    lambda P, Vx, Vy, Vz, Rho, Kc=Kc, s=steps:
                    (*fused_stokes_trapezoid_iters(
                        P, Vx, Vy, Vz, Rho, n_inner=s, K=Kc,
                        **kwp)[:4], Rho),
                    donate_argnums=(0, 1, 2, 3))
                smeasure(f"stokes_trapezoid_{bc}K{Kc}", fn,
                         fresh_stokes(), steps)
            igg.finalize_global_grid()
        # The every-platform section below opens with finalize; leave a
        # grid initialized for it (its contents are never read).
        igg.init_global_grid(n, n, n, quiet=True)

    # Every platform: the open-boundary chunk path's XLA window
    # realization (interpret mode — same gates, same chunked structure) at
    # a fixed small shape, so the CI bench smoke always carries one
    # open-boundary chunk row (round 6) regardless of the host's
    # accelerator and of `n`.
    from igg.ops.diffusion_trapezoid import (
        fused_diffusion_trapezoid_steps as _traps,
        trapezoid_supported as _trap_ok)
    from igg.timing import time_steps

    igg.finalize_global_grid()
    igg.init_global_grid(16, 16, 128, quiet=True)   # all dims open
    grid = igg.get_global_grid()
    dx, dy, dz = params.spacing()
    scal16 = dict(rdx2=1.0 / (dx * dx), rdy2=1.0 / (dy * dy),
                  rdz2=1.0 / (dz * dz))
    bx = 8
    assert _trap_ok(grid, (16, 16, 128), bx, bx, np.float32,
                    allow_open=True)
    T = igg.update_halo(igg.zeros((16, 16, 128), dtype=np.float32) + 1)
    A = igg.zeros((16, 16, 128), dtype=np.float32) + 0.05
    # igg.sharded, not plain jit: on a virtual multi-device host the open
    # dims run "oext" and the slab exchange needs the mesh axes bound.
    step_open = igg.sharded(
        lambda T, A: _traps(T, A, n_inner=bx, bx=bx, grid=grid, **scal16,
                            interpret=True)[0], donate_argnums=(0,))
    _, sec = time_steps(lambda T, A: (step_open(T, A), A), (T, A),
                        n1=2, n2=4)
    emit({
        "metric": "pallas_sweep_ms_per_step",
        "config": "trapezoid_open_interpret_bx8", "local": 16,
        "value": round(sec / bx * 1e3, 4), "unit": "ms",
        "platform": platform,
    })
    igg.finalize_global_grid()

    # Ditto for the Stokes chunk tier (round 7): the window realization of
    # one K=4 chunk on an open overlap-3 grid, emitted on EVERY platform
    # so the CI smoke always carries a stokes_trapezoid row.
    from igg.models import stokes3d
    from igg.ops.stokes_trapezoid import (fused_stokes_trapezoid_iters
                                          as _straps,
                                          stokes_trapezoid_supported
                                          as _strap_ok)

    igg.init_global_grid(16, 16, 128, overlapx=3, overlapy=3, overlapz=3,
                         quiet=True)   # all dims open
    grid = igg.get_global_grid()
    Ks = 4
    assert _strap_ok(grid, (16, 16, 128), Ks, Ks, np.float32,
                     interpret=True)
    sparams = stokes3d.Params(lx=4.0, ly=4.0, lz=4.0)
    skw = stokes3d._pseudo_steps(sparams)
    sP, sVx, sVy, sVz, sRho = stokes3d.init_fields(sparams,
                                                   dtype=np.float32)
    pre = stokes3d.make_iteration(sparams, donate=False, n_inner=2,
                                  use_pallas=False)
    sP, sVx, sVy, sVz = pre(sP, sVx, sVy, sVz, sRho)
    step_chunk = igg.sharded(
        lambda P, Vx, Vy, Vz, Rho: _straps(P, Vx, Vy, Vz, Rho,
                                           n_inner=Ks, K=Ks, **skw,
                                           interpret=True)[:4],
        donate_argnums=(0, 1, 2, 3))
    _, sec = time_steps(
        lambda P, Vx, Vy, Vz, Rho: (*step_chunk(P, Vx, Vy, Vz, Rho), Rho),
        (sP, sVx, sVy, sVz, sRho), n1=2, n2=4)
    emit({
        "metric": "pallas_sweep_ms_per_step",
        "config": "stokes_trapezoid_open_interpret_K4", "local": 16,
        "value": round(sec / Ks * 1e3, 4), "unit": "ms",
        "platform": platform,
    })
    igg.finalize_global_grid()

    # Round 16: the two NEW chunk-engine rungs, emitted on EVERY platform
    # as CONTRACT rows ("pass" = the tier's output matches the XLA
    # composition within tolerance) — golden-gated via `igg.perf compare`
    # (benchmarks/goldens/pallas_sweep.jsonl keeps the contract rows;
    # run_all's GOLDEN_CONTRACT_ONLY filter).  The interpret realizations
    # run the same admission gates and chunked-exchange structure the
    # compiled kernels take; the compiled kernels themselves are pinned
    # by tests/test_mega_tpu.py on hardware.
    from igg.models import hm3d as _hm

    # Automatic dims (no more (8,1,1) pin): the sublane-tile-extension
    # refusal is a structured Admission reason now, so the smoke row
    # picks the depth the live mesh admits instead of crashing on a
    # hard-coded one — (2,2,2)'s y-extension needs E % 8 == 0, which
    # K=8 satisfies (`fit_hm3d_K` finds it; `chunk_engine.
    # admit_sublane_extension` refuses K=4 with the structured reason).
    igg.init_global_grid(16, 16, 128, quiet=True)   # all dims open
    from igg.ops.hm3d_trapezoid import fit_hm3d_K as _hfit

    hgrid = igg.get_global_grid()
    hK = _hfit(hgrid, (16, 16, 128), 8, np.float32, interpret=True)
    assert hK, "no hm3d chunk depth admissible on the auto mesh"
    hp = _hm.Params(lx=4.0, ly=4.0, lz=4.0)
    hPe, hphi = _hm.init_fields(hp, dtype=np.float32)
    hn = hK + 1   # warm-up + one K-deep chunk
    href = _hm.make_step(hp, donate=False, n_inner=hn, use_pallas=False)
    htrap = _hm.make_step(hp, donate=False, n_inner=hn, use_pallas=True,
                          pallas_interpret=True, trapezoid=True, K=hK)
    hr = href(hPe, hphi)
    ht = htrap(hPe, hphi)
    hrel = max(
        float(abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))
              .max() / (abs(np.asarray(a, np.float64)).max() + 1e-30))
        for a, b in zip(hr, ht))
    _, sec = time_steps(lambda Pe, phi: htrap(Pe, phi), (hPe, hphi),
                        n1=2, n2=4)
    emit({
        "metric": "pallas_sweep_ms_per_step",
        "config": f"hm3d_trapezoid_open_interpret_K{hK}", "local": 16,
        "value": round(sec / hn * 1e3, 4), "unit": "ms",
        "platform": platform, "rel_vs_composition": hrel,
        "pass": bool(hrel < 1e-4),
    })
    igg.finalize_global_grid()

    n5 = 5   # warm-up + one K=4 chunk
    from igg.models import wave2d as _w2

    igg.init_global_grid(16, 16, 1, periodx=1, periody=1, quiet=True)
    wp = _w2.Params()
    wP, wVx, wVy = _w2.init_fields(wp, dtype=np.float32)
    wref = _w2.make_step(wp, donate=False, n_inner=n5, use_pallas=False)
    wr = wref(wP, wVx, wVy)
    for tag, kw in (("wave2d_mosaic_interpret", dict(chunk=False)),
                    ("wave2d_chunk_interpret_K4", dict(chunk=True, K=4))):
        wstep = _w2.make_step(wp, donate=False, n_inner=n5,
                              use_pallas=True, pallas_interpret=True,
                              **kw)
        wo = wstep(wP, wVx, wVy)
        wrel = max(
            float(abs(np.asarray(a, np.float64)
                      - np.asarray(b, np.float64)).max()
                  / (abs(np.asarray(a, np.float64)).max() + 1e-30))
            for a, b in zip(wr, wo))
        _, sec = time_steps(lambda P, Vx, Vy: wstep(P, Vx, Vy),
                            (wP, wVx, wVy), n1=2, n2=4)
        emit({
            "metric": "pallas_sweep_ms_per_step", "config": tag,
            "local": 16, "value": round(sec / n5 * 1e3, 4), "unit": "ms",
            "platform": platform, "rel_vs_composition": wrel,
            "pass": bool(wrel < 1e-4),
        })
    igg.finalize_global_grid()

    # Round 17: the SPEC-GENERATED rungs (igg.stencil), emitted on EVERY
    # platform as CONTRACT rows and golden-gated like the round-16 ones.
    # The spec-wave2d chunk row's oracle is the HAND-WRITTEN module's
    # composition (the frontend's bit-exactness contract); the
    # shallow-water rows — a family with ZERO hand-written kernel code —
    # gate against their own generated XLA truth.
    from igg import stencil as _st
    from igg.models import shallow_water as _sw

    igg.init_global_grid(16, 16, 1, periodx=1, periody=1, quiet=True)
    wp = _w2.Params()
    wP, wVx, wVy = _w2.init_fields(wp, dtype=np.float32)
    wref = _w2.make_step(wp, donate=False, n_inner=n5,
                         use_pallas=False)(wP, wVx, wVy)
    sstep = _st.compile(_st.wave2d_spec(), coeffs=_st.wave2d_coeffs(wp),
                        donate=False, n_inner=n5, use_pallas=True,
                        pallas_interpret=True, chunk=True, K=4)
    so = sstep(wP, wVx, wVy)
    srel = max(
        float(abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))
              .max() / (abs(np.asarray(a, np.float64)).max() + 1e-30))
        for a, b in zip(wref, so))
    _, sec = time_steps(lambda P, Vx, Vy: sstep(P, Vx, Vy),
                        (wP, wVx, wVy), n1=2, n2=4)
    emit({
        "metric": "pallas_sweep_ms_per_step",
        "config": "stencil_wave2d_chunk_interpret_K4", "local": 16,
        "value": round(sec / n5 * 1e3, 4), "unit": "ms",
        "platform": platform, "rel_vs_hand_composition": srel,
        "pass": bool(srel < 1e-4),
    })

    sp = _sw.Params()
    sfields = _sw.init_fields(sp, dtype=np.float32)
    sref = _sw.make_step(sp, donate=False, n_inner=n5,
                         use_pallas=False)(*sfields)
    for tag, kw in (("shallow_water_mosaic_interpret", dict(chunk=False)),
                    ("shallow_water_chunk_interpret_K4",
                     dict(chunk=True, K=4))):
        swstep = _sw.make_step(sp, donate=False, n_inner=n5,
                               use_pallas=True, pallas_interpret=True,
                               **kw)
        swo = swstep(*sfields)
        swrel = max(
            float(abs(np.asarray(a, np.float64)
                      - np.asarray(b, np.float64)).max()
                  / (abs(np.asarray(a, np.float64)).max() + 1e-30))
            for a, b in zip(sref, swo))
        _, sec = time_steps(lambda h, hu, hv: swstep(h, hu, hv),
                            sfields, n1=2, n2=4)
        emit({
            "metric": "pallas_sweep_ms_per_step", "config": tag,
            "local": 16, "value": round(sec / n5 * 1e3, 4), "unit": "ms",
            "platform": platform, "rel_vs_composition": swrel,
            "pass": bool(swrel < 1e-4),
        })
    igg.finalize_global_grid()


if __name__ == "__main__":
    main()
