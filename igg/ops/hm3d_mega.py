"""K-step fused HM3D mega-kernel (self-wrap single-device grids).

The two-field instance of `diffusion_mega`: ONE `pallas_call` advances the
entire inner time loop of the coupled hydro-mechanical step — grid
`(K, nb)` with sequential semantics, manual HBM<->VMEM DMA, HBM ping-pong
for BOTH fields, and hand double-buffering.  Unlike the diffusion mega
there is no loop-invariant coefficient array to keep resident, so VMEM
holds only the double-buffered slabs (~20 MB at 256³) and the kernel
applies at ANY local x extent.

What it removes vs the per-step fused kernel (`hm3d_pallas`, 0.64 ms/step
at 256³): the per-step XLA glue between kernels — the x-end window
recomputation in XLA, the engine's (self-wrap) plane exchange, and the
kernel-boundary buffer round-trips.  Per-step HBM traffic becomes
`(Pe + phi)*(1 + 2/bx)` reads + `(Pe + phi)` writes.

Measured on v5e at 256³ f32 (slope-timed, K=100, bx=8 — the swept
optimum: bx 4/8/16/32 give 0.530/0.478/0.541/0.551): **0.478 ms/step**,
**6.1x the XLA composition** (2.93 ms) and 1.34x the per-step fused
kernel — ~632 GB/s on the actual ~302 MB/step traffic, at the chip's
sustained streaming rate; the residual vs the ideal 268 MB is the slab
margins, and the nonlinear `(phi/phi0)^n` VPU work overlaps under it.  Matches the
per-step fused kernel to float32 rounding
(`tests/test_mega_tpu.py::test_hm3d_mega_matches_per_step_kernel`).

Halo maintenance is the self-wrap scheme of the per-step kernel: y/z halos
are VMEM aliases of the updated interior; the two x halo planes of each
field are computed by the first program of each step from 3-plane x-end
slabs of the current source buffers
(`/root/reference/src/update_halo.jl:516-532`).

DMA/semaphore accounting mirrors `diffusion_mega._kernel` exactly, with
every per-field structure doubled: each DMA start is paired with exactly
one wait (slot reuse two programs later, a full drain at each step
boundary before the ping-pong source is read, and a final drain).

Not available in interpret mode (manual TPU DMA/semaphores); callers fall
back to the per-step kernel.
"""

from __future__ import annotations

from functools import partial

from .diffusion_mega import _VMEM_BUDGET


def hm3d_mega_supported(shape, bx: int, n_inner: int, interpret: bool,
                        dtype) -> bool:
    """Same gates as `diffusion_mega.mega_supported`, with the two-field
    VMEM accounting and no resident coefficient."""
    import numpy as np

    if interpret or n_inner < 2:
        return False
    S0, S1, S2 = shape
    if S0 % bx != 0 or S0 < 2 * bx:
        return False
    if S2 % 128 != 0 or S1 % 8 != 0:
        return False
    itemsize = np.dtype(dtype).itemsize
    need = itemsize * 2 * (2 * (bx + 2) * S1 * S2    # ext slabs x2 fields
                           + 2 * bx * S1 * S2        # out slabs x2 fields
                           + 8 * S1 * S2)            # x-plane scratch x2
    return need <= _VMEM_BUDGET


def _kernel(Pe_hbm, Phi_hbm, pe_out, phi_out, pb0, pb1, fb0, fb1,
            ext_pe, ext_phi, o_pe, o_phi, xfl_pe, xfl_phi,
            esems_pe, esems_phi, osems_pe, osems_phi, xsems,
            *, K, bx, nb, S0, S1, S2, kw_core):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ..models.hm3d import step_core

    k = pl.program_id(0)
    i = pl.program_id(1)
    sl = i % 2

    # Out-write bookkeeping (per field): drain at each step boundary, else
    # wait the DMA whose slot this program reuses.
    @pl.when((i == 0) & (k > 0))
    def _():
        for o2, osems in ((o_pe, osems_pe), (o_phi, osems_phi)):
            pltpu.make_async_copy(o2.at[0], o2.at[0], osems.at[0]).wait()
            pltpu.make_async_copy(o2.at[1], o2.at[1], osems.at[1]).wait()

    @pl.when(i >= 2)
    def _():
        for o2, osems in ((o_pe, osems_pe), (o_phi, osems_phi)):
            pltpu.make_async_copy(o2.at[sl], o2.at[sl], osems.at[sl]).wait()

    # Extended-slab fetches (rows [i*bx-1, i*bx+bx+1) mod S0) for BOTH
    # fields; edge programs fetch their own wrapping segments synchronously,
    # interior programs consume their predecessor's prefetch and issue the
    # next one.
    def sync_fetch(src, ext2, esems):
        @pl.when(i == 0)
        def _():
            c0 = pltpu.make_async_copy(src.at[S0 - 1:S0],
                                       ext2.at[sl, 0:1], esems.at[sl])
            c1 = pltpu.make_async_copy(src.at[0:bx + 1],
                                       ext2.at[sl, 1:bx + 2],
                                       esems.at[1 - sl])
            c0.start(); c1.start(); c0.wait(); c1.wait()

        @pl.when(i == nb - 1)
        def _():
            c0 = pltpu.make_async_copy(src.at[S0 - bx - 1:S0],
                                       ext2.at[sl, 0:bx + 1], esems.at[sl])
            c1 = pltpu.make_async_copy(src.at[0:1],
                                       ext2.at[sl, bx + 1:bx + 2],
                                       esems.at[1 - sl])
            c0.start(); c1.start(); c0.wait(); c1.wait()

    def prefetch_next(src, ext2, esems):
        @pl.when((i + 1 >= 1) & (i + 1 <= nb - 2))
        def _():
            pltpu.make_async_copy(
                src.at[pl.ds((i + 1) * bx - 1, bx + 2)],
                ext2.at[1 - sl], esems.at[1 - sl]).start()

    def fetch_xplanes(src, xfl, xsem0, xsem1):
        c0 = pltpu.make_async_copy(src.at[S0 - 3:S0], xfl.at[0:3], xsem0)
        c1 = pltpu.make_async_copy(src.at[0:3], xfl.at[3:6], xsem1)
        c0.start(); c1.start(); c0.wait(); c1.wait()

    for cond, src_pe, src_phi in ((k == 0, Pe_hbm, Phi_hbm),
                                  ((k > 0) & (k % 2 == 1), pb0, fb0),
                                  ((k > 0) & (k % 2 == 0), pb1, fb1)):
        @pl.when(cond)
        def _(src_pe=src_pe, src_phi=src_phi):
            sync_fetch(src_pe, ext_pe, esems_pe)
            sync_fetch(src_phi, ext_phi, esems_phi)

            @pl.when(i == 0)
            def _():
                fetch_xplanes(src_pe, xfl_pe, xsems.at[0], xsems.at[1])
                fetch_xplanes(src_phi, xfl_phi, xsems.at[2], xsems.at[3])
            prefetch_next(src_pe, ext_pe, esems_pe)
            prefetch_next(src_phi, ext_phi, esems_phi)

    @pl.when((i > 0) & (i < nb - 1))
    def _():
        for ext2, esems in ((ext_pe, esems_pe), (ext_phi, esems_phi)):
            pltpu.make_async_copy(ext2.at[sl], ext2.at[sl],
                                  esems.at[sl]).wait()

    # x halo planes of this step for both fields (row 0 <- updated row
    # S0-2, row S0-1 <- updated row 1, wrapped in y/z), computed once per
    # step from the x-end slabs.
    @pl.when(i == 0)
    def _():
        def wrap_yz(U):
            U = jnp.concatenate([U[:, -1:, :], U, U[:, :1, :]], axis=1)
            return jnp.concatenate([U[:, :, -1:], U, U[:, :, :1]], axis=2)

        for key in (0, 1):   # 0: hi slab -> plane for row 0; 1: lo slab
            lo_, hi_ = (3, 6) if key else (0, 3)
            wpe = xfl_pe[lo_:hi_]
            wphi = xfl_phi[lo_:hi_]
            dPe, dphi = step_core(wpe, wphi, **kw_core)
            pe_pl = wpe[1:2, 1:-1, 1:-1] + dPe
            phi_pl = wphi[1:2, 1:-1, 1:-1] + dphi
            xfl_pe[6 + key:7 + key] = wrap_yz(pe_pl)
            xfl_phi[6 + key:7 + key] = wrap_yz(phi_pl)

    # Coupled stencil update on the extended slabs + y/z self-wrap assembly
    # (identical scheme to hm3d_pallas._make_kernel in wrap mode).
    ePe = ext_pe.at[sl][:]
    ephi = ext_phi.at[sl][:]
    ope = o_pe.at[sl]
    ophi = o_phi.at[sl]
    dPe, dphi = step_core(ePe, ephi, **kw_core)
    ope[:] = ePe[1:1 + bx]
    ope[:, 1:-1, 1:-1] = ePe[1:1 + bx, 1:-1, 1:-1] + dPe[0:bx]
    ophi[:] = ephi[1:1 + bx]
    ophi[:, 1:-1, 1:-1] = ephi[1:1 + bx, 1:-1, 1:-1] + dphi[0:bx]
    for o in (ope, ophi):
        o[:, 0:1, 1:-1] = o[:, S1 - 2:S1 - 1, 1:-1]
        o[:, S1 - 1:S1, 1:-1] = o[:, 1:2, 1:-1]
        o[:, :, 0:1] = o[:, :, S2 - 2:S2 - 1]
        o[:, :, S2 - 1:S2] = o[:, :, 1:2]

    @pl.when(i == 0)
    def _():
        ope[0:1] = xfl_pe[6:7]
        ophi[0:1] = xfl_phi[6:7]

    @pl.when(i == nb - 1)
    def _():
        ope[bx - 1:bx] = xfl_pe[7:8]
        ophi[bx - 1:bx] = xfl_phi[7:8]

    # Async write-back to this step's destinations.
    def put(o2, dst, osems):
        pltpu.make_async_copy(o2.at[sl], dst.at[pl.ds(i * bx, bx)],
                              osems.at[sl]).start()

    @pl.when(k == K - 1)
    def _():
        put(o_pe, pe_out, osems_pe)
        put(o_phi, phi_out, osems_phi)

    @pl.when((k < K - 1) & (k % 2 == 0))
    def _():
        put(o_pe, pb0, osems_pe)
        put(o_phi, fb0, osems_phi)

    @pl.when((k < K - 1) & (k % 2 == 1))
    def _():
        put(o_pe, pb1, osems_pe)
        put(o_phi, fb1, osems_phi)

    # Final drain: the last out DMAs of each field have no successor.
    @pl.when((k == K - 1) & (i == nb - 1))
    def _():
        for o2, osems in ((o_pe, osems_pe), (o_phi, osems_phi)):
            pltpu.make_async_copy(o2.at[1 - sl], o2.at[1 - sl],
                                  osems.at[1 - sl]).wait()
            pltpu.make_async_copy(o2.at[sl], o2.at[sl], osems.at[sl]).wait()


def fused_hm3d_megasteps(Pe, phi, *, n_inner: int, bx: int, **kw_core):
    """Advance `n_inner` self-wrap HM3D steps in ONE pallas_call.  The
    input buffers are donated to the results (the k=0 reads all happen
    before any write lands in them; `n_inner >= 2` gated in
    `hm3d_mega_supported`)."""
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s = Pe.shape
    S0, S1, S2 = s
    nb = S0 // bx
    kern = partial(_kernel, K=n_inner, bx=bx, nb=nb, S0=S0, S1=S1, S2=S2,
                   kw_core=kw_core)

    vmas = [getattr(getattr(x, "aval", None), "vma", None)
            for x in (Pe, phi)]
    vma = frozenset().union(*[v for v in vmas if v])

    def shp():
        return (jax.ShapeDtypeStruct(s, Pe.dtype, vma=vma) if vma
                else jax.ShapeDtypeStruct(s, Pe.dtype))

    pe_out, phi_out, *_ = pl.pallas_call(
        kern,
        name="hm3d_mega",
        grid=(n_inner, nb),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 6,
        out_shape=[shp()] * 6,
        input_output_aliases={0: 0, 1: 1},
        scratch_shapes=[
            pltpu.VMEM((2, bx + 2, S1, S2), Pe.dtype),    # ext_pe
            pltpu.VMEM((2, bx + 2, S1, S2), Pe.dtype),    # ext_phi
            pltpu.VMEM((2, bx, S1, S2), Pe.dtype),        # o_pe
            pltpu.VMEM((2, bx, S1, S2), Pe.dtype),        # o_phi
            pltpu.VMEM((8, S1, S2), Pe.dtype),            # xfl_pe
            pltpu.VMEM((8, S1, S2), Pe.dtype),            # xfl_phi
            pltpu.SemaphoreType.DMA((2,)),                # esems_pe
            pltpu.SemaphoreType.DMA((2,)),                # esems_phi
            pltpu.SemaphoreType.DMA((2,)),                # osems_pe
            pltpu.SemaphoreType.DMA((2,)),                # osems_phi
            pltpu.SemaphoreType.DMA((4,)),                # xsems
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=128 * 1024 * 1024,
            dimension_semantics=("arbitrary", "arbitrary")),
    )(Pe, phi)
    return pe_out, phi_out
