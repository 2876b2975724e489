"""K-step fused diffusion mega-kernel (single-device grids).

One `pallas_call` advances the ENTIRE inner time loop: grid `(K, nb)` with
sequential ("arbitrary") semantics, manual HBM<->VMEM DMA, and three
structural wins over one-kernel-per-step:

  1. **VMEM-resident coefficient** — `A = dt*lam/Cp` is DMA'd into a VMEM
     scratch once and read from on-chip memory for all K steps, removing a
     full-array HBM read per step (custom-call boundaries otherwise force
     every operand back to HBM each step).  When A does not fit (local
     blocks beyond ~300^3 f32 — the 512^3 headline case), the kernel
     STREAMS it instead: per-program A slabs ride the same double-buffered
     prefetch pipeline as the T slabs (round 5).  The streaming trade is
     +A bytes of HBM read per step — unavoidable at that size, and
     exactly what the per-step kernel pays too, while keeping wins 2/3.
  2. **HBM ping-pong** — T alternates between two HBM scratch buffers
     (extra ANY-space outputs); no XLA-level copy between steps.
  3. **Hand double-buffering** — each program consumes an extended x-slab
     prefetched by its predecessor and writes its output slab back
     asynchronously, with statically-balanced semaphore waits (every DMA
     start is paired with exactly one wait: slot reuse two programs later,
     plus a drain at each step boundary so the ping-pong source is fully
     written before it is read, plus a final drain).

Halo maintenance is per-dimension (round 5 generalized the original
all-self-wrap form to the open single-device modes, so the reference's
published headline workload — open boundaries — runs here too):

  - ``"wrap"`` (periodic single device, the reference's self-neighbor
    path `/root/reference/src/update_halo.jl:516-532`): y/z halos are
    VMEM aliases of the updated interior; the two x halo planes are
    computed by the first program of each step from 3-plane x-end slabs
    of the current source buffer.
  - ``"frozen"`` (open single device, the reference's no-write halo
    semantics `/root/reference/test/test_update_halo.jl:727-732`): halo
    rows are copied through from the step's SOURCE buffer — frozen rows
    never change, and the copy-through reproduces the per-step path's
    leave-them-alone behavior bit-for-bit at zero extra HBM traffic (the
    source rows are already in the fetched slabs).

`bench.py` on one TPU v5e at 256^3 f32 (K=100, bx=8) measured 0.2407
ms/step (PR 21).  Against the ACTUAL per-step HBM traffic
`T*(1+2/bx) + T_out + A/K` ≈ 151 MB that is 76.6% of the chip's 819 GB/s
HBM peak.  The "~840 GB/s" *equivalent ideal-fusion throughput* (what a
kernel touching only `read T + Cp, write T` would need) is a speedup
proxy, NOT a physical bandwidth, and it exceeds peak precisely because
the mega-kernel eliminates the Cp read.  Matches the per-step kernel
path to 1 ulp.

Not available in interpret mode (manual TPU DMA/semaphores); callers fall
back to the per-step kernel.
"""

from __future__ import annotations

from functools import partial

# VMEM headroom for the resident coefficient + double buffers (the v5e has
# 128MB; leave slack for Mosaic's own allocations).
_VMEM_BUDGET = 110 * 1024 * 1024


def _working_vmem(shape, bx, itemsize, resident: bool) -> int:
    S0, S1, S2 = shape
    return itemsize * (
        (S0 * S1 * S2 if resident else (2 * bx + 2) * S1 * S2)  # A
        + 2 * (bx + 2) * S1 * S2   # ext slabs (double-buffered)
        + 2 * bx * S1 * S2         # out slabs (double-buffered)
        + 8 * S1 * S2)             # x-plane scratch


def resident_a_fits(shape, bx: int, dtype) -> bool:
    """Whether the coefficient array can stay VMEM-resident for the whole
    loop (the fastest mode; ~<=300^3 f32 locals)."""
    import numpy as np

    return (_working_vmem(shape, bx, np.dtype(dtype).itemsize, True)
            <= _VMEM_BUDGET)


def mega_supported(shape, bx: int, n_inner: int, interpret: bool,
                   dtype) -> bool:
    """Whether the K-step mega-kernel applies to a local block of `shape`:
    compiled mode only, at least two steps (with one step, the donated
    input buffer doubles as the output and the last program's wrapping
    fetch would read a row already overwritten), and the working buffers —
    sized at the ACTUAL element width, with the coefficient resident when
    it fits and streamed otherwise — must fit in VMEM (a hard-coded 4
    would under-estimate wider dtypes and fail at Mosaic compile time
    instead of falling back to the per-step kernel)."""
    import numpy as np

    if interpret or n_inner < 2:
        return False
    S0, S1, S2 = shape
    if S0 % bx != 0:  # nb = S0 // bx must cover every row
        return False
    if S0 < 2 * bx:  # the wrapping edge fetches assume >= 2 slabs per step
        return False
    if S2 % 128 != 0 or S1 % 8 != 0:
        # Mosaic requires tile-aligned VMEM memref slices: the double-
        # buffered scratch (2, ..., S1, S2) is sliced on its leading dim,
        # which needs the trailing (sublane, lane) extents tile-aligned.
        return False
    itemsize = np.dtype(dtype).itemsize
    return _working_vmem(shape, bx, itemsize, False) <= _VMEM_BUDGET


# Shared with the per-step kernel: the 1-ulp equality contract between the
# two paths (tests/test_mega_tpu.py) depends on literally the same stencil.
from .diffusion_pallas import _u_rows  # noqa: E402


def _kernel(T_hbm, A_hbm, out_ref, buf0, buf1, *scratch,
            K, bx, nb, S0, S1, S2, rdx2, rdy2, rdz2, resident, modes):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    it = iter(scratch)
    if resident:
        a_vmem = next(it)
        a2 = asems2 = axr = axsem = None
    else:
        a_vmem = None
        a2, asems2, axr, axsem = next(it), next(it), next(it), next(it)
    ext2, o2, xfl, esems, osems, xsems = (next(it) for _ in range(6))
    asem = next(it) if resident else None

    k = pl.program_id(0)
    i = pl.program_id(1)
    scal = (rdx2, rdy2, rdz2)
    sl = i % 2              # this program's ext/out slot

    if resident:
        # One-time: coefficient array into VMEM.
        @pl.when((k == 0) & (i == 0))
        def _():
            dma = pltpu.make_async_copy(A_hbm, a_vmem, asem)
            dma.start()
            dma.wait()
    else:
        # Streamed coefficient: per-program A slabs on the same
        # edge-sync/interior-prefetch pipeline as the T slabs below
        # (A is step-invariant but the 2-slot buffer forces a re-fetch
        # every step — the documented streaming trade).
        @pl.when((i == 0) | (i == nb - 1))
        def _():
            c = pltpu.make_async_copy(A_hbm.at[pl.ds(i * bx, bx)],
                                      a2.at[sl], asems2.at[sl])
            c.start()
            c.wait()

        # Prefetch the NEXT program's A slab — targets slabs 1..nb-2 only
        # (edge programs fetch their own synchronously above), the same
        # window convention as the ext-slab pipeline's `prefetch_next`.
        @pl.when((i >= 0) & (i <= nb - 3))
        def _():
            pltpu.make_async_copy(A_hbm.at[pl.ds((i + 1) * bx, bx)],
                                  a2.at[1 - sl], asems2.at[1 - sl]).start()

        @pl.when((i > 0) & (i < nb - 1))
        def _():
            pltpu.make_async_copy(a2.at[sl], a2.at[sl],
                                  asems2.at[sl]).wait()

        if modes[0] == "wrap":
            # The wrap-x halo planes need A rows S0-2 and 1 (fetched once:
            # A never changes).
            @pl.when((k == 0) & (i == 0))
            def _():
                c0 = pltpu.make_async_copy(A_hbm.at[S0 - 2:S0 - 1],
                                           axr.at[0:1], axsem.at[0])
                c1 = pltpu.make_async_copy(A_hbm.at[1:2], axr.at[1:2],
                                           axsem.at[1])
                c0.start(); c1.start(); c0.wait(); c1.wait()

    # Out-write bookkeeping: drain everything outstanding at each step
    # boundary (the ping-pong source must be fully written before any read
    # of step k), and otherwise wait the DMA whose slot this program reuses.
    @pl.when((i == 0) & (k > 0))
    def _():
        pltpu.make_async_copy(o2.at[0], o2.at[0], osems.at[0]).wait()
        pltpu.make_async_copy(o2.at[1], o2.at[1], osems.at[1]).wait()

    @pl.when(i >= 2)
    def _():
        pltpu.make_async_copy(o2.at[sl], o2.at[sl], osems.at[sl]).wait()

    # Extended-slab fetches (rows [i*bx-1, i*bx+bx+1) mod S0).  Edge
    # programs fetch their own wrapping segments synchronously; interior
    # programs consume the prefetch issued by their predecessor and issue
    # the next one.
    def sync_fetch(src):
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

    def prefetch_next(src):
        # Prefetch the NEXT program's slab — targets slabs 1..nb-2 only
        # (edge programs fetch their own wrapping segments synchronously).
        @pl.when((i >= 0) & (i <= nb - 3))
        def _():
            pltpu.make_async_copy(
                src.at[pl.ds((i + 1) * bx - 1, bx + 2)],
                ext2.at[1 - sl], esems.at[1 - sl]).start()

    def fetch_xplanes(src):
        # Dedicated semaphores: these waits must not consume the prefetch
        # signal pending on esems for the next program.
        c0 = pltpu.make_async_copy(src.at[S0 - 3:S0], xfl.at[0:3],
                                   xsems.at[0])
        c1 = pltpu.make_async_copy(src.at[0:3], xfl.at[3:6], xsems.at[1])
        c0.start(); c1.start(); c0.wait(); c1.wait()

    for cond, src in ((k == 0, T_hbm),
                      ((k > 0) & (k % 2 == 1), buf0),
                      ((k > 0) & (k % 2 == 0), buf1)):
        @pl.when(cond)
        def _(src=src):
            sync_fetch(src)

            @pl.when(i == 0)
            def _():
                fetch_xplanes(src)
            prefetch_next(src)

    # Interior programs: wait for the prefetched slab.
    @pl.when((i > 0) & (i < nb - 1))
    def _():
        pltpu.make_async_copy(ext2.at[sl], ext2.at[sl], esems.at[sl]).wait()

    # x halo planes of this step, computed once per step.  Wrap mode:
    # T_new[0] = U[S0-2], T_new[S0-1] = U[1] stenciled from the x-end
    # slabs; frozen mode: the source edge rows pass through verbatim (a
    # fully-frozen row keeps every cell — even its wrap-dim halo cells
    # only ever copy values from within the same frozen row).  Edge cells
    # of computed planes follow the y/z modes, frozen edges sourced from
    # the plane's own center source row.
    @pl.when(i == 0)
    def _():
        def ywrap_col(col):
            # A frozen-z column with its wrap-y halo cells re-wrapped (the
            # engine's self-alias corner patch: edge row 0 <- inner row
            # S1-2, edge row S1-1 <- inner row 1).
            return jnp.concatenate([col[:, S1 - 2:S1 - 1, :],
                                    col[:, 1:S1 - 1, :],
                                    col[:, 1:2, :]], axis=1)

        def edge_yz(U, src):
            # U: (1, S1-2, S2-2) the new interior of an x-halo row; `src`:
            # (1, S1, S2) the source row the plane's frozen-dim edge cells
            # carry (the engine's corner patching delivers the x-SOURCE
            # row's values there: for a wrap-x plane the stencil center
            # row, for a frozen-x plane the row itself).  Wrap edges copy
            # from the new row's own interior; frozen-z corner cells under
            # wrap-y additionally re-wrap (the y self-alias patch runs
            # after the x patch on the pending z plane).
            if modes[1] == "wrap":
                U = jnp.concatenate([U[:, -1:, :], U, U[:, :1, :]], axis=1)
            else:
                U = jnp.concatenate([src[:, 0:1, 1:-1], U,
                                     src[:, S1 - 1:S1, 1:-1]], axis=1)
            if modes[2] == "wrap":
                return jnp.concatenate([U[:, :, -1:], U, U[:, :, :1]],
                                       axis=2)
            zlo = src[:, :, 0:1]
            zhi = src[:, :, S2 - 1:S2]
            if modes[1] == "wrap":
                zlo, zhi = ywrap_col(zlo), ywrap_col(zhi)
            return jnp.concatenate([zlo, U, zhi], axis=2)

        hi = xfl[0:3]
        lo = xfl[3:6]
        if modes[0] == "wrap":
            aS = a_vmem[S0 - 2:S0 - 1] if resident else axr[0:1]
            a1 = a_vmem[1:2] if resident else axr[1:2]
            xfl[6:7] = edge_yz(_u_rows(hi[0:1], hi[1:2], hi[2:3], aS,
                                       *scal), hi[1:2])
            xfl[7:8] = edge_yz(_u_rows(lo[0:1], lo[1:2], lo[2:3], a1,
                                       *scal), lo[1:2])
        else:
            # Frozen x: the source edge rows pass through, with their OWN
            # wrap-dim halo cells re-wrapped (the per-step path's wrap
            # writes copy within the frozen row; a no-op once the state is
            # exchange-fresh, but exact for any input).
            xfl[6:7] = edge_yz(lo[0:1, 1:-1, 1:-1], lo[0:1])
            xfl[7:8] = edge_yz(hi[2:3, 1:-1, 1:-1], hi[2:3])

    # Interior stencil update in x-row bands + per-mode y/z assembly
    # (identical scheme to diffusion_pallas._make_kernel).
    ext = ext2.at[sl]
    o_vmem = o2.at[sl]
    c = ext[1:bx + 1]
    a = a_vmem[pl.ds(i * bx, bx)] if resident else a2[sl]
    if bx > 2:
        o_vmem[1:bx - 1, 1:-1, 1:-1] = _u_rows(
            c[0:bx - 2], c[1:bx - 1], c[2:bx], a[1:bx - 1], *scal)
    o_vmem[0:1, 1:-1, 1:-1] = _u_rows(ext[0:1], c[0:1], c[1:2],
                                      a[0:1], *scal)
    o_vmem[bx - 1:bx, 1:-1, 1:-1] = _u_rows(
        c[bx - 2:bx - 1], c[bx - 1:bx], ext[bx + 1:bx + 2],
        a[bx - 1:bx], *scal)
    if modes[1] == "wrap":
        o_vmem[:, 0:1, 1:-1] = o_vmem[:, S1 - 2:S1 - 1, 1:-1]
        o_vmem[:, S1 - 1:S1, 1:-1] = o_vmem[:, 1:2, 1:-1]
    else:
        o_vmem[:, 0:1, 1:-1] = c[:, 0:1, 1:-1]
        o_vmem[:, S1 - 1:S1, 1:-1] = c[:, S1 - 1:S1, 1:-1]
    if modes[2] == "wrap":
        o_vmem[:, :, 0:1] = o_vmem[:, :, S2 - 2:S2 - 1]
        o_vmem[:, :, S2 - 1:S2] = o_vmem[:, :, 1:2]
    else:
        o_vmem[:, :, 0:1] = c[:, :, 0:1]
        o_vmem[:, :, S2 - 1:S2] = c[:, :, S2 - 1:S2]
        if modes[1] == "wrap":
            # Corner cells of the frozen z columns under wrap-y: the
            # engine's y self-alias patch rewraps the pending z plane's
            # y-edge rows (edge 0 <- inner S1-2, edge S1-1 <- inner 1).
            for zc in (slice(0, 1), slice(S2 - 1, S2)):
                o_vmem[:, 0:1, zc] = c[:, S1 - 2:S1 - 1, zc]
                o_vmem[:, S1 - 1:S1, zc] = c[:, 1:2, zc]

    @pl.when(i == 0)
    def _():
        o_vmem[0:1] = xfl[6:7]

    @pl.when(i == nb - 1)
    def _():
        o_vmem[bx - 1:bx] = xfl[7:8]

    # Async write-back to this step's destination.
    def put(dst):
        pltpu.make_async_copy(o_vmem, dst.at[pl.ds(i * bx, bx)],
                              osems.at[sl]).start()

    @pl.when(k == K - 1)
    def _():
        put(out_ref)

    @pl.when((k < K - 1) & (k % 2 == 0))
    def _():
        put(buf0)

    @pl.when((k < K - 1) & (k % 2 == 1))
    def _():
        put(buf1)

    # Final drain: the last two out DMAs have no successor to wait them.
    @pl.when((k == K - 1) & (i == nb - 1))
    def _():
        pltpu.make_async_copy(o2.at[1 - sl], o2.at[1 - sl],
                              osems.at[1 - sl]).wait()
        pltpu.make_async_copy(o2.at[sl], o2.at[sl], osems.at[sl]).wait()


def fused_diffusion_megasteps(T, A, *, n_inner: int, bx: int,
                              rdx2, rdy2, rdz2,
                              modes=("wrap", "wrap", "wrap"),
                              force_streamed: bool = False):
    """Advance `n_inner` single-device diffusion steps in ONE pallas_call.
    `A = dt*lam/Cp`; `modes` gives each dimension's halo mode ("wrap" for
    a periodic self-neighbor ring, "frozen" for an open boundary — module
    docstring).  The coefficient stays VMEM-resident when it fits and is
    slab-streamed otherwise (`force_streamed` pins streaming, for the
    equivalence tests).  The input T buffer is donated to the result (the
    k=0 reads all happen before any write lands in it)."""
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s = T.shape
    S0, S1, S2 = s
    nb = S0 // bx
    resident = (not force_streamed) and resident_a_fits(s, bx, T.dtype)
    kern = partial(_kernel, K=n_inner, bx=bx, nb=nb, S0=S0, S1=S1, S2=S2,
                   rdx2=rdx2, rdy2=rdy2, rdz2=rdz2, resident=resident,
                   modes=tuple(modes))

    vmas = [getattr(getattr(x, "aval", None), "vma", None) for x in (T, A)]
    vma = frozenset().union(*[v for v in vmas if v])

    def shp():
        return (jax.ShapeDtypeStruct(s, T.dtype, vma=vma) if vma
                else jax.ShapeDtypeStruct(s, T.dtype))

    if resident:
        a_scratch = [pltpu.VMEM(s, T.dtype)]              # a_vmem
    else:
        a_scratch = [
            pltpu.VMEM((2, bx, S1, S2), T.dtype),         # a2
            pltpu.SemaphoreType.DMA((2,)),                # asems2
            pltpu.VMEM((2, S1, S2), T.dtype),             # axr
            pltpu.SemaphoreType.DMA((2,)),                # axsem
        ]
    out, _, _ = pl.pallas_call(
        kern,
        name="diffusion3d_mega",
        grid=(n_inner, nb),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3,
        out_shape=[shp(), shp(), shp()],
        input_output_aliases={0: 0},
        scratch_shapes=a_scratch + [
            pltpu.VMEM((2, bx + 2, S1, S2), T.dtype),     # ext2
            pltpu.VMEM((2, bx, S1, S2), T.dtype),         # o2
            pltpu.VMEM((8, S1, S2), T.dtype),             # xfl
            pltpu.SemaphoreType.DMA((2,)),                # esems
            pltpu.SemaphoreType.DMA((2,)),                # osems
            pltpu.SemaphoreType.DMA((2,)),                # xsems
        ] + ([pltpu.SemaphoreType.DMA] if resident else []),  # asem
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=128 * 1024 * 1024,
            dimension_semantics=("arbitrary", "arbitrary")),
    )(T, A)
    return out
