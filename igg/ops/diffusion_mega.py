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
     +A bytes of HBM read per step — exactly what the per-step kernel
     pays too, while keeping wins 2/3 — and per D steps in the open-x
     wavefront below.
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

The kernel above sweeps HBM once per step.  For the wrap-x kernel,
`bench.py` on one TPU v5e at 256^3 f32 (K=100, bx=8) measured 0.2407
ms/step (PR 21).  Against the ACTUAL per-step HBM traffic
`T*(1+2/bx) + T_out + A/K` ≈ 151 MB that is 76.6% of the chip's 819 GB/s
HBM peak.  The "~840 GB/s" *equivalent ideal-fusion throughput* (what a
kernel touching only `read T + Cp, write T` would need) is a speedup
proxy, NOT a physical bandwidth, and it exceeds peak precisely because
the mega-kernel eliminates the Cp read.  Matches the per-step kernel
path to 1 ulp.  With the coefficient streamed (512^3) a step moves
`T*(1+2/bx) + A + T_out` = 3.25 x 537 MB = 1.745 GB at bx 8: 2.13 ms at
peak, against 2.549 ms measured.

**Open-x wavefront** (`diffusion3d_mega_wavefront`).  Where x is open
(frozen), one HBM sweep advances D steps: grid `(n_inner // D, nb +
ceil(D/bx))`.  Program i fetches level-0 slab i (and its coefficient
rows) and advances each level l = 1..D on rows `[i*bx - l, i*bx + bx -
l)`, one row at a time, from a VMEM ring of the level below (bx new rows
plus a 2-row carry from program i-1); the last ceil(D/bx) programs drain
the skew.  Only level D goes back to HBM, into T's own buffer: those
writes trail the level-0 reads by D rows, and each sweep drains them
before the next one reads.  A sweep moves `T + A + T_out` once, 1.61 GB
at 512^3, so a step moves 1.61/D GB.  The arithmetic per cell is the
kernel above's (`_u_rows`, the same y/z edge assembly, the x edge rows
passed through with their own wrap-dim cells re-wrapped), so the result
is bit-identical to it.  Wrap x keeps the kernel above: its x halo
planes need the far end of the same level, which a one-way sweep does
not have.  The trapezoid chunk (`diffusion_trapezoid`) runs the same
kernel, under its own name, on its extended window: the extended dims'
end rows are shoulders that never reach the central window, so they
pass as frozen.  `wavefront_plan` takes the deepest D (<= 8) whose modeled
footprint fits the budget, preferring a divisor of `n_inner` (a
remainder runs one shorter sweep).  At 512^3 f32 it picks D 5, bx 4 (58
MiB) and ran 0.9256 ms/step against 2.5668 (one TPU v5e); from D 3
to 8 the time is flat at ~0.91 ms/step, bound by the stencil's VPU work
rather than HBM.

Both kernels use manual TPU DMA and semaphores: the library's interpret
mode takes the per-step kernel, and the tests run them on the CPU under
the TPU interpreter (`pltpu.force_tpu_interpret_mode`).
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


# Deepest wavefront the plan considers (steps advanced per HBM sweep).  At
# 512^3 f32 on a v5e the time is flat from D 3 to 8: the stencil's VPU
# work, not HBM, bounds it there.
_MAX_DEPTH = 8


def _wavefront_vmem(shape, D, bx, itemsize, resident: bool) -> int:
    """Modeled VMEM bytes of the wavefront kernel: level 0's three slab
    slots (the 2-row carry's slab, the current slab and the prefetch), a
    ring of bx + 2 rows for each level 1..D (bx new rows plus the 2-row
    carry; level D's ring stages the write-back), and the coefficient,
    resident or a ring of ceil(D/bx) + 2 slabs."""
    S0, S1, S2 = shape
    a_rows = S0 if resident else (-(-D // bx) + 2) * bx
    return itemsize * S1 * S2 * (3 * bx + D * (bx + 2) + a_rows)


def wavefront_plan(shape, n_inner: int, modes, dtype, interpret=False):
    """`(D, bx)` for the wavefront kernel on a local block of `shape`, or 0
    where it does not apply: compiled mode, at least two steps, an open x
    dimension (wrap-x halo planes need the far end of the same level,
    which a one-way sweep does not have), tile-aligned y/z, at least three
    slabs, and a modeled footprint (streamed coefficient) within the VMEM
    budget.  D is the deepest that fits, or the deepest divisor of
    `n_inner` above half of that; bx the largest slab of (4, 2) that fits
    it."""
    import numpy as np

    if interpret or n_inner < 2 or modes[0] != "frozen":
        return 0
    S0, S1, S2 = shape
    if S2 % 128 != 0 or S1 % 8 != 0:
        return 0
    itemsize = np.dtype(dtype).itemsize

    def slab(D):
        return next((bx for bx in (4, 2)
                     if S0 % bx == 0 and S0 >= 3 * bx
                     and _wavefront_vmem(shape, D, bx, itemsize, False)
                     <= _VMEM_BUDGET), 0)

    fits = [D for D in range(2, min(n_inner, _MAX_DEPTH) + 1) if slab(D)]
    if not fits:
        return 0
    divides = [D for D in fits if n_inner % D == 0 and 2 * D > fits[-1]]
    D = (divides or fits)[-1]
    return D, slab(D)


# Shared with the per-step kernel: the 1-ulp equality contract between the
# two paths (tests/test_mega_tpu.py) depends on literally the same stencil.
from .diffusion_pallas import _u_rows  # noqa: E402


def _ywrap_col(col, S1):
    # A frozen-z column with its wrap-y halo cells re-wrapped (the
    # engine's self-alias corner patch: edge row 0 <- inner row S1-2, edge
    # row S1-1 <- inner row 1).
    import jax.numpy as jnp

    return jnp.concatenate([col[:, S1 - 2:S1 - 1, :], col[:, 1:S1 - 1, :],
                            col[:, 1:2, :]], axis=1)


def _edge_yz(U, src, modes, S1, S2):
    """An x-halo row from the new interior `U` (1, S1-2, S2-2) and the
    source row `src` (1, S1, S2) its frozen-dim edge cells carry (the
    engine's corner patching delivers the x-SOURCE row's values there:
    for a wrap-x plane the stencil center row, for a frozen-x plane the
    row itself).  Wrap edges copy from the new row's own interior;
    frozen-z corner cells under wrap-y additionally re-wrap (the y
    self-alias patch runs after the x patch on the pending z plane)."""
    import jax.numpy as jnp

    if modes[1] == "wrap":
        U = jnp.concatenate([U[:, -1:, :], U, U[:, :1, :]], axis=1)
    else:
        U = jnp.concatenate([src[:, 0:1, 1:-1], U,
                             src[:, S1 - 1:S1, 1:-1]], axis=1)
    if modes[2] == "wrap":
        return jnp.concatenate([U[:, :, -1:], U, U[:, :, :1]], axis=2)
    zlo = src[:, :, 0:1]
    zhi = src[:, :, S2 - 1:S2]
    if modes[1] == "wrap":
        zlo, zhi = _ywrap_col(zlo, S1), _ywrap_col(zhi, S1)
    return jnp.concatenate([zlo, U, zhi], axis=2)


def _assemble_yz(o, c, modes, S1, S2):
    """The y/z edge cells of x rows whose interior `o[:, 1:-1, 1:-1]` is
    already written: wrap dims alias the new interior, frozen dims copy
    the source rows `c` (identical scheme to
    diffusion_pallas._make_kernel)."""
    if modes[1] == "wrap":
        o[:, 0:1, 1:-1] = o[:, S1 - 2:S1 - 1, 1:-1]
        o[:, S1 - 1:S1, 1:-1] = o[:, 1:2, 1:-1]
    else:
        o[:, 0:1, 1:-1] = c[:, 0:1, 1:-1]
        o[:, S1 - 1:S1, 1:-1] = c[:, S1 - 1:S1, 1:-1]
    if modes[2] == "wrap":
        o[:, :, 0:1] = o[:, :, S2 - 2:S2 - 1]
        o[:, :, S2 - 1:S2] = o[:, :, 1:2]
    else:
        o[:, :, 0:1] = c[:, :, 0:1]
        o[:, :, S2 - 1:S2] = c[:, :, S2 - 1:S2]
        if modes[1] == "wrap":
            # Corner cells of the frozen z columns under wrap-y: the
            # engine's y self-alias patch rewraps the pending z plane's
            # y-edge rows (edge 0 <- inner S1-2, edge S1-1 <- inner 1).
            for zc in (slice(0, 1), slice(S2 - 1, S2)):
                o[:, 0:1, zc] = c[:, S1 - 2:S1 - 1, zc]
                o[:, S1 - 1:S1, zc] = c[:, 1:2, zc]


def _kernel(T_hbm, A_hbm, out_ref, buf0, buf1, *scratch,
            K, bx, nb, S0, S1, S2, rdx2, rdy2, rdz2, resident, modes):
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
        def edge_yz(U, src):
            return _edge_yz(U, src, modes, S1, S2)

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
    _assemble_yz(o_vmem, c, modes, S1, S2)

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


def _wavefront_kernel(T_hbm, A_hbm, out_ref, ring, tsems, osems, *a_scratch,
                      D, bx, nb, S0, S1, S2, rdx2, rdy2, rdz2, resident,
                      modes):
    """One program of a D-step sweep (module docstring).  Program i
    advances level l = 1..D on rows [i*bx - l, i*bx + bx - l), one row at
    a time; `ring` holds level 0's three slab slots, then a bx + 2 row
    ring per level (row r of level l at `base(l) + r mod size(l)`)."""
    from jax import lax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    del T_hbm   # aliased to out_ref: every sweep reads and writes out_ref
    i = pl.program_id(1)
    scal = (rdx2, rdy2, rdz2)
    R = bx + 2          # ring rows of levels 1..D
    L0 = 3 * bx         # level-0 slab slots
    if resident:
        a_vmem, asem = a_scratch
    else:
        aring, asems = a_scratch
        nA = -(-D // bx) + 2

    def t_copy(j):
        return pltpu.make_async_copy(out_ref.at[pl.ds(j * bx, bx)],
                                     ring.at[pl.ds((j % 3) * bx, bx)],
                                     tsems.at[j % 3])

    def a_copy(j):
        return pltpu.make_async_copy(A_hbm.at[pl.ds(j * bx, bx)],
                                     aring.at[pl.ds((j % nA) * bx, bx)],
                                     asems.at[j % nA])

    def write(p, r=0):
        # Level D's ring row p to HBM row r (r only sizes a wait).
        return pltpu.make_async_copy(
            ring.at[pl.ds(L0 + (D - 1) * R + p, 1)],
            out_ref.at[pl.ds(r, 1)], osems.at[p])

    if resident:
        @pl.when((pl.program_id(0) == 0) & (i == 0))
        def _():
            c = pltpu.make_async_copy(A_hbm, a_vmem, asem)
            c.start()
            c.wait()
    copies = (t_copy,) if resident else (t_copy, a_copy)

    # Level-0 slabs: slab i is consumed by program i and prefetched by its
    # predecessor (the first program fetches its own).  Every start is
    # waited exactly once.
    @pl.when(i == 0)
    def _():
        for f in copies:
            f(0).start()

    @pl.when(i + 1 < nb)
    def _():
        for f in copies:
            f(i + 1).start()

    @pl.when(i < nb)
    def _():
        for f in copies:
            f(i).wait()

    def a_row(r):
        if resident:
            return a_vmem[pl.ds(r, 1)]
        return aring[pl.ds(lax.rem(r, nA * bx), 1)]

    def level_row(k, carry):
        l = k // bx + 1
        r = i * bx - l + k % bx

        @pl.when((r >= 0) & (r < S0))
        def _():
            base = jnp.where(l == 1, 0, L0 + (l - 2) * R)
            size = jnp.where(l == 1, L0, R)

            def src(q):
                return ring[pl.ds(base + lax.rem(q, size), 1)]

            p = lax.rem(r, R)
            dst = ring.at[pl.ds(L0 + (l - 1) * R + p, 1)]
            last = l == D

            # Level D reuses a ring row once its write-back has landed.
            @pl.when(last & (r >= R))
            def _():
                write(p).wait()

            # Frozen x: the edge rows pass through, their own wrap-dim
            # halo cells re-wrapped (today's frozen-x planes).
            @pl.when((r == 0) | (r == S0 - 1))
            def _():
                v = src(r)
                dst[...] = _edge_yz(v[:, 1:-1, 1:-1], v, modes, S1, S2)

            @pl.when((r > 0) & (r < S0 - 1))
            def _():
                c = src(r)
                dst[:, 1:-1, 1:-1] = _u_rows(src(r - 1), c, src(r + 1),
                                             a_row(r), *scal)
                _assemble_yz(dst, c, modes, S1, S2)

            @pl.when(last)
            def _():
                write(p, r).start()

        return carry

    lax.fori_loop(0, D * bx, level_row, 0)

    # Sweep end: drain the write-backs (the next sweep reads what they
    # write; the last R rows' writes have no successor to wait them).
    @pl.when(i == pl.num_programs(1) - 1)
    def _():
        for p in range(R):
            write(p).wait()


def _out_struct(T, A):
    """T's shape and dtype as a kernel result; under shard_map with
    varying-mesh-axes checking it must carry the operands' axes."""
    import jax

    vmas = [getattr(getattr(x, "aval", None), "vma", None) for x in (T, A)]
    vma = frozenset().union(*[v for v in vmas if v])
    return (jax.ShapeDtypeStruct(T.shape, T.dtype, vma=vma) if vma
            else jax.ShapeDtypeStruct(T.shape, T.dtype))


def _wavefront_steps(T, A, *, sweeps, D, bx, rdx2, rdy2, rdz2, modes,
                     force_streamed, name="diffusion3d_mega_wavefront"):
    """`sweeps` x D steps, in place in T's buffer (writes trail the
    level-0 reads by D rows).  `name` names the kernel in the compiled
    program and in traces (the trapezoid chunk passes its own)."""
    import numpy as np
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s = T.shape
    S0, S1, S2 = s
    nb = S0 // bx
    # A resident coefficient is loaded whole before program 0 and spares
    # the re-read of every later sweep; a single sweep reads it once
    # either way, and the streamed ring hides that read behind the
    # prefetch (0.095 ms less per call at 272 x 272 x 256, D 8, one v5e).
    resident = (not force_streamed) and sweeps > 1 and _wavefront_vmem(
        s, D, bx, np.dtype(T.dtype).itemsize, True) <= _VMEM_BUDGET
    kern = partial(_wavefront_kernel, D=D, bx=bx, nb=nb, S0=S0, S1=S1,
                   S2=S2, rdx2=rdx2, rdy2=rdy2, rdz2=rdz2,
                   resident=resident, modes=tuple(modes))
    if resident:
        a_scratch = [pltpu.VMEM(s, T.dtype), pltpu.SemaphoreType.DMA]
    else:
        nA = -(-D // bx) + 2
        a_scratch = [pltpu.VMEM((nA * bx, S1, S2), T.dtype),
                     pltpu.SemaphoreType.DMA((nA,))]
    return pl.pallas_call(
        kern,
        name=name,
        grid=(sweeps, nb + -(-D // bx)),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=_out_struct(T, A),
        input_output_aliases={0: 0},
        scratch_shapes=[
            pltpu.VMEM((3 * bx + D * (bx + 2), S1, S2), T.dtype),  # ring
            pltpu.SemaphoreType.DMA((3,)),                         # tsems
            pltpu.SemaphoreType.DMA((bx + 2,)),                    # osems
        ] + a_scratch,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=128 * 1024 * 1024,
            dimension_semantics=("arbitrary", "arbitrary")),
    )(T, A)


def fused_diffusion_megasteps(T, A, *, n_inner: int, bx: int,
                              rdx2, rdy2, rdz2,
                              modes=("wrap", "wrap", "wrap"),
                              force_streamed: bool = False,
                              _per_step_sweep: bool = False):
    """Advance `n_inner` single-device diffusion steps in ONE pallas_call
    (two for an open-x wavefront whose depth leaves a remainder).
    `A = dt*lam/Cp`; `modes` gives each dimension's halo mode ("wrap" for
    a periodic self-neighbor ring, "frozen" for an open boundary — module
    docstring).  Open x runs the wavefront kernel where `wavefront_plan`
    admits it (`_per_step_sweep` pins the one-step-per-sweep kernel, for
    the equivalence tests).  The coefficient stays VMEM-resident when it
    fits (for the wavefront, when the call also runs more than one sweep)
    and is slab-streamed otherwise (`force_streamed` pins streaming, for
    the equivalence tests).  The input T buffer is donated to the
    result (the k=0 reads all happen before any write lands in it)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    plan = (not _per_step_sweep) and wavefront_plan(T.shape, n_inner, modes,
                                                    T.dtype)
    if plan:
        D, wbx = plan
        run = partial(_wavefront_steps, bx=wbx, rdx2=rdx2, rdy2=rdy2,
                      rdz2=rdz2, modes=modes, force_streamed=force_streamed)
        T = run(T, A, sweeps=n_inner // D, D=D)
        if n_inner % D:
            T = run(T, A, sweeps=1, D=n_inner % D)
        return T

    s = T.shape
    S0, S1, S2 = s
    nb = S0 // bx
    resident = (not force_streamed) and resident_a_fits(s, bx, T.dtype)
    kern = partial(_kernel, K=n_inner, bx=bx, nb=nb, S0=S0, S1=S1, S2=S2,
                   rdx2=rdx2, rdy2=rdy2, rdz2=rdz2, resident=resident,
                   modes=tuple(modes))

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
        out_shape=[_out_struct(T, A)] * 3,
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
