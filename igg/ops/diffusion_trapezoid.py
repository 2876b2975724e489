"""K-step trapezoidal (halo-deep) diffusion kernel for exchanged meshes.

The mega-kernel (`diffusion_mega`) fuses the whole inner time loop into one
`pallas_call`, but only where every dimension self-wraps on one device.  On
the practical pod decompositions — `(N,1,1)` and `(N,M,1)` with the grid
split over the ring/torus — each step needs fresh halo planes from the
neighbors, so the per-step kernel re-pays the kernel-boundary HBM
round-trip and a collective per step
(`/root/reference/src/update_halo.jl`'s per-step exchange, likewise).

This module restores K-step fusion there with classic *trapezoidal temporal
blocking* over the exchanged dimension(s) — up to the full `(N,M,K)` 3-D
torus, the v5p BASELINE topology:

  1. Once per K-step chunk, each device receives the K rows beyond each end
     of its block along every exchanged dimension (ONE `ppermute` pair per
     dim moving K-deep slabs — 1/K of the per-step collective count at the
     same total bytes) and forms the extended buffer — a contiguous window
     of the global array.  The extensions are built dimension-sequentially:
     the y slabs are cut from the x-EXTENDED buffer and the z slabs from
     the x/y-extended buffer, so corner/edge regions arrive via the later
     neighbors' own earlier-dim extensions (the same sequential-exchange
     trick the halo engine uses for corner propagation,
     `/root/reference/src/update_halo.jl:36,130`).  z slabs ride the wire
     TRANSPOSED (z on the sublane axis) so nothing lane-padded
     materializes.
  2. ONE `pallas_call` advances K steps on the extended window.  Where no
     dim is "oext" and `diffusion_mega.wavefront_plan` admits the window,
     it is the mega kernel's wavefront (`diffusion3d_trapezoid_wavefront`):
     one HBM sweep advances D steps (D = K = 8 on the 2x2 mesh's 272 x 272
     x 256 window), in place in the window's buffer.  An extended dim's
     end rows are shoulders whose values never reach the central window,
     so the wavefront holds them fixed (its "frozen" mode); wrap and
     frozen dims keep their modes.  Otherwise it is the one-step-per-sweep
     kernel below (`diffusion3d_trapezoid_chunk`: VMEM-resident
     coefficient, HBM ping-pong, hand double-buffered DMA, in-VMEM
     self-wrap aliases, per-device freeze planes for open mesh edges).
     Each step the outermost rows of every extended dimension lose
     validity — after K steps exactly the device's own block (interior AND
     halo rows) carries the values the per-step path would produce,
     bit-for-bit, because every row is updated by the identical stencil
     arithmetic the neighbor would apply; the two kernels differ only in
     the order of the rows.
  3. The central window is sliced out of the evolved window (the wavefront)
     or written alone by the last step's programs (the one-step kernel,
     whose garbage shoulders stay in its ping-pong scratch).

Per-chunk overhead vs the ideal: the concat (one extended-buffer write), the
central-window slice, and the redundant shoulder compute (`2K/S` per
extended dim) — all amortized by K.  The wavefront reads and writes the
window once a chunk (the one-step kernel once a step: 170 MB a step at
272 x 272 x 256 f32).

Validity needs fresh data beyond each extended end.  Periodic rings
provide it by neighbor slabs (`periods[d]`, any `dims[d] >= 1` — on one
device a ring is the self-neighbor ppermute, handled by the in-kernel
wrap).  OPEN boundaries provide it by freezing: a no-write boundary row
(`/root/reference/test/test_update_halo.jl:727-732`) is genuinely local —
global-edge devices re-freeze their boundary plane from the chunk-entry
buffer every step (uniform SPMD shapes, `axis_index` edge flags), which
both preserves the frozen rows bit-for-bit and quarantines the
beyond-domain shoulder garbage, so the validity front never shrinks from
an open side.  The open modes (`_dim_modes`: "oext"/"frozen") run in BOTH
realizations (round 6 — the reference's examples default to non-periodic,
so this is its default boundary condition on the compiled tier):

  - the pure-XLA window path freezes the boundary plane AND the
    beyond-domain shoulder planes from the chunk-entry buffer
    (`_window_steps_xla`), pinned per-step-equivalent on open and mixed
    8-device meshes by `tests/test_trapezoid.py::test_open_*`;
  - the Mosaic chunk kernel re-freezes exactly the boundary plane per
    open side per step, from freeze planes held VMEM-resident for the
    whole chunk, gated by per-device `axis_index` edge flags in SMEM
    (the same no-write semantics the open mega-kernel modes realize,
    `diffusion_mega` "frozen").  Freezing the single boundary plane
    suffices for central-window equality with the window realization:
    influence from the shoulder planes can only reach the central window
    THROUGH the frozen plane, which never reads its neighbors — so the
    two realizations (and the per-step path) agree bit-for-bit on the
    block, and the evolving shoulder garbage is quarantined exactly as
    the window's explicit shoulder freeze quarantines it.

The compiled dispatcher admits the open modes with
`trapezoid_supported(..., allow_open=True)`; the dispatcher in
`fused_diffusion_steps` also runs one per-step kernel step BEFORE the
chunks, which consumes never-exchanged entry halos exactly like every
other path (bit-equivalence for ANY input).

In interpret mode (CPU meshes, the driver dryrun) the chunk runs as a
pure-XLA realization of the same window dynamics (`_window_steps_xla`) —
the chunked exchange, corner-carrying extensions, and shrinking validity
are exercised everywhere; only the manual-DMA kernel itself is TPU-only.

Round 16: the family-independent machinery — per-dim window modes, edge
flags, the slab-permute extension, the open-dim freeze masks, the chunk
driver, the VMEM budget authority — moved to the shared K-step chunk
engine (`igg.ops.chunk_engine`, `igg.ops._vmem`); this module keeps the
diffusion physics, the HBM-streaming ping-pong Mosaic kernel (unique to
blocks that exceed VMEM), and its admission accounting.  The historical
underscore names (`_dim_modes`, `_edge_flags`, `_extend_dim`, `_extend`)
remain importable as engine aliases.
"""

from __future__ import annotations

from functools import partial

from ._vmem import chunk_budget
from .chunk_engine import (admit_chunk_common, central_window, dim_modes,
                           edge_flags, ext_shape, extend_dim_grouped,
                           extend_fields, freeze_open_dim, pad128,
                           run_chunks, wrap_edges)
from .diffusion_mega import _wavefront_steps, wavefront_plan
from .diffusion_pallas import _u_rows

# Engine aliases (the historical private names, still used by tests and
# benchmarks; the implementations live in `igg.ops.chunk_engine`).
_dim_modes = dim_modes
_edge_flags = edge_flags


def _extend_dim(T, K, ol, grid, d, mode: str = "ext"):
    """One field's `size + 2K` window along dim `d` — the single-field
    form of the engine's grouped slab extension (one ppermute pair of
    `(K+1)`-row slabs; see `chunk_engine.extend_dim_grouped`)."""
    return extend_dim_grouped([T], [ol], K, grid, d, mode)[0]


def _extend(T, K, grid, shape, modes):
    """Dimension-sequential extension of one field (x, then y OF the
    x-extended buffer, then z — the sequential-exchange corner trick);
    wrap/frozen dims are not extended."""
    ols = [tuple(grid.ol_of_local(d, shape) for d in range(3))]
    return extend_fields([T], ols, K, grid, modes)[0]


def trapezoid_supported(grid, shape, bx: int, n_inner: int, dtype,
                        force_y_ext=None, force_z_ext=None,
                        allow_open: bool = False):
    """Whether the K=bx trapezoidal chunk path applies: periodic rings
    along every dimension (self-wrap or extended), at least one full
    chunk, the K-slab sends must lie inside the block, and the extended
    coefficient plus working buffers must fit in VMEM (the interpret-mode
    XLA fallback obeys the same gates so both modes take the same
    route).  Returns an :class:`igg.degrade.Admission` (truthy/falsy)
    carrying the structured refusal reason.

    `allow_open=True` additionally admits open (non-periodic) dimensions
    — the "oext"/"frozen" modes of `_dim_modes`, realized by BOTH the
    Mosaic chunk kernel (per-device edge-freeze planes + `axis_index`
    flags) and the pure-XLA window path; the compiled dispatcher passes
    it, serving the reference-default boundary condition on the K-step
    tier.  The default stays False so direct callers opt in explicitly."""
    import numpy as np

    from ..degrade import Admission
    from .chunk_engine import admit_chunk_common

    common = admit_chunk_common(grid, bx, n_inner)
    if common is not None:
        return common
    modes = _dim_modes(grid, force_y_ext, force_z_ext)
    if not allow_open and any(m in ("oext", "frozen") for m in modes):
        return Admission.no(f"open (non-periodic) dimensions {modes} and "
                            f"the caller did not pass allow_open=True")
    y_ext = modes[1] in ("ext", "oext")
    z_ext = modes[2] in ("ext", "oext")
    S0, S1, S2 = shape
    K = bx
    olx = grid.ol_of_local(0, shape)
    if olx < 2 or S0 % bx != 0:
        return Admission.no(f"x extent {S0} (overlap {olx}) not chunkable "
                            f"at K={bx} (needs ol >= 2, S0 % K == 0)")
    if modes[0] != "frozen" and (S0 - olx - K < 0 or olx + K > S0):
        # x send slabs inside the block (no slabs in frozen mode)
        return Admission.no(f"K={K} x send slabs fall outside the local "
                            f"block (S0={S0}, ol={olx})")
    if modes[0] == "frozen" and S0 // bx < 2:
        # The kernel's edge programs fetch their own clamped segments;
        # with one program both edge branches would collide on one slot.
        return Admission.no(f"frozen-x block needs >= 2 band programs "
                            f"(S0={S0}, K={bx})")
    if S1 % 8 != 0:
        # Mosaic requires tile-aligned VMEM memref slices of the double-
        # buffered scratch; sublane extent must be 8-aligned (f32).
        return Admission.no(f"y extent {S1} not a multiple of 8 (Mosaic "
                            f"sublane tile)")
    if not z_ext and S2 % 128 != 0:
        # Ditto for the lane extent; in z-extended mode the kernel
        # right-pads the extended extent to a 128 multiple instead.
        return Admission.no(f"z extent {S2} not a multiple of 128 (Mosaic "
                            f"lane tile; z not extended)")
    S1e, S2e = S1, S2
    if y_ext:
        oly = grid.ol_of_local(1, shape)
        # 8-aligned K keeps the extended span and the caller's central-
        # window XLA slice on sublane-tile boundaries (S1 alignment is
        # gated unconditionally above); the y send slabs must lie inside
        # the block.
        if oly < 2 or K % 8 != 0:
            return Admission.no(f"y-extended chunk needs ol >= 2 and "
                                f"K % 8 == 0 (ol={oly}, K={K})")
        if S1 - oly - K < 0 or oly + K > S1:
            return Admission.no(f"K={K} y send slabs fall outside the "
                                f"local block (S1={S1}, ol={oly})")
        S1e = S1 + 2 * K
    if z_ext:
        olz = grid.ol_of_local(2, shape)
        # No S2 alignment requirement on the caller: the extension slabs
        # ride the wire TRANSPOSED (z on the sublane axis) so nothing
        # lane-padded materializes, and the compiled kernel right-pads the
        # extended lane extent to a 128 multiple (Mosaic requires aligned
        # VMEM lane slices); the K-offset central z slice is a relayout
        # pass amortized 1/K per step.
        if olz < 2:
            return Admission.no(f"z-extended chunk needs overlap >= 2 "
                                f"(ol={olz})")
        if S2 - olz - K < 0 or olz + K > S2:
            return Admission.no(f"K={K} z send slabs fall outside the "
                                f"local block (S2={S2}, ol={olz})")
        S2e = ((S2 + 2 * K + 127) // 128) * 128
    S0e = S0 + (2 * K if modes[0] != "frozen" else 0)
    itemsize = np.dtype(dtype).itemsize
    need = itemsize * (S0e * S1e * S2e            # A_ext resident
                       + 2 * (bx + 2) * S1e * S2e   # ext slabs (dbl-buffered)
                       + 2 * bx * S1e * S2e)        # out slabs (dbl-buffered)
    # Open dims keep their two freeze planes VMEM-resident for the chunk.
    for d, plane in ((0, S1e * S2e), (1, S0e * S2e), (2, S0e * S1e)):
        if modes[d] in ("oext", "frozen"):
            need += 2 * itemsize * plane
    if need > chunk_budget():
        return Admission.no(f"resident working set {need} bytes exceeds "
                            f"the VMEM budget {chunk_budget()}")
    return Admission.yes()


def _kernel(*refs, K, bx, nbe, nbo, off, S0e, S1e, S2, modes, frz,
            rdx2, rdy2, rdz2):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    # Variadic unpacking (`frz` is the static tuple of (dim, lo, hi)
    # freeze-plane indices — empty on fully-periodic grids, whose program
    # carries no flags/planes/freeze scratch at all).
    nfr = 2 * len(frz)
    it = iter(refs)
    Text_hbm, A_hbm = next(it), next(it)
    flags = next(it) if frz else None          # SMEM (6,) i32 edge flags
    fr_hbm = [next(it) for _ in range(nfr)]    # squeezed freeze planes
    out_ref, buf0, buf1 = next(it), next(it), next(it)
    a_vmem, ext2, o2, esems, osems, asem = (next(it) for _ in range(6))
    fr_vmem = [next(it) for _ in range(nfr)]
    fsems = next(it) if frz else None

    k = pl.program_id(0)
    i = pl.program_id(1)
    scal = (rdx2, rdy2, rdz2)
    sl = i % 2

    # One-time: extended coefficient (and the chunk-invariant freeze
    # planes of the open dims) into VMEM.
    @pl.when((k == 0) & (i == 0))
    def _():
        dma = pltpu.make_async_copy(A_hbm, a_vmem, asem)
        dma.start()
        dma.wait()

    if frz:
        @pl.when((k == 0) & (i == 0))
        def _():
            cs = [pltpu.make_async_copy(fr_hbm[j], fr_vmem[j], fsems.at[j])
                  for j in range(nfr)]
            for c in cs:
                c.start()
            for c in cs:
                c.wait()

    # Out-write bookkeeping (identical scheme to diffusion_mega._kernel):
    # drain at each step boundary, else wait the DMA whose slot is reused.
    @pl.when((i == 0) & (k > 0))
    def _():
        pltpu.make_async_copy(o2.at[0], o2.at[0], osems.at[0]).wait()
        pltpu.make_async_copy(o2.at[1], o2.at[1], osems.at[1]).wait()

    @pl.when(i >= 2)
    def _():
        pltpu.make_async_copy(o2.at[sl], o2.at[sl], osems.at[sl]).wait()

    # Extended-slab fetches (rows [i*bx-1, i*bx+bx+1), CLAMPED at the
    # buffer ends — the clamped duplicate rows only feed shoulder rows that
    # are outside the validity trapezoid).  Edge programs fetch their own
    # segments synchronously; interior programs consume their
    # predecessor's prefetch and issue the next one.
    def sync_fetch(src):
        @pl.when(i == 0)
        def _():
            c0 = pltpu.make_async_copy(src.at[0:1], ext2.at[sl, 0:1],
                                       esems.at[sl])
            c1 = pltpu.make_async_copy(src.at[0:bx + 1],
                                       ext2.at[sl, 1:bx + 2],
                                       esems.at[1 - sl])
            c0.start(); c1.start(); c0.wait(); c1.wait()

        @pl.when(i == nbe - 1)
        def _():
            c0 = pltpu.make_async_copy(src.at[S0e - bx - 1:S0e],
                                       ext2.at[sl, 0:bx + 1], esems.at[sl])
            c1 = pltpu.make_async_copy(src.at[S0e - 1:S0e],
                                       ext2.at[sl, bx + 1:bx + 2],
                                       esems.at[1 - sl])
            c0.start(); c1.start(); c0.wait(); c1.wait()

    def prefetch_next(src):
        # Prefetch the NEXT program's slab — targets slabs 1..nbe-2 only
        # (edge programs fetch their own clamped segments synchronously).
        @pl.when((i >= 0) & (i <= nbe - 3))
        def _():
            pltpu.make_async_copy(
                src.at[pl.ds((i + 1) * bx - 1, bx + 2)],
                ext2.at[1 - sl], esems.at[1 - sl]).start()

    for cond, src in ((k == 0, Text_hbm),
                      ((k > 0) & (k % 2 == 1), buf0),
                      ((k > 0) & (k % 2 == 0), buf1)):
        @pl.when(cond)
        def _(src=src):
            sync_fetch(src)
            prefetch_next(src)

    @pl.when((i > 0) & (i < nbe - 1))
    def _():
        pltpu.make_async_copy(ext2.at[sl], ext2.at[sl], esems.at[sl]).wait()

    # Stencil update in x-row bands (identical scheme to the mega-kernel's
    # interior programs; every row of the extended buffer is "interior" —
    # shoulder rows compute garbage that the shrinking validity never reads
    # back into valid cells).
    ext = ext2.at[sl]
    o_vmem = o2.at[sl]
    c = ext[1:bx + 1]
    a = a_vmem[pl.ds(i * bx, bx)]
    if bx > 2:
        o_vmem[1:bx - 1, 1:-1, 1:-1] = _u_rows(
            c[0:bx - 2], c[1:bx - 1], c[2:bx], a[1:bx - 1], *scal)
    o_vmem[0:1, 1:-1, 1:-1] = _u_rows(ext[0:1], c[0:1], c[1:2],
                                      a[0:1], *scal)
    o_vmem[bx - 1:bx, 1:-1, 1:-1] = _u_rows(
        c[bx - 2:bx - 1], c[bx - 1:bx], ext[bx + 1:bx + 2],
        a[bx - 1:bx], *scal)
    if modes[1] == "wrap":
        # y self-wrap; in extended-y mode the edge rows are shoulder cells
        # whose (garbage) values the validity argument never reads back,
        # and in frozen-y mode the edge rows are owned by the freeze below.
        o_vmem[:, 0:1, 1:-1] = o_vmem[:, S1e - 2:S1e - 1, 1:-1]
        o_vmem[:, S1e - 1:S1e, 1:-1] = o_vmem[:, 1:2, 1:-1]
    if modes[2] == "wrap":
        # z self-wrap; ditto for extended-z shoulder lanes / frozen-z.
        o_vmem[:, :, 0:1] = o_vmem[:, :, S2 - 2:S2 - 1]
        o_vmem[:, :, S2 - 1:S2] = o_vmem[:, :, 1:2]

    # Open-boundary edge freeze (after the wrap writes — the freeze wins
    # the shared cells, like the per-step path's no-write planes): each
    # open dim's boundary plane is re-written from the chunk-entry values
    # on the devices whose `axis_index` edge flag is set ("frozen" dims
    # set both flags on every device — one device IS both edges).  x
    # planes belong to the single program owning that extended row; y/z
    # planes are written band-wise by every program.
    for j, (d, lo_i, hi_i) in enumerate(frz):
        vlo, vhi = fr_vmem[2 * j], fr_vmem[2 * j + 1]
        flo, fhi = flags[2 * d], flags[2 * d + 1]
        if d == 0:
            @pl.when((i == lo_i // bx) & (flo == 1))
            def _(vlo=vlo, r=lo_i % bx):
                o_vmem[r:r + 1] = vlo[...][None]

            @pl.when((i == hi_i // bx) & (fhi == 1))
            def _(vhi=vhi, r=hi_i % bx):
                o_vmem[r:r + 1] = vhi[...][None]
        elif d == 1:
            @pl.when(flo == 1)
            def _(vlo=vlo, p=lo_i):
                o_vmem[:, p:p + 1, :] = vlo[pl.ds(i * bx, bx)][:, None, :]

            @pl.when(fhi == 1)
            def _(vhi=vhi, p=hi_i):
                o_vmem[:, p:p + 1, :] = vhi[pl.ds(i * bx, bx)][:, None, :]
        else:
            @pl.when(flo == 1)
            def _(vlo=vlo, p=lo_i):
                o_vmem[:, :, p:p + 1] = vlo[pl.ds(i * bx, bx)][:, :, None]

            @pl.when(fhi == 1)
            def _(vhi=vhi, p=hi_i):
                o_vmem[:, :, p:p + 1] = vhi[pl.ds(i * bx, bx)][:, :, None]

    # Async write-back.  Final step: the central window goes to the real
    # output; shoulder programs park their slab in the (otherwise unused)
    # next ping-pong buffer so every program starts exactly one out-DMA and
    # the semaphore accounting stays statically balanced.
    # All puts are FULL slabs: every semaphore wait above assumes the
    # full-slab byte count, so a narrower (y-windowed) final DMA would
    # unbalance the accounting and hang the chip.  In y-extended mode the
    # output therefore carries the extended y span and the caller slices
    # the central window in XLA.
    central = (i >= off) & (i < off + nbo)

    def put(dst, at):
        pltpu.make_async_copy(o_vmem, dst.at[at], osems.at[sl]).start()

    @pl.when((k == K - 1) & central)
    def _():
        put(out_ref, pl.ds((i - off) * bx, bx))

    # Shoulder slabs park in the would-be ping-pong TARGET of this step
    # (buf0 for even k, buf1 for odd) — the other buffer is this step's
    # SOURCE, still being read by neighboring programs.
    @pl.when((k == K - 1) & ~central)
    def _():
        put(buf0 if (K - 1) % 2 == 0 else buf1, pl.ds(i * bx, bx))

    @pl.when((k < K - 1) & (k % 2 == 0))
    def _():
        put(buf0, pl.ds(i * bx, bx))

    @pl.when((k < K - 1) & (k % 2 == 1))
    def _():
        put(buf1, pl.ds(i * bx, bx))

    # Final drain: the last two out DMAs have no successor to wait them.
    @pl.when((k == K - 1) & (i == nbe - 1))
    def _():
        pltpu.make_async_copy(o2.at[1 - sl], o2.at[1 - sl],
                              osems.at[1 - sl]).wait()
        pltpu.make_async_copy(o2.at[sl], o2.at[sl], osems.at[sl]).wait()


def _window_steps_xla(Text, A_ext, *, K, modes, grid, rdx2, rdy2, rdz2):
    """Pure-XLA realization of the chunk kernel's per-step update (interior
    x rows; y/z wrap or extended) — the interpret-mode fallback so CPU
    meshes and the driver dryrun exercise the SAME chunked-exchange
    /shrinking-validity structure the TPU kernel runs (the kernel itself is
    manual-DMA and has no interpret mode).  This realization additionally
    carries the open-boundary modes (`_dim_modes`): after each step, open
    global-edge devices re-freeze their boundary slab from the chunk-entry
    buffer — the no-write halo semantics — which both preserves the
    reference's frozen boundary rows bit-for-bit and quarantines the
    garbage in the beyond-domain shoulder rows (a frozen row is never
    recomputed, so nothing beyond it is ever read by a valid row).  The
    wrap/freeze primitives are the engine's
    (`chunk_engine.wrap_edges`/`freeze_open_dim`)."""
    from jax import lax

    F = Text   # chunk-entry values: the freeze source for open edges

    def step(_, U):
        S1e, S2 = U.shape[1], U.shape[2]
        U = U.at[1:-1, 1:-1, 1:-1].set(
            _u_rows(U[:-2], U[1:-1], U[2:], A_ext[1:-1],
                    rdx2=rdx2, rdy2=rdy2, rdz2=rdz2))
        if modes[1] == "wrap":
            U = wrap_edges(U, 1, S1e, 2)
        if modes[2] == "wrap":
            U = wrap_edges(U, 2, S2, 2)
        for d in range(3):
            Sd = U.shape[d]
            if modes[d] in ("frozen", "oext"):
                lo = K if modes[d] == "oext" else 0
                hi = Sd - 1 - K if modes[d] == "oext" else Sd - 1
                U = freeze_open_dim(U, F, d, modes[d], lo, hi, grid)
        return U

    return lax.fori_loop(0, K, step, Text)


def _window_shape(shape, K, modes):
    """The chunk's extended window as its kernels see it: +2K along every
    extended dim, the lane extent of an extended z padded to 128."""
    S = list(ext_shape(shape, K, modes))
    if modes[2] in ("ext", "oext"):
        S[2] = pad128(S[2])
    return tuple(S)


def _wavefront_modes(modes):
    """The wavefront kernel's halo modes for a chunk's window: an
    extended dim's end rows are shoulders, held fixed ("frozen") as they
    never reach the central window; wrap and frozen dims keep theirs.
    None where a dim is "oext": open mesh edges re-freeze per device
    (`axis_index` flags), which the wavefront kernel does not do."""
    if "oext" in modes:
        return None
    return tuple("frozen" if m == "ext" else m for m in modes)


def _wavefront_plan(window_shape, K, modes, dtype):
    """`(D, bx)` where the chunk's K steps run as wavefront sweeps of its
    window (`diffusion_mega.wavefront_plan`), else 0."""
    wmodes = _wavefront_modes(modes)
    return wmodes and wavefront_plan(window_shape, K, wmodes, dtype)


def _chunk_call(Text, A_ext, out_shape3, *, K, bx, modes, grid,
                rdx2, rdy2, rdz2, interpret=False, _per_step_sweep=False):
    """Advance K steps on the extended buffer; returns the central
    `out_shape3` window.  The compiled chunk runs as wavefront sweeps
    where `_wavefront_plan` admits its window, else (or with
    `_per_step_sweep`, for the equivalence tests) as the one-step-per-
    sweep kernel."""
    import jax
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S0e, S1e, S2e = Text.shape
    S0, S1o, S2o = out_shape3
    if interpret:
        out = _window_steps_xla(Text, A_ext, K=K, modes=modes, grid=grid,
                                rdx2=rdx2, rdy2=rdy2, rdz2=rdz2)
        return central_window(out, out_shape3, K, modes)
    import jax.numpy as jnp

    extended = [m in ("ext", "oext") for m in modes]
    y_ext, z_ext = extended[1], extended[2]
    if z_ext and S2e % 128 != 0:
        # Mosaic requires 128-aligned VMEM lane slices; right-pad the
        # extended lane extent with zeros.  The garbage lanes lie beyond
        # the +K extension: their invalidity front reaches exactly lane
        # K+S2o after K steps, never entering the central window.
        S2p = ((S2e + 127) // 128) * 128
        pad = [(0, 0), (0, 0), (0, S2p - S2e)]
        Text = jnp.pad(Text, pad)
        A_ext = jnp.pad(A_ext, pad)
        S2e = S2p
    plan = (not _per_step_sweep) and _wavefront_plan(Text.shape, K, modes,
                                                     Text.dtype)
    if plan:
        # D steps per HBM sweep, in place in the window's buffer; the
        # shoulders' end rows pass through, which keeps the validity front
        # at one row a step, so the central window equals the one-step
        # kernel's below.
        D, wbx = plan
        run = partial(_wavefront_steps, bx=wbx, rdx2=rdx2, rdy2=rdy2,
                      rdz2=rdz2, modes=_wavefront_modes(modes),
                      force_streamed=False,
                      name="diffusion3d_trapezoid_wavefront")
        out = run(Text, A_ext, sweeps=K // D, D=D)
        if K % D:
            out = run(out, A_ext, sweeps=1, D=K % D)
        return central_window(out, out_shape3, K, modes)
    assert K == bx, "chunk depth is pinned to the block row count"
    nbe = S0e // bx
    nbo = S0 // bx
    off = 1 if modes[0] != "frozen" else 0   # = extension rows // bx

    # Open-dim freeze config: (dim, lo, hi) boundary-plane indices in the
    # (logical) extended buffer — `out + K` offsets for "oext", the buffer
    # ends for "frozen" — plus the squeezed chunk-entry freeze planes and
    # the per-device SMEM edge flags (frozen dims statically flag both
    # sides: one device IS both global edges, and no `axis_index` is
    # traced, so 1-device frozen grids still run under plain `jax.jit`).
    frz = tuple((d, (K if modes[d] == "oext" else 0),
                 out_shape3[d] + (K if modes[d] == "oext" else 0) - 1)
                for d in range(3) if modes[d] in ("oext", "frozen"))
    fr_planes = []
    flag_ops = []
    if frz:
        for d, lo, hi in frz:
            for idx in (lo, hi):
                fr_planes.append(jnp.squeeze(
                    lax.slice_in_dim(Text, idx, idx + 1, axis=d), d))
        flag_ops = [_edge_flags(modes, grid)]

    kern = partial(_kernel, K=K, bx=bx, nbe=nbe, nbo=nbo, off=off,
                   S0e=S0e, S1e=S1e, S2=S2e, modes=tuple(modes), frz=frz,
                   rdx2=rdx2, rdy2=rdy2, rdz2=rdz2)

    operands = [Text, A_ext, *flag_ops, *fr_planes]
    vmas = [getattr(getattr(x, "aval", None), "vma", None)
            for x in operands]
    vma = frozenset().union(*[v for v in vmas if v])

    def shp(s):
        return (jax.ShapeDtypeStruct(s, Text.dtype, vma=vma) if vma
                else jax.ShapeDtypeStruct(s, Text.dtype))

    fr_scratch = [pltpu.VMEM(p.shape, Text.dtype) for p in fr_planes]
    if frz:
        fr_scratch.append(pltpu.SemaphoreType.DMA((len(fr_planes),)))
    out, _, _ = pl.pallas_call(
        kern,
        name="diffusion3d_trapezoid_chunk",
        grid=(K, nbe),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)]
        + [pl.BlockSpec(memory_space=pltpu.SMEM)] * len(flag_ops)
        + [pl.BlockSpec(memory_space=pl.ANY)] * len(fr_planes),
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3,
        out_shape=[shp((S0, S1e, S2e)), shp(Text.shape), shp(Text.shape)],
        # Text is dead after the k=0 reads (the freeze planes are their
        # own buffers); buf1 (first written at k=1) reuses its buffer.
        input_output_aliases={0: 2},
        scratch_shapes=[
            pltpu.VMEM(Text.shape, Text.dtype),             # a_vmem
            pltpu.VMEM((2, bx + 2, S1e, S2e), Text.dtype),  # ext2
            pltpu.VMEM((2, bx, S1e, S2e), Text.dtype),      # o2
            pltpu.SemaphoreType.DMA((2,)),                  # esems
            pltpu.SemaphoreType.DMA((2,)),                  # osems
            pltpu.SemaphoreType.DMA,                        # asem
        ] + fr_scratch,                                     # fr_vmem, fsems
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=128 * 1024 * 1024,
            dimension_semantics=("arbitrary", "arbitrary")),
    )(*operands)
    if y_ext:
        # Central y window (tile-aligned K offset: a cheap slab slice).
        out = lax.slice_in_dim(out, K, K + S1o, axis=1)
    if z_ext:
        # Central z window (lane-dim slice, one relayout pass per chunk —
        # amortized 1/K per step).
        out = lax.slice_in_dim(out, K, K + S2o, axis=2)
    return out


def fused_diffusion_trapezoid_steps(T, A, *, n_inner: int, bx: int,
                                    grid, rdx2, rdy2, rdz2,
                                    force_y_ext=None, force_z_ext=None,
                                    interpret=False, _per_step_sweep=False):
    """Advance `n_inner` steps in chunks of K=bx trapezoidal kernel calls
    (plus a per-step remainder handled by the caller; this function runs
    only the `n_inner // bx` full chunks and returns `(T, steps_done)`).
    `force_y_ext`/`force_z_ext` override the mesh-derived modes
    (benchmarking the `(N,M,K)` program shapes on a 1-device self-torus).
    Each dispatch of the program being traced counts the steps its
    chunks ran as wavefront sweeps in `igg_chunk_wavefront_steps_total`
    (`igg.parallel.count_per_dispatch`); `_per_step_sweep` pins the
    one-step-per-sweep kernel, for the equivalence tests."""
    from ..parallel import count_per_dispatch

    K = bx
    shape = T.shape
    modes = _dim_modes(grid, force_y_ext, force_z_ext)
    A_ext = _extend(A, K, grid, shape, modes)   # loop-invariant

    def one(T):
        Text = _extend(T, K, grid, shape, modes)
        return (_chunk_call(Text, A_ext, shape, K=K, bx=bx, modes=modes,
                            grid=grid, rdx2=rdx2, rdy2=rdy2, rdz2=rdz2,
                            interpret=interpret,
                            _per_step_sweep=_per_step_sweep),)

    T, done = run_chunks((T,), n_inner=n_inner, K=K, one_chunk=one)
    if not (interpret or _per_step_sweep) and _wavefront_plan(
            _window_shape(shape, K, modes), K, modes, T.dtype):
        count_per_dispatch("igg_chunk_wavefront_steps_total", done)
    return T, done
