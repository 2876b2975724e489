"""Fused Pallas HydroMech3D iteration (`hydromech3d.mosaic`).

One `pallas_call` (`hydromech3d_iteration`) performs a whole
pseudo-transient iteration of the two-phase system — materials, the
`maxloc` of the permeability, both pressure equations, the stresses,
momentum residuals and damped velocity updates — on x-slabs of `bx` cell
rows, reading each state field once and writing each updated field once.
The arithmetic is LITERALLY the model's `fluid_core` and `momentum_core`
(the momentum chain is Stokes's `momentum_residuals`), applied to the slab
extended by the margin rows the chain reads: one cell row on each side
(the x-staggered velocity one face row more after it).

Margin rows are carried in VMEM.  The slabs run in sequence, and the
fields with margins (`_MARGINS`) are copied from HBM block by block into
a ring of `_SLOTS` slab slots, two blocks ahead of the slab computed: the
program that computes slab k finds its extended window (the last row of
block k - 1, block k and the head of block k + 1) in the ring, and starts
the copy of block k + 2 into the slot block k - 2 held.  So each row is
read from HBM once, and the fields without margins and every output
follow the slab computed through their index maps.  The rows before and
after a sequence are read once, into the ring's last rows, after its
slots; at the grid's two ends they are the wrapped rows whose results
land only in x halo rows, which the exchange below overwrites.  Where the number of
slabs is even the grid has two `"parallel"` halves, each its own sequence
with its own edge rows, so a chip with two TensorCores splits the sweep;
one core runs the halves in turn.  `igg_margin_rows_carried_total` and
`igg_margin_rows_fetched_total` count the margin rows served from the
ring and read from HBM.

Halos.  The fields the iteration updates on interior cells only (the
model's `EXCHANGED`) are exchanged; Pt, EtaC and K_muf are computed on
every cell from halo-valid inputs and need none.  y and z dims that are
periodic on a single device are wrapped in VMEM by every program
(`stokes_pallas`'s self-wrap, per-field staggered overlap).  Every other
halo plane is exchanged after the kernel, dimension by dimension
(`igg.halo.exchange_all_dims_grouped`), from the kernel's own updated
planes, and written into the outputs; x always goes this way, so its
wrapped edge rows are replaced, and an open boundary gets back the planes
the iteration read (the no-write semantics of the XLA rung).  The dense x
planes cost two 1-row copies per field; a mesh split along y or z pays
strided planes there.

:func:`hydromech_iterations` runs `n` iterations as an HBM ping-pong
(`stokes_pallas.stokes_iterations`' scheme): each iteration writes into
the state set the one before it read (`into=`), so no loop carry is
copied.
"""

from __future__ import annotations

from functools import partial

from .diffusion_pallas import _wrap_dims, _wrap_set
from ._vmem import fit_bx, vmem_limit

_BX_FULL = 8     # the slab height the fit starts from

# The fields the chain reads beyond the slab, each with the rows it reads
# after it (one row before it for all): the x-staggered velocity two.
_MARGINS = (("Pt", 1), ("Pf", 1), ("EtaC", 1), ("K_muf", 1), ("Phi", 1),
            ("Vx", 2), ("Vy", 1), ("Vz", 1))
# The fields read on the slab alone.
_SLAB_ONLY = ("RPf", "dVx", "dVy", "dVz")
# Ring slots: the blocks of the slab computed, of its two neighbours, and
# the one in flight.  After them the ring holds a sequence's edge rows:
# the row before it and the one or two after it.
_SLOTS = 4
_EDGE_ROWS = 1 + max(after for _, after in _MARGINS)


def _halves(nb: int) -> int:
    """Sequences the slab sweep is cut into: two where `nb` is even."""
    return 2 if nb % 2 == 0 else 1


def margin_rows(nb: int):
    """`(carried, fetched)`: the margin rows one iteration over `nb` slabs
    serves from the ring and reads from HBM (the edge rows of each
    sequence)."""
    per_slab = sum(1 + after for _, after in _MARGINS)
    return per_slab * (nb - _halves(nb)), per_slab * _halves(nb)


def _vmem_need(bx: int, S1: int, S2: int, itemsize: int = 4) -> int:
    """VMEM bytes the fused iteration demands at slab height `bx`: the
    rings (`_SLOTS` blocks and the edge rows of each of the eight fields
    with margins), the
    double-buffered blocks of the four fields read on the slab alone and
    of the eleven outputs, each row tile-padded (the face fields of y and
    z are 257 rows and lanes at 256^2), and a 1.3x margin for Mosaic's
    spill slots and scratch (calibrated against chipless v5e compiles at
    256^2, bx 4: the windows take 73.7 MiB, the compile 88.1 MiB, the
    model 95.8 MiB; bx 8 models 183 MiB, over the cap)."""
    from .chunk_engine import pad8, pad128

    centre = pad8(S1) * pad128(S2)
    ry, rz = pad8(S1 + 1) * pad128(S2), pad8(S1) * pad128(S2 + 1)
    ring = (_SLOTS * bx + _EDGE_ROWS) * (6 * centre + ry + rz)  # Pt..Vz
    slab_only = 2 * (2 * centre + ry + rz)          # RPf, dVx; dVy; dVz
    outs = 2 * (7 * centre + 2 * ry + 2 * rz)
    return int((ring + bx * (slab_only + outs)) * itemsize * 1.3)


def _fit_bx(bx: int, S0: int, S1: int, S2: int,
            check_vmem: bool = True) -> int:
    return fit_bx(_vmem_need, bx, S0, S1, S2, min_bx=4,
                  check_vmem=check_vmem)


def hydromech_pallas_supported(grid, Pt, interpret: bool = False):
    """Whether the fused iteration applies: an overlap-3 grid, a 3-D
    centre-shaped block large enough to slab, and (compiled) some slab
    height whose windows fit the VMEM budget.  Any device count and
    periodicity.  Returns an :class:`igg.degrade.Admission`."""
    from ..degrade import Admission

    if grid.overlaps != (3, 3, 3):
        return Admission.no(f"grid overlaps {grid.overlaps} != (3, 3, 3)")
    if Pt.ndim != 3:
        return Admission.no(f"pressure rank {Pt.ndim} != 3")
    s = tuple(grid.local_shape_any(Pt))
    if s != tuple(grid.nxyz):
        return Admission.no(f"local shape {s} != grid block "
                            f"{tuple(grid.nxyz)}")
    if not (s[0] % 8 == 0 and s[0] >= 16 and s[1] >= 8 and s[2] >= 8):
        return Admission.no(f"local block {s} too small to slab "
                            f"(needs x % 8 == 0, x >= 16, y >= 8, z >= 8)")
    if _fit_bx(_BX_FULL, *s, check_vmem=not interpret) < 4:
        return Admission.no(f"no slab height bx >= 4 fits the VMEM budget "
                            f"for local y*z area {s[1]}x{s[2]}")
    return Admission.yes()


def _slab(ePt, ePf, eEta, eK, ePhi, eVx, eVy, eVz, cRPf, cdV, outs, *,
          bx, c, ols, wrap_y, wrap_z):
    """One slab's update: the extended windows `e*` (bx + 2 rows, Vx
    bx + 3), the slab refs `cRPf` and `cdV`, the output refs `outs`
    (`STATE` order)."""
    import jax.numpy as jnp

    from ..models.hydromech3d import fluid_core, momentum_core

    (oPt, oPf, oRPf, oEta, oK, oVx, oVy, oVz, odVx, odVy, odVz) = outs
    I = slice(1, -1)
    Eta, K, Pt, RPf, Pf = fluid_core(ePt, ePf, cRPf[:, I, I], eEta, eK,
                                     ePhi, eVx, eVy, eVz, c=c)
    # The x-staggered residual spans bx + 1 face rows of the extended
    # slab; its accumulator gets a dummy last row (result unused).
    dVx = cdV[0][:, I, I]
    dVx = jnp.concatenate([dVx, jnp.zeros_like(dVx[0:1])], axis=0)
    dVxn, dVyn, dVzn, ix, iy, iz = momentum_core(
        Pt, eVx, eVy, eVz, dVx, cdV[1][:, I, I], cdV[2][:, I, I], ePhi, c=c)

    # Output rows j <-> extended rows j + 1.
    oPt[:] = Pt[1:1 + bx]
    oEta[:] = Eta[1:1 + bx]
    oK[:] = K[1:1 + bx]
    oPf[:] = ePf[1:1 + bx]
    oPf[:, I, I] = Pf
    oRPf[:] = cRPf[:]
    oRPf[:, I, I] = RPf
    for o, e, inc in ((oVx, eVx, ix[0:bx]), (oVy, eVy, iy), (oVz, eVz, iz)):
        o[:] = e[1:1 + bx]
        o[:, I, I] = e[1:1 + bx, I, I] + inc
    for o, cd, dn in ((odVx, cdV[0], dVxn[0:bx]), (odVy, cdV[1], dVyn),
                      (odVz, cdV[2], dVzn)):
        o[:] = cd[:]
        o[:, I, I] = dn

    # Self-wrap of the exchanged fields' y rows, then z columns (z owns
    # the shared corners): halo <- the plane `ol` in from the other side.
    exch = (oPf, oRPf, oVx, oVy, oVz, odVx, odVy, odVz)
    if wrap_y:
        for o, (oly, _) in zip(exch, ols):
            sy = o.shape[1]
            o[:, 0:1, :] = o[:, sy - oly:sy - oly + 1, :]
            o[:, sy - 1:sy, :] = o[:, oly - 1:oly, :]
    if wrap_z:
        for o, (_, olz) in zip(exch, ols):
            sz = o.shape[2]
            o[:, :, 0:1] = o[:, :, sz - olz:sz - olz + 1]
            o[:, :, sz - 1:sz] = o[:, :, olz - 1:olz]


def _kernel(*refs, bx, m, exts, into, **slab):
    """Program `(h, j)` computes slab j of sequence h (of its m).  Blocks
    j and j + 1 are in the ring when it runs: the first program copies
    blocks 0 and 1 and the sequence's edge rows and waits for them all,
    every later one waits for the block its predecessor started.  `exts`: the y and z extents each ring field's copies span.  The index
    arithmetic is int32 `lax`, done once for all fields: each scalar `jnp`
    operation would be a traced call of its own.  No `pl.when` holds the
    slab's arithmetic: traced inside a conditional, it costs seconds of
    set-up on the chip."""
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .diffusion_pallas import _ref_taker

    take = _ref_taker(refs)
    hbm = take(len(_MARGINS))
    cRPf, *cdV = take(len(_SLAB_ONLY))
    take(11 if into else 0)                # the outputs' buffers, never read
    outs = take(11)
    rings = take(len(_MARGINS))
    sems, esems = take(2)
    fields = range(len(_MARGINS))
    i32 = np.int32
    j = pl.program_id(1)
    first = lax.mul(pl.program_id(0), i32(m))    # the sequence's first slab

    def start(f, src, n, dst, sem):
        rows = (hbm[f].at[pl.ds(src, n)] if exts[f] == hbm[f].shape[1:]
                else hbm[f].at[pl.ds(src, n), pl.ds(0, exts[f][0]),
                               pl.ds(0, exts[f][1])])
        pltpu.make_async_copy(rows, rings[f].at[pl.ds(dst, n)], sem).start()

    def wait(f, src, n, dst, sem):
        # A wait reads only the semaphore and the bytes: the ring rows
        # stand in for the source.
        rows = rings[f].at[pl.ds(dst, n)]
        pltpu.make_async_copy(rows, rows, sem).wait()

    def slot_row(k):
        return lax.mul(lax.rem(k, i32(_SLOTS)), i32(bx))

    def blocks(k):
        # Block k of the sequence, every field: into ring slot k % _SLOTS.
        slot = lax.rem(k, i32(_SLOTS))
        src = lax.mul(lax.add(first, k), i32(bx))
        dst = lax.mul(slot, i32(bx))
        return [(f, src, bx, dst, sems.at[f, slot]) for f in fields]

    # The rows before and after the sequence (wrapped at the grid's ends),
    # in the ring's last rows, after its slots.
    edge = _SLOTS * bx
    before = lax.sub(lax.mul(first, i32(bx)), i32(1))
    after = lax.mul(lax.add(first, i32(m)), i32(bx))
    edge_rows, edges = {}, []
    for f in fields:
        sx = hbm[f].shape[0]
        for e in range(_MARGINS[f][1] + 1):
            if (sx, e) not in edge_rows:
                r = before if e == 0 else lax.add(after, i32(e - 1))
                edge_rows[sx, e] = lax.rem(lax.add(r, i32(sx)), i32(sx))
            edges.append((f, edge_rows[sx, e], 1, edge + e, esems.at[f, e]))

    # Every start is waited exactly once, in the same sequence.  Block
    # j + 2 goes into the slot of block j - 2, which slab j - 1 read last.
    @pl.when(lax.eq(j, i32(0)))
    def _():
        now = blocks(i32(0)) + edges
        for d in now + (blocks(i32(1)) if m > 1 else []):
            start(*d)
        for d in now:
            wait(*d)

    if m > 2:
        @pl.when(lax.lt(j, i32(m - 2)))
        def _():
            for d in blocks(lax.add(j, i32(2))):
                start(*d)

    if m > 1:
        @pl.when(lax.lt(j, i32(m - 1)))
        def _():
            for d in blocks(lax.add(j, i32(1))):
                wait(*d)

    # Slab j: the last row of block j - 1 (or the row before the
    # sequence), block j and the head of block j + 1 (or the rows after).
    prev = lax.select(lax.eq(j, i32(0)), i32(edge), lax.add(
        slot_row(lax.add(j, i32(_SLOTS - 1))), i32(bx - 1)))
    cur = slot_row(j)
    nxt = lax.select(lax.eq(j, i32(m - 1)), i32(edge + 1),
                     slot_row(lax.add(j, i32(1))))

    def window(f):
        _, s1, s2 = hbm[f].shape
        return jnp.concatenate(
            [rings[f][pl.ds(r, n), :s1, :s2] for r, n in (
                (prev, 1), (cur, bx), (nxt, _MARGINS[f][1]))], axis=0)

    _slab(*(window(f) for f in fields), cRPf, cdV, outs, bx=bx, **slab)


def _exchange(new, old, grid, wrap):
    """The exchanged fields' halo planes after the kernel: sends are the
    updated planes `ol - 1` and `s - ol`, the open-boundary fallbacks the
    planes the iteration read; dims wrapped in the kernel are skipped, x is
    always done (its edge rows hold wrapped-margin results).  Received
    planes are written in dimension order."""
    from jax import lax

    from ..halo import _plane, active_dims, exchange_all_dims_grouped

    dims_actives, sends, stales = [], [], []
    for F, O in zip(new, old):
        da = [(d, ol) for d, ol in active_dims(F.shape, grid)
              if d not in wrap and (d == 0 or grid.dims[d] > 1
                                    or grid.periods[d])]
        dims_actives.append(da)
        sends.append({k: v for d, ol in da for k, v in (
            ((d, 0), _plane(F, d, ol - 1)),
            ((d, 1), _plane(F, d, F.shape[d] - ol)))})
        stales.append({k: v for d, _ in da if not grid.periods[d]
                       for k, v in (((d, 0), _plane(O, d, 0)),
                                    ((d, 1), _plane(O, d, O.shape[d] - 1)))})
    recvs = exchange_all_dims_grouped(
        [F.shape for F in new], sends, dims_actives, grid, stales=stales,
        wraps=[wrap] * len(new), blocks=list(old))
    # The planes are materialized before any is written: a self-wrap plane
    # is a slice of the field it is written into, and fused into its
    # update it would cost a whole-field copy to keep the update in place.
    recvs = lax.optimization_barrier(recvs)
    out = []
    for F, recv in zip(new, recvs):
        for d in sorted(recv):
            first, last = recv[d]
            F = lax.dynamic_update_slice_in_dim(F, first, 0, axis=d)
            F = lax.dynamic_update_slice_in_dim(F, last, F.shape[d] - 1,
                                                axis=d)
        out.append(F)
    return out


def _slabs(shape, bx: int, interpret: bool):
    """`(bx, nb)`: the slab height whose windows fit, and the slabs."""
    S0, S1, S2 = shape
    bx = _fit_bx(bx, S0, S1, S2, check_vmem=not interpret)
    if bx < 4:
        raise ValueError(
            f"x size {S0} not divisible into slabs of >= 4 rows whose "
            f"windows fit VMEM at y*z area {S1}x{S2}")
    return bx, S0 // bx


def fused_hydromech_iteration(*args, c, bx: int = _BX_FULL,
                              interpret: bool = False, into=None):
    """One fused HydroMech3D PT iteration `(*state, Phi) -> state` (the
    model's `STATE` order) with its halo exchange, on any mesh.  Call
    inside SPMD code (`igg.sharded`).  Matches `hydromech3d.local_iteration`
    to Mosaic-vs-XLA rounding.  `c`: the model's `pseudo_steps`.

    `into`: a dead state set the outputs are written into (aliased, never
    read), for :func:`hydromech_iterations`.  The outputs cannot alias the
    inputs: a sequence's edge rows belong to slabs the other sequence (or,
    wrapped, the same one) may have written before they are read."""
    import jax
    import numpy as np
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .. import shared
    from ..halo import _is_tpu
    from ..models.hydromech3d import EXCHANGED, STATE
    from .chunk_engine import pad8, pad128

    *state, Phi = args
    Pt = state[0]
    grid = shared.global_grid()
    S0, S1, S2 = Pt.shape
    bx, nb = _slabs(Pt.shape, bx, interpret)
    H = _halves(nb)
    m = nb // H
    wy, wz = _wrap_dims(grid)
    f = dict(zip(STATE, state), Phi=Phi)

    def slab_block(h, j):
        # The slab sequence h's program j computes.
        return (lax.add(lax.mul(h, np.int32(m)), j), 0, 0)

    ring_fields = [f[n] for n, _ in _MARGINS]
    operands = ring_fields + [f[n] for n in _SLAB_ONLY]
    in_specs = ([pl.BlockSpec(memory_space=pl.ANY)] * len(_MARGINS)
                + [pl.BlockSpec((bx, *f[n].shape[1:]), slab_block)
                   for n in _SLAB_ONLY])
    aliases = {}
    if into is not None:
        aliases = {len(operands) + k: k for k in range(len(STATE))}
        operands.extend(into)
        in_specs.extend([pl.BlockSpec(memory_space=pl.ANY)] * len(STATE))

    vmas = [getattr(getattr(x, "aval", None), "vma", None) for x in operands]
    vma = frozenset().union(*[v for v in vmas if v])

    def shp(F):
        return (jax.ShapeDtypeStruct(F.shape, F.dtype, vma=vma) if vma
                else jax.ShapeDtypeStruct(F.shape, F.dtype))

    # The x-staggered fields' last row (global S0) lies outside the block
    # grid: the exchange below writes it.
    out_shape = [shp(F) for F in state]
    out_specs = [pl.BlockSpec((bx, *F.shape[1:]), slab_block)
                 for F in state]
    ols = [tuple(grid.ol_of_local(d, f[n].shape) for d in (1, 2))
           for n in EXCHANGED]
    # Mosaic slices a tiled ref only along whole tiles: on the chip a ring
    # copy spans the y and z tiles whole, padding included (the face
    # fields' 257 rows or lanes at 256^2 take 264 and 384); the
    # interpreters keep the fields' own extents.
    tiles = not interpret and _is_tpu(grid)
    exts = tuple((pad8(F.shape[1]), pad128(F.shape[2])) if tiles
                 else F.shape[1:] for F in ring_fields)
    scratch = ([pltpu.VMEM((_SLOTS * bx + _EDGE_ROWS, *e), F.dtype)
                for F, e in zip(ring_fields, exts)]
               + [pltpu.SemaphoreType.DMA((len(_MARGINS), _SLOTS)),
                  pltpu.SemaphoreType.DMA((len(_MARGINS), _EDGE_ROWS))])

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit(_vmem_need(bx, S1, S2)),
            dimension_semantics=("parallel", "arbitrary"))

    new = pl.pallas_call(
        partial(_kernel, bx=bx, m=m, exts=exts, into=into is not None,
                c=c, ols=ols, wrap_y=wy, wrap_z=wz),
        name="hydromech3d_iteration",
        grid=(H, m),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        input_output_aliases=aliases,
        interpret=interpret,
        **kwargs,
    )(*operands)

    new = list(new)
    idx = [STATE.index(n) for n in EXCHANGED]
    done = _exchange([new[i] for i in idx], [state[i] for i in idx], grid,
                     _wrap_set((wy, wz)))
    for i, F in zip(idx, done):
        new[i] = F
    return tuple(new)


def hydromech_iterations(state, Phi, n: int, **kw):
    """`n` fused iterations of `state` as an HBM ping-pong: the first
    writes fresh buffers, every later one writes into the set the one
    before it read (`into=`), so no iteration's result is copied back into
    a loop carry.  Each dispatch of the program being traced counts its
    `n - 1` ping-pong iterations in `igg_pingpong_iterations_total`, and
    the margin rows of its `n` iterations in
    `igg_margin_rows_carried_total` (served from the ring) and
    `igg_margin_rows_fetched_total` (read from HBM)
    (`igg.parallel.count_per_dispatch`)."""
    from jax import lax

    from ..parallel import count_per_dispatch

    A = tuple(state)
    if n <= 0:
        return A
    B, A = A, fused_hydromech_iteration(*A, Phi, **kw)

    def two(_, AB):
        A, B = AB
        B = fused_hydromech_iteration(*A, Phi, **kw, into=B)
        return fused_hydromech_iteration(*B, Phi, **kw, into=A), B

    A, B = lax.fori_loop(0, (n - 1) // 2, two, (A, B))
    if (n - 1) % 2:
        A = fused_hydromech_iteration(*A, Phi, **kw, into=B)
    count_per_dispatch("igg_pingpong_iterations_total", n - 1)
    _, nb = _slabs(A[0].shape, kw.get("bx", _BX_FULL),
                   kw.get("interpret", False))
    carried, fetched = margin_rows(nb)
    count_per_dispatch("igg_margin_rows_carried_total", n * carried)
    count_per_dispatch("igg_margin_rows_fetched_total", n * fetched)
    return A
