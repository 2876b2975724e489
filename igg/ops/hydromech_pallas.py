"""Fused Pallas HydroMech3D iteration (`hydromech3d.mosaic`).

One `pallas_call` (`hydromech3d_iteration`) performs a whole
pseudo-transient iteration of the two-phase system — materials, the
`maxloc` of the permeability, both pressure equations, the stresses,
momentum residuals and damped velocity updates — on one x-slab of `bx`
cell rows per program, reading each state field once and writing each
updated field once.  The arithmetic is LITERALLY the model's
`fluid_core` and `momentum_core` (the momentum chain is Stokes's
`momentum_residuals`), applied to the slab extended by the margin rows
the chain reads: one cell row on each side (the x-staggered velocity and
its accumulator one face row more), read as single-row blocks through
modular index maps, as in `stokes_pallas`.  Edge programs read wrapped
rows whose results land only in x halo rows, which the exchange below
overwrites.

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


def _vmem_need(bx: int, S1: int, S2: int, itemsize: int = 4) -> int:
    """VMEM bytes the fused iteration demands at slab height `bx`: the
    double-buffered windows (14*bx + 17 input rows: five centre fields
    with a margin row on each side, the damped fluid residual, the three
    velocities with their margins, the three accumulators; 11*bx output
    rows) and a 1.3x margin for Mosaic's spill slots and scratch
    (calibrated against chipless v5e compiles at 256^2: 56.2 MB at bx 4,
    where the windows are 58.5 MB; 136.9 MB at bx 8, over the chip's
    128 MB, of which 27.3 MB spill slots)."""
    rows = 25 * bx + 17
    return int(2 * rows * S1 * S2 * itemsize * 1.3)


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


def _kernel(*refs, bx, c, ols, wrap_y, wrap_z, into):
    import jax.numpy as jnp

    from ..models.hydromech3d import fluid_core, momentum_core
    from .diffusion_pallas import _ref_taker

    take = _ref_taker(refs)

    def ext(n):
        rs = take(n)
        return jnp.concatenate([r[:] for r in rs], axis=0)

    ePt, ePf, eEta, eK, ePhi = (ext(3) for _ in range(5))
    (cRPf,) = take(1)
    eVx, eVy, eVz = ext(4), ext(3), ext(3)
    cdV = take(3)
    take(11 if into else 0)                # the outputs' buffers, never read
    (oPt, oPf, oRPf, oEta, oK, oVx, oVy, oVz, odVx, odVy, odVz) = take(11)

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


def fused_hydromech_iteration(*args, c, bx: int = _BX_FULL,
                              interpret: bool = False, into=None):
    """One fused HydroMech3D PT iteration `(*state, Phi) -> state` (the
    model's `STATE` order) with its halo exchange, on any mesh.  Call
    inside SPMD code (`igg.sharded`).  Matches `hydromech3d.local_iteration`
    to Mosaic-vs-XLA rounding.  `c`: the model's `pseudo_steps`.

    `into`: a dead state set the outputs are written into (aliased, never
    read), for :func:`hydromech_iterations`.  The outputs cannot alias the
    inputs: each slab program reads its neighbours' margin rows."""
    import jax
    from jax.experimental import pallas as pl

    from .. import shared
    from ..models.hydromech3d import EXCHANGED, STATE

    *state, Phi = args
    Pt = state[0]
    grid = shared.global_grid()
    S0, S1, S2 = Pt.shape
    bx = _fit_bx(bx, S0, S1, S2, check_vmem=not interpret)
    if bx < 4:
        raise ValueError(
            f"x size {S0} not divisible into slabs of >= 4 rows whose "
            f"windows fit VMEM at y*z area {S1}x{S2}")
    nb = S0 // bx
    wy, wz = _wrap_dims(grid)
    f = dict(zip(STATE, state), Phi=Phi)

    # Extended windows: margin rows -1 and bx (the x-staggered velocity
    # also bx + 1); the damped residuals are read on the slab alone.
    reads = ([(n, (-1, "c", bx)) for n in ("Pt", "Pf", "EtaC", "K_muf",
                                           "Phi")]
             + [("RPf", ("c",)), ("Vx", (-1, "c", bx, bx + 1)),
                ("Vy", (-1, "c", bx)), ("Vz", (-1, "c", bx))]
             + [(n, ("c",)) for n in ("dVx", "dVy", "dVz")])
    operands, in_specs = [], []
    for name, rows in reads:
        F = f[name]
        sx, yz = F.shape[0], F.shape[1:]
        for r in rows:
            operands.append(F)
            if r == "c":
                in_specs.append(pl.BlockSpec((bx, *yz),
                                             lambda i: (i, 0, 0)))
            else:
                in_specs.append(pl.BlockSpec(
                    (1, *yz),
                    lambda i, rr=r, ss=sx: ((i * bx + rr) % ss, 0, 0)))
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
    out_specs = [pl.BlockSpec((bx, *F.shape[1:]), lambda i: (i, 0, 0))
                 for F in state]
    ols = [tuple(grid.ol_of_local(d, f[n].shape) for d in (1, 2))
           for n in EXCHANGED]

    kwargs = {}
    if not interpret:
        from jax.experimental.pallas import tpu as pltpu
        kwargs["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit(_vmem_need(bx, S1, S2)),
            dimension_semantics=("parallel",))

    new = pl.pallas_call(
        partial(_kernel, bx=bx, c=c, ols=ols, wrap_y=wy, wrap_z=wz,
                into=into is not None),
        name="hydromech3d_iteration",
        grid=(nb,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
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
    `n - 1` ping-pong iterations in `igg_pingpong_iterations_total`
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
    return A
