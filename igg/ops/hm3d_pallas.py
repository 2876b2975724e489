"""Fused Pallas HM3D step — mesh-capable (any dims / periodicity).

One `pallas_call` performs the full coupled hydro-mechanical step —
porosity-dependent (cubic) face permeabilities, Darcy fluxes, the effective
pressure update, the Gauss-Seidel-coupled porosity update, AND the grouped
halo update of both fields — reading Pe and phi once each and writing them
once each (the ideal 2+2 array traffic).  The XLA composition
(`hm3d.local_step`: `compute_step` + grouped `update_halo_local(Pe, phi)`)
pays ~10 HBM-bound fusion passes for the same step.

This extends the native-kernel tier (the reference's ">10x" claim for
custom kernels over array broadcasting, `/root/reference/README.md:161`)
to BASELINE config 4's model family on *every* rank of a decomposed run —
the per-rank property of the reference's native tier — not just the
single-device configuration.

The nonlinear per-step `(phi/phi0)^n` permeabilities and two coupled
interior updates cost the XLA path many extra HBM passes that all fuse
here.  Matches the XLA path to float32 rounding
(`tests/test_mega_tpu.py::test_hm3d_kernel_compiled_matches_xla`, on the
chip).  On self-wrap grids `fused_hm3d_steps` routes the time loop
through the two-field K-step mega-kernel (`igg/ops/hm3d_mega.py`).  Speed
against the XLA composition is not measured on today's code.

Structure (the two-field radius-1 instance of the `diffusion_pallas`
recipe; see that module's docstring for the design rationale):

1. **Send planes from thin-slab recomputation** — the updated inner
   boundary planes `ol-1` / `s-ol` of both fields
   (`/root/reference/src/update_halo.jl:386-394`) are produced by applying
   `hm3d.compute_step` (radius-1 shift-invariant) to 3-plane slabs, O(s²)
   work data-independent of the main kernel.
2. **Dimension-sequential plane exchange** — `exchange_all_dims_grouped`:
   both fields' planes ride ONE ppermute per (dim, side) (they share plane
   shapes), with corner/edge propagation, open-boundary stale fallbacks,
   and self-wrap local copies (`/root/reference/src/update_halo.jl:36,130,
   516-532`).
3. **Fused compute + assembly kernel** — x-slab programs compute both
   interior updates from extended slabs (single-row modular margins; edge
   programs read wrapped rows whose results land only in overwritten halo
   rows) and assemble the received planes in dimension order: x planes
   first, y rows, then z columns winning the shared corners.

**Per-dimension halo modes** (from `diffusion_pallas._wrap_dims`): y/z dims
that are periodic with a single device are handled by in-VMEM self-wrap
aliases — no plane of theirs ever materializes; exchanged (or open
single-device) dims take received/stale planes as blocked kernel inputs.
x always goes through the plane exchange (its planes cross program
boundaries anyway; on a single periodic device the engine degenerates to
the swap of the send planes — the self-neighbor path).

**Slab carry** (`fused_hm3d_steps`): for recv-mode y/z dims the kernel
emits the 3-plane boundary slabs of its assembled outputs as compact extra
outputs (z TRANSPOSED to `(S0, 3, S1)` — the natural `(S0, S1, 3)` form is
lane-padded ~42x in HBM), and the next iteration's send planes are computed
from the carried slabs without touching the big arrays.  The z send planes
are produced by applying `compute_step` with swapped y/z spacings to the
transposed slabs (the stencil is axis-symmetric), yielding the squeezed z
plane directly.

Semantics match :func:`igg.hide_communication` exactly: identical to the
plain sequential composition on periodic/interior ranks; at open-boundary
edge ranks the physically-meaningless halo cells keep pre-step values.
"""

from __future__ import annotations

from .diffusion_pallas import _check_applicable, _wrap_dims, _wrap_set

# See stokes_pallas: a tight scoped-vmem budget steers Mosaic
# toward better DMA/compute interleaving for slab kernels of this shape.
from ._vmem import fit_bx, vmem_limit


def _vmem_need(bx: int, S1: int, S2: int, itemsize: int = 4) -> int:
    """First-order window footprint of the fused step at slab height
    `bx`: two fields x (bx-row center + 2 single-row sides) + two bx-row
    outputs + compact slab emissions, double-buffered; the 2.0x margin
    absorbs Mosaic scratch (same calibration as
    `stokes_pallas._vmem_need` — the fixed 32 MB budget OOM'd the
    512^3 per-step compile, caught round 5)."""
    rows = 4 * bx + 8
    return int(2 * rows * S1 * S2 * itemsize * 2.0)


def _vmem_limit(bx: int, S1: int, S2: int) -> int:
    return vmem_limit(_vmem_need(bx, S1, S2))


def _fit_bx(bx: int, S0: int, S1: int, S2: int,
            check_vmem: bool = True) -> int:
    # min_bx=2: `_check_applicable` accepts bx=2 slabs and the per-step
    # kernel ran them before the round-5 VMEM gating.
    return fit_bx(_vmem_need, bx, S0, S1, S2, min_bx=2,
                  check_vmem=check_vmem)


def hm3d_pallas_supported(grid, Pe, interpret: bool = False):
    """Whether the fused step applies: 3-D unstaggered overlap-2 grid (any
    device count and any periodicity — the exchange engine handles open
    boundaries and multi-device meshes), local blocks large enough to slab.
    A recv-mode z dimension (exchanged or open) additionally needs z >= 128:
    its compact slab emission is an in-kernel lane extraction.  Returns an
    :class:`igg.degrade.Admission` (truthy/falsy) carrying the structured
    refusal reason."""
    from ..degrade import Admission

    if grid.overlaps != (2, 2, 2):
        return Admission.no(f"grid overlaps {grid.overlaps} != (2, 2, 2)")
    if Pe.ndim != 3:
        return Admission.no(f"field rank {Pe.ndim} != 3")
    s = tuple(grid.local_shape_any(Pe))
    if s != tuple(grid.nxyz):
        return Admission.no(f"staggered local shape {s} != grid block "
                            f"{tuple(grid.nxyz)}")
    if not (s[0] % 4 == 0 and s[0] >= 8 and s[1] >= 8 and s[2] >= 8):
        return Admission.no(f"local block {s} too small to slab "
                            f"(needs x % 4 == 0, x >= 8, y >= 8, z >= 8)")
    _, wz = _wrap_dims(grid)
    if not (wz or s[2] >= 128):
        return Admission.no(f"recv-mode z extent {s[2]} < 128 (in-kernel "
                            f"lane extraction needs a full lane tile)")
    # Some slab height must fit the VMEM cap in compiled mode (512^3-class
    # y*z areas overflow the fixed budget — round 5).
    if _fit_bx(8, s[0], s[1], s[2], check_vmem=not interpret) < 2:
        return Admission.no(f"no slab height bx >= 2 fits the VMEM budget "
                            f"for local y*z area {s[1]}x{s[2]}")
    return Admission.yes()


def _updated(wPe, wphi, kw):
    """`compute_step` on a 3-plane window of both fields: full-shape outputs
    with the interior updated, edge cells stale — exactly the XLA path's
    pre-exchange state (the engine patches edge rows of pending planes)."""
    from ..models.hm3d import compute_step

    return compute_step(wPe, wphi, **kw)


def _sends_and_stale(Pe, phi, slabs, kw, wrap_yz):
    """Keepdims send planes (updated inner planes `ol-1`/`s-ol`) for BOTH
    fields from compact boundary slabs, plus stale (outermost) planes for
    open-boundary dims — no reads of the big arrays beyond their four cheap
    contiguous x-end slabs.  Wrapped y/z dims need neither.

    Returns `(sends, stales)` as two-element lists (Pe, phi) of
    `{(dim, side): plane}` dicts for `exchange_all_dims_grouped`.

    z slabs arrive TRANSPOSED `(S0, 3, S1)`: the stencil is axis-symmetric,
    so applying it with swapped y/z spacings produces the transposed update
    whose middle plane is the squeezed z send plane `(S0, S1)`."""
    import jax.numpy as jnp
    from jax import lax

    s = Pe.shape
    wy, wz = wrap_yz
    (pe_y_lo, pe_y_hi, phi_y_lo, phi_y_hi,
     pe_zt_lo, pe_zt_hi, phi_zt_lo, phi_zt_hi) = slabs

    def xcut(A, lo, hi):
        return lax.slice_in_dim(A, lo, hi, axis=0)

    sends = [{}, {}]
    stales = [{}, {}]
    up = _updated(xcut(Pe, 0, 3), xcut(phi, 0, 3), kw)
    for i in range(2):
        sends[i][(0, 0)] = up[i][1:2]
    up = _updated(xcut(Pe, s[0] - 3, s[0]), xcut(phi, s[0] - 3, s[0]), kw)
    for i in range(2):
        sends[i][(0, 1)] = up[i][1:2]
    stales[0][(0, 0)] = xcut(Pe, 0, 1)
    stales[0][(0, 1)] = xcut(Pe, s[0] - 1, s[0])
    stales[1][(0, 0)] = xcut(phi, 0, 1)
    stales[1][(0, 1)] = xcut(phi, s[0] - 1, s[0])

    if not wy:
        up = _updated(pe_y_lo, phi_y_lo, kw)
        for i in range(2):
            sends[i][(1, 0)] = up[i][:, 1:2, :]
        up = _updated(pe_y_hi, phi_y_hi, kw)
        for i in range(2):
            sends[i][(1, 1)] = up[i][:, 1:2, :]
        stales[0][(1, 0)] = pe_y_lo[:, 0:1, :]
        stales[0][(1, 1)] = pe_y_hi[:, 2:3, :]
        stales[1][(1, 0)] = phi_y_lo[:, 0:1, :]
        stales[1][(1, 1)] = phi_y_hi[:, 2:3, :]
    if not wz:
        swapped = dict(kw)
        swapped["dy"], swapped["dz"] = kw["dz"], kw["dy"]
        up = _updated(pe_zt_lo, phi_zt_lo, swapped)
        for i in range(2):
            sends[i][(2, 0)] = jnp.expand_dims(up[i][:, 1, :], 2)
        up = _updated(pe_zt_hi, phi_zt_hi, swapped)
        for i in range(2):
            sends[i][(2, 1)] = jnp.expand_dims(up[i][:, 1, :], 2)
        stales[0][(2, 0)] = jnp.expand_dims(pe_zt_lo[:, 0, :], 2)
        stales[0][(2, 1)] = jnp.expand_dims(pe_zt_hi[:, 2, :], 2)
        stales[1][(2, 0)] = jnp.expand_dims(phi_zt_lo[:, 0, :], 2)
        stales[1][(2, 1)] = jnp.expand_dims(phi_zt_hi[:, 2, :], 2)
    return sends, stales


def _boundary_slabs(Pe, phi, wrap_yz):
    """One-time strided extraction of both fields' y/z 3-plane boundary
    slabs for the recv-mode dims (thereafter the kernel re-emits them
    compactly, z TRANSPOSED); `None` placeholders for wrapped dims.  Order
    matches the kernel's slab outputs: y slabs of both fields, then z."""
    from .diffusion_pallas import _boundary_slabs as one

    pe = one(Pe, wrap_yz)    # (y_lo, y_hi, zt_lo, zt_hi)
    ph = one(phi, wrap_yz)
    return (pe[0], pe[1], ph[0], ph[1], pe[2], pe[3], ph[2], ph[3])


def _make_kernel(wrap_y: bool, wrap_z: bool, kw_core, bx: int, nb: int,
                 emit_slabs: bool):
    """Kernel factory: one x-slab program computing both coupled updates and
    assembling halos in dimension order (x planes first, then y rows, then z
    columns — later dimensions own the shared corner/edge cells, realizing
    `/root/reference/src/update_halo.jl:36,130`).  `emit_slabs` adds the
    compact boundary-slab outputs consumed by the slab-carry loop; the
    single-step entry skips them (no consumer)."""
    from jax.experimental import pallas as pl

    n_planes_y = 0 if wrap_y else 4
    n_planes_z = 0 if wrap_z else 4

    def kernel(*refs):
        import jax.numpy as jnp

        from ..models.hm3d import step_core
        from .diffusion_pallas import _ref_taker

        take = _ref_taker(refs)
        m1, cPe, p1 = take(3)
        ePe = jnp.concatenate([m1[:], cPe[:], p1[:]], axis=0)
        m1, cphi, p1 = take(3)
        ephi = jnp.concatenate([m1[:], cphi[:], p1[:]], axis=0)
        pef, phif, pel, phil = take(4)            # squeezed (S1,S2) x planes
        y_in = take(n_planes_y)                   # (pe_f, pe_l, phi_f, phi_l)
        z_in = take(n_planes_z)
        oPe, ophi = take(2)
        y_out = take(4 if emit_slabs and not wrap_y else 0)
        z_out = take(4 if emit_slabs and not wrap_z else 0)

        dPe, dphi = step_core(ePe, ephi, **kw_core)

        # Out rows j <-> ext rows j+1; increments are on the ext interior
        # (offset 1), so out row j <-> increment row j.
        oPe[:] = ePe[1:1 + bx]
        oPe[:, 1:-1, 1:-1] = ePe[1:1 + bx, 1:-1, 1:-1] + dPe[0:bx]
        ophi[:] = ephi[1:1 + bx]
        ophi[:, 1:-1, 1:-1] = ephi[1:1 + bx, 1:-1, 1:-1] + dphi[0:bx]

        i = pl.program_id(0)
        S1, S2 = oPe.shape[1], oPe.shape[2]

        # x halo planes (interior region only — their y/z edge cells are
        # owned by the later y/z writes).
        @pl.when(i == 0)
        def _():
            oPe[0:1, 1:-1, 1:-1] = pef[1:-1, 1:-1][None]
            ophi[0:1, 1:-1, 1:-1] = phif[1:-1, 1:-1][None]

        @pl.when(i == nb - 1)
        def _():
            oPe[bx - 1:bx, 1:-1, 1:-1] = pel[1:-1, 1:-1][None]
            ophi[bx - 1:bx, 1:-1, 1:-1] = phil[1:-1, 1:-1][None]

        # y halo rows (full x extent; z edges overwritten below).
        if wrap_y:
            for o in (oPe, ophi):
                o[:, 0:1, 1:-1] = o[:, S1 - 2:S1 - 1, 1:-1]
                o[:, S1 - 1:S1, 1:-1] = o[:, 1:2, 1:-1]
        else:
            for o, f, l in ((oPe, y_in[0], y_in[1]), (ophi, y_in[2], y_in[3])):
                o[:, 0:1, 1:-1] = jnp.expand_dims(f[:, 1:-1], 1)
                o[:, S1 - 1:S1, 1:-1] = jnp.expand_dims(l[:, 1:-1], 1)
        # z halo columns (own all shared corners).
        if wrap_z:
            for o in (oPe, ophi):
                o[:, :, 0:1] = o[:, :, S2 - 2:S2 - 1]
                o[:, :, S2 - 1:S2] = o[:, :, 1:2]
        else:
            for o, f, l in ((oPe, z_in[0], z_in[1]), (ophi, z_in[2], z_in[3])):
                o[:, :, 0:1] = jnp.expand_dims(f[:], 2)
                o[:, :, S2 - 1:S2] = jnp.expand_dims(l[:], 2)

        # Compact boundary slabs of the assembled outputs for the recv-mode
        # dims (consumed by the slab-carry loop); z TRANSPOSED (bx,3,S1).
        if y_out:
            y_out[0][:] = oPe[:, 0:3, :]
            y_out[1][:] = oPe[:, S1 - 3:S1, :]
            y_out[2][:] = ophi[:, 0:3, :]
            y_out[3][:] = ophi[:, S1 - 3:S1, :]
        if z_out:
            for j in range(3):
                z_out[0][:, j, :] = oPe[:, :, j]
                z_out[1][:, j, :] = oPe[:, :, S2 - 3 + j]
                z_out[2][:, j, :] = ophi[:, :, j]
                z_out[3][:, j, :] = ophi[:, :, S2 - 3 + j]

    return kernel


def _call_kernel(Pe, phi, recvs, kw_core, bx, interpret, wrap_yz,
                 emit_slabs: bool = True):
    """pallas_call plumbing: returns `(Pe', phi', *slabs)` where `slabs` are
    the recv-mode boundary-slab outputs in (y: pe_lo, pe_hi, phi_lo, phi_hi;
    z: same transposed) order — wrap dims emit none, and `emit_slabs=False`
    (the single-step entry) emits none at all."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    s = Pe.shape
    S0, S1, S2 = s
    nb = S0 // bx
    wy, wz = wrap_yz
    # Squeeze the engine's keepdims recv planes at the kernel boundary.
    rq = [{d: (jnp.squeeze(a, d), jnp.squeeze(b, d))
           for d, (a, b) in r.items()} for r in recvs]

    kern = _make_kernel(wy, wz, kw_core, bx, nb, emit_slabs)
    kwargs = {}
    if not interpret:
        from jax.experimental.pallas import tpu as pltpu
        kwargs["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(bx, S1, S2))

    operands, in_specs = [], []
    for F in (Pe, phi):
        for r in (-1, "c", bx):
            operands.append(F)
            if r == "c":
                in_specs.append(pl.BlockSpec((bx, S1, S2),
                                             lambda i: (i, 0, 0)))
            else:
                in_specs.append(pl.BlockSpec(
                    (1, S1, S2), lambda i, rr=r: ((i * bx + rr) % S0, 0, 0)))
    plane_x = pl.BlockSpec((S1, S2), lambda i: (0, 0))
    operands += [rq[0][0][0], rq[1][0][0], rq[0][0][1], rq[1][0][1]]
    in_specs += [plane_x] * 4
    if not wy:
        operands += [rq[0][1][0], rq[0][1][1], rq[1][1][0], rq[1][1][1]]
        in_specs += [pl.BlockSpec((bx, S2), lambda i: (i, 0))] * 4
    if not wz:
        operands += [rq[0][2][0], rq[0][2][1], rq[1][2][0], rq[1][2][1]]
        in_specs += [pl.BlockSpec((bx, S1), lambda i: (i, 0))] * 4

    vmas = [getattr(getattr(x, "aval", None), "vma", None) for x in operands]
    vma = frozenset().union(*[v for v in vmas if v])

    def shp(*dims):
        return (jax.ShapeDtypeStruct(dims, Pe.dtype, vma=vma) if vma
                else jax.ShapeDtypeStruct(dims, Pe.dtype))

    out_shape = [shp(S0, S1, S2)] * 2
    out_specs = [pl.BlockSpec((bx, S1, S2), lambda i: (i, 0, 0))] * 2
    if emit_slabs and not wy:
        out_shape += [shp(S0, 3, S2)] * 4
        out_specs += [pl.BlockSpec((bx, 3, S2), lambda i: (i, 0, 0))] * 4
    if emit_slabs and not wz:
        out_shape += [shp(S0, 3, S1)] * 4   # transposed z slabs
        out_specs += [pl.BlockSpec((bx, 3, S1), lambda i: (i, 0, 0))] * 4
    return pl.pallas_call(
        kern,
        name="hm3d_step",
        out_shape=tuple(out_shape),
        grid=(nb,),
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        interpret=interpret,
        **kwargs,
    )(*operands)


def _exchange(Pe, phi, slabs, kw, grid, dims_active, wrap_yz):
    from ..halo import exchange_all_dims_grouped

    sends, stales = _sends_and_stale(Pe, phi, slabs, kw, wrap_yz)
    wrap = _wrap_set(wrap_yz)
    return exchange_all_dims_grouped(
        [Pe.shape, phi.shape], sends, [dims_active] * 2, grid,
        stales=stales, wraps=[wrap] * 2, blocks=[Pe, phi])


def fused_hm3d_step(Pe, phi, *, dx, dy, dz, dt, phi0, npow, eta,
                    bx: int = 8, interpret: bool = False):
    """One fused HM3D step `(Pe, phi) -> (Pe', phi')` with halo maintenance
    included, on any mesh (see module docstring).  Call inside SPMD code
    (`igg.sharded` / shard_map); on a 1-device grid the exchange degenerates
    to local copies and the function also works under plain `jax.jit`.  For
    time loops use :func:`fused_hm3d_steps`, which avoids the per-step
    strided slab extraction this entry pays."""
    from .. import shared

    grid = shared.global_grid()
    bx, dims_active = _check_applicable(grid, Pe.shape, bx)
    bx = _fit_bx(bx, *Pe.shape, check_vmem=not interpret)
    if bx < 2:
        raise ValueError(
            f"no slab height divides x size {Pe.shape[0]}"
            + ("" if interpret else
               f" with windows fitting the VMEM budget at y*z area "
               f"{Pe.shape[1]}x{Pe.shape[2]}"))
    kw = dict(dx=dx, dy=dy, dz=dz, dt=dt, phi0=phi0, npow=npow, eta=eta)
    wrap_yz = _wrap_dims(grid)
    slabs = _boundary_slabs(Pe, phi, wrap_yz)
    recvs = _exchange(Pe, phi, slabs, kw, grid, dims_active, wrap_yz)
    Pe2, phi2 = _call_kernel(Pe, phi, recvs, kw, bx, interpret, wrap_yz,
                             emit_slabs=False)
    return Pe2, phi2


def fused_hm3d_steps(Pe, phi, *, n_inner, dx, dy, dz, dt, phi0, npow, eta,
                     bx: int = 8, interpret: bool = False):
    """`n_inner` fused HM3D steps with boundary-slab carry (module
    docstring): the recv-mode y/z slabs feeding each step's send planes are
    emitted by the previous step's kernel, so the steady-state HBM traffic
    per step is the ideal 2 reads + 2 writes + compact slab I/O.  Wrapped
    y/z dims skip sends, slabs, and carry entirely."""
    from jax import lax

    from .. import shared
    from .diffusion_pallas import _self_wrap_all

    grid = shared.global_grid()
    bx, dims_active = _check_applicable(grid, Pe.shape, bx)
    kw = dict(dx=dx, dy=dy, dz=dz, dt=dt, phi0=phi0, npow=npow, eta=eta)
    wrap_yz = _wrap_dims(grid)

    if _self_wrap_all(grid):
        from .hm3d_mega import fused_hm3d_megasteps, hm3d_mega_supported

        # Fastest: the whole inner loop as ONE pallas_call with manual DMA
        # and HBM ping-pong for both fields (see `hm3d_mega`).
        if hm3d_mega_supported(Pe.shape, bx, n_inner, interpret, Pe.dtype):
            return fused_hm3d_megasteps(Pe, phi, n_inner=n_inner, bx=bx,
                                        **kw)

    # Per-step loop path: the slab height must also fit the VMEM budget
    # (the mega branch above sizes its own buffers).
    bx = _fit_bx(bx, *Pe.shape, check_vmem=not interpret)
    if bx < 2:
        raise ValueError(
            f"no slab height divides x size {Pe.shape[0]}"
            + ("" if interpret else
               f" with windows fitting the VMEM budget at y*z area "
               f"{Pe.shape[1]}x{Pe.shape[2]}"))
    init_slabs = _boundary_slabs(Pe, phi, wrap_yz)
    keep = [j for j, sl in enumerate(init_slabs) if sl is not None]

    def body(_, carry):
        Pe, phi = carry[0], carry[1]
        slabs = [None] * 8
        for p, val in zip(keep, carry[2:]):
            slabs[p] = val
        recvs = _exchange(Pe, phi, slabs, kw, grid, dims_active, wrap_yz)
        # _call_kernel returns (Pe', phi', *slabs-in-keep-order)
        return _call_kernel(Pe, phi, recvs, kw, bx, interpret, wrap_yz)

    out = lax.fori_loop(0, n_inner, body,
                        (Pe, phi, *(init_slabs[j] for j in keep)))
    return out[0], out[1]
