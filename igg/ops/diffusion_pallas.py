"""Fused Pallas diffusion step — mesh-capable (any dims / periodicity).

One kernel performs what the XLA path expresses as ~10 separate HBM-bound
fusions (flux/Laplacian temporaries, interior dynamic-update-slice, six halo
plane updates): read T and Cp once, write T once.

Structure (the TPU-native re-expression of the reference's device-kernel
layer, `/root/reference/src/update_halo.jl:439-486`, combined with
ParallelStencil's `@hide_communication` overlap trade,
`/root/reference/README.md:9`):

1. **Send planes from thin-slab recomputation** — the inner boundary planes
   `ol-1` / `s-ol` of the *updated* field
   (`/root/reference/src/update_halo.jl:386-394`) are produced by radius-1
   stencil applications on 3-plane slabs, O(s²) work independent of the main
   kernel (the :func:`igg.hide_communication` recipe).
2. **Dimension-sequential plane exchange** — `igg.halo.exchange_all_dims`,
   the same engine the XLA path uses: ppermute per mesh axis, corner/edge
   propagation by patching pending planes, open-boundary no-write via stale
   planes, self-wrap local copies when a periodic dimension has one device
   (`/root/reference/src/update_halo.jl:36,130,516-532`).
3. **Fused compute + assembly kernel** — each program reads its x-slab of T
   (plus one neighbor plane each side, modular index maps), computes the
   interior update, and writes the output block with the *received* halo
   planes assembled in dimension order (x plane first, y rows, then z
   columns winning the shared corners — the in-VMEM equivalent of
   `igg.halo.assemble_planes`).  HBM traffic per step:
   `T*(1 + 2/bx) + Cp + T_out` + O(s²) plane traffic.

**Slab carry (the multi-step fast path).**  Slicing 3-plane y/z slabs out of
the big array costs far more than their size on TPU — a minor-dim slice
still transfers whole (8,128) tiles, ~an eighth of the array for y and the
*entire* array for z.  :func:`fused_diffusion_steps` therefore carries the
four y/z boundary slabs of the field as separate compact arrays through the
time loop: the kernel emits them as extra outputs (copies of its assembled
output block's edge slabs, a few MB of dense writes), and the next
iteration's send planes are computed from the carried slabs without touching
the big array.  Cp's slabs are loop-invariant and sliced once.

**Compact minor-dim representation (round 3).**  Halo planes travel as
*squeezed* dense 2-D arrays (see `igg.halo`), and the carried z slabs are
stored **transposed** — `(S0, 3, S1)` with z on the sublane axis — because a
`(S0, S1, 3)` array is lane-padded to 128 on TPU (~42x its logical HBM
footprint, per-step I/O measured at ~40x logical size in round 2).  The
kernel emits the transposed slabs directly (an in-kernel lane extraction per
plane), and their send planes are produced by applying the axis-symmetric
stencil with swapped y/z coefficients, which yields the squeezed z plane
`(S0, S1)` with no further transposition.  No lane-padded array of any kind
touches HBM on this path.

Because the send planes are recomputed rather than sliced from the kernel
output, the exchange is data-independent of the main kernel; semantics match
:func:`igg.hide_communication` exactly (identical to the plain sequential
composition on periodic/interior ranks; at open-boundary edge ranks the
physically-meaningless halo cells keep pre-step values).  On a sharded mesh
this is the fused analog of running the XLA path with `overlap=True`.

**Path selection in** :func:`fused_diffusion_steps` (fastest applicable
wins): the K-step mega-kernel (`diffusion_mega`, 1-device grids, every
dim self-wrap or frozen, 0.24 ms/step at 256^3; with x open, a
wavefront of D steps per HBM sweep, 0.93 ms/step at 512^3) > K-step trapezoidal
chunks (`diffusion_trapezoid`, exchanged rings/tori of ANY per-dim
periodicity — periodic dims self-wrapped or extended, open dims extended
with per-device edge freezing (the reference-default boundary condition)
— 0.29 ms/step on the `(N,1,1)` pod decomposition, 0.40 on `(N,M,1)`
with both dims extended; one K-deep slab ppermute pair per exchanged dim
per K steps; where no mesh edge is open, each chunk is one wavefront
sweep of its extended window) > the per-step kernel above (any mesh, 0.52 ms/step;
`benchmarks/results/pallas_sweep.jsonl`).
"""

from __future__ import annotations

from functools import partial


def pallas_supported(grid, T):
    """Whether the fused kernel applies: 3-D unstaggered f32-shaped field
    with overlap 2 in every dimension, local block large enough to slab
    (any device count and any periodicity — the exchange engine handles
    open boundaries and multi-device meshes).  Returns an
    :class:`igg.degrade.Admission` (truthy/falsy) carrying the structured
    refusal reason."""
    from ..degrade import Admission

    if grid.overlaps != (2, 2, 2):
        return Admission.no(f"grid overlaps {grid.overlaps} != (2, 2, 2)")
    if T.ndim != 3:
        return Admission.no(f"field rank {T.ndim} != 3")
    s = tuple(grid.local_shape_any(T))
    if s != tuple(grid.nxyz):
        return Admission.no(f"staggered local shape {s} != grid block "
                            f"{tuple(grid.nxyz)}")
    if s[0] % 4 != 0:
        return Admission.no(f"local x extent {s[0]} not a multiple of 4")
    if s[1] < 8 or s[2] < 128:
        return Admission.no(f"local block {s} too small to slab "
                            f"(needs y >= 8, z >= 128)")
    return Admission.yes()


def diffusion_compute(T, A, *, rdx2, rdy2, rdz2):
    """The pure stencil update on an arbitrary 3-D block: conservative
    7-point-Laplacian interior update, boundary planes keep their stale
    values (the reference's no-write semantics; physics of
    `/root/reference/docs/examples/diffusion3D_multigpu_CuArrays_novis.jl:41-48`,
    flux divergence re-associated — see `igg.models.diffusion3d.compute_step`).
    Shift-invariant and radius-1, so it applies equally to full local blocks
    and to the 3-plane slabs that produce send planes."""
    lap = ((T[2:, 1:-1, 1:-1] + T[:-2, 1:-1, 1:-1]) * rdx2
           + (T[1:-1, 2:, 1:-1] + T[1:-1, :-2, 1:-1]) * rdy2
           + (T[1:-1, 1:-1, 2:] + T[1:-1, 1:-1, :-2]) * rdz2
           - 2.0 * (rdx2 + rdy2 + rdz2) * T[1:-1, 1:-1, 1:-1])
    from .stencil import interior_add

    return interior_add(T, A[1:-1, 1:-1, 1:-1] * lap)


def _u_rows(Tm, T0, Tp, A0, rdx2, rdy2, rdz2):
    # (k,S1,S2) row bands; Tm/Tp are the x-neighbors of T0's rows; A0 is the
    # precomputed dt*lam/Cp coefficient band.
    ctr = T0[:, 1:-1, 1:-1]
    lap = ((Tp[:, 1:-1, 1:-1] + Tm[:, 1:-1, 1:-1]) * rdx2
           + (T0[:, 2:, 1:-1] + T0[:, :-2, 1:-1]) * rdy2
           + (T0[:, 1:-1, 2:] + T0[:, 1:-1, :-2]) * rdz2
           - 2.0 * (rdx2 + rdy2 + rdz2) * ctr)
    return ctr + A0[:, 1:-1, 1:-1] * lap


def _ref_taker(refs):
    """Positional consumer for variadic kernel refs: `take = _ref_taker(refs);
    a, b = take(2)` — shared by the fused kernels' argument unpacking."""
    state = {"pos": 0}

    def take(n):
        out = refs[state["pos"]:state["pos"] + n]
        state["pos"] += n
        return out

    return take


def _make_kernel(wrap_y: bool, wrap_z: bool, scal, bx: int, nb: int):
    """Kernel factory: one x-slab program with per-dimension halo modes.

    Assembly order realizes the reference's sequential-dimension semantics
    (`/root/reference/src/update_halo.jl:36,130`): x halo planes first, then
    y rows, then z columns — later dimensions own the shared corner/edge
    cells, exactly like `igg.halo.assemble_planes`.  Per dimension:

      - x: always plane inputs (they cross program boundaries) — received
        planes on multi-device x, the self-swapped send planes on a single
        periodic device;
      - y/z `wrap` mode (single periodic device along the dim — the
        reference's self-neighbor path,
        `/root/reference/src/update_halo.jl:516-532`): the halo is an
        in-VMEM alias of the updated inner plane.  No (S0,S1,1)-shaped
        z-plane arrays — whose minor-dim lane padding makes their HBM I/O
        cost ~40x their logical size — ever touch HBM, which is why 1-D/2-D
        decompositions `(N,1,1)`/`(N,M,1)` are the recommended meshes;
      - y/z `recv` mode: exchanged planes arrive as blocked inputs.

    No extended-slab concatenate: the update is written in three x-row
    bands whose outer rows take their x-neighbor from the single-plane
    `p`/`n` refs.

    Alias precision on periodic dims: wrap-mode halos are in-VMEM copies of
    their aliased interiors (bitwise equal); x halo planes are computed by
    XLA outside the kernel while their aliased interiors are computed by
    Mosaic inside, so `T_new[0] == T_new[S0-2]` holds to 1 ulp, not bitwise
    (measured max diff 1.5e-8 f32 on v5e; `tests/test_alias_invariant.py`).
    """
    from jax.experimental import pallas as pl

    def kernel(*refs):
        it = iter(refs)
        c_ref, p_ref, n_ref, a_ref = next(it), next(it), next(it), next(it)
        rxf_ref, rxl_ref = next(it), next(it)
        ryf_ref = ryl_ref = rzf_ref = rzl_ref = None
        if not wrap_y:
            ryf_ref, ryl_ref = next(it), next(it)
        if not wrap_z:
            rzf_ref, rzl_ref = next(it), next(it)
        o_ref = next(it)
        oy_lo_ref = oy_hi_ref = oz_lo_ref = oz_hi_ref = None
        if not wrap_y:
            oy_lo_ref, oy_hi_ref = next(it), next(it)
        if not wrap_z:
            oz_lo_ref, oz_hi_ref = next(it), next(it)

        import jax.numpy as jnp

        S1, S2 = c_ref.shape[1], c_ref.shape[2]
        c = c_ref[:]
        a = a_ref[:]
        if bx > 2:
            o_ref[1:bx - 1, 1:-1, 1:-1] = _u_rows(
                c[0:bx - 2], c[1:bx - 1], c[2:bx], a[1:bx - 1], *scal)
        o_ref[0:1, 1:-1, 1:-1] = _u_rows(p_ref[:], c[0:1], c[1:2],
                                         a[0:1], *scal)
        o_ref[bx - 1:bx, 1:-1, 1:-1] = _u_rows(
            c[bx - 2:bx - 1], c[bx - 1:bx], n_ref[:], a[bx - 1:bx], *scal)

        i = pl.program_id(0)

        # x halo planes (squeezed (S1,S2) inputs; interior region only —
        # their y/z edge cells are owned by the later y/z writes below).
        @pl.when(i == 0)
        def _():
            o_ref[0:1, 1:-1, 1:-1] = rxf_ref[1:-1, 1:-1][None]

        @pl.when(i == nb - 1)
        def _():
            o_ref[bx - 1:bx, 1:-1, 1:-1] = rxl_ref[1:-1, 1:-1][None]

        # y halo rows (full x extent; z edges overwritten below).
        if wrap_y:
            o_ref[:, 0:1, 1:-1] = o_ref[:, S1 - 2:S1 - 1, 1:-1]
            o_ref[:, S1 - 1:S1, 1:-1] = o_ref[:, 1:2, 1:-1]
        else:
            o_ref[:, 0:1, 1:-1] = jnp.expand_dims(ryf_ref[:, 1:-1], 1)
            o_ref[:, S1 - 1:S1, 1:-1] = jnp.expand_dims(ryl_ref[:, 1:-1], 1)
        # z halo columns (own all shared corners).  The squeezed (bx,S1)
        # plane is transposed onto the sublane axis in-register.
        if wrap_z:
            o_ref[:, :, 0:1] = o_ref[:, :, S2 - 2:S2 - 1]
            o_ref[:, :, S2 - 1:S2] = o_ref[:, :, 1:2]
        else:
            o_ref[:, :, 0:1] = jnp.expand_dims(rzf_ref[:], 2)
            o_ref[:, :, S2 - 1:S2] = jnp.expand_dims(rzl_ref[:], 2)

        # Boundary slabs of the assembled output for the recv-mode dims,
        # emitted compactly (consumed by the slab-carry loop); wrap dims
        # need no slabs.  z slabs are emitted TRANSPOSED (bx,3,S1) — the
        # natural (bx,S1,3) form would be lane-padded ~42x in HBM.
        if not wrap_y:
            oy_lo_ref[:] = o_ref[:, 0:3, :]
            oy_hi_ref[:] = o_ref[:, S1 - 3:S1, :]
        if not wrap_z:
            for j in range(3):
                oz_lo_ref[:, j, :] = o_ref[:, :, j]
                oz_hi_ref[:, j, :] = o_ref[:, :, S2 - 3 + j]

    return kernel


def _check_applicable(grid, s, bx):
    from ..halo import active_dims

    if bx < 2 or (bx & (bx - 1)) != 0:
        raise ValueError(f"bx must be a power of two >= 2, got {bx}")
    S0 = s[0]
    while S0 % bx != 0:
        bx //= 2  # halving reaches a divisor; S0 % 4 == 0 keeps bx >= 2
    if bx < 2:
        raise ValueError(f"x size {S0} not divisible into slabs of >= 2 rows")
    dims_active = active_dims(s, grid)
    if [d for d, _ in dims_active] != [0, 1, 2]:
        raise ValueError(
            f"fused kernel requires a halo in all three dimensions; active: "
            f"{dims_active}")
    return bx, dims_active


def _wrap_set(wrap_yz):
    """Dim indices handled by in-kernel wrap, for `exchange_all_dims`."""
    return {d for d, w in zip((1, 2), wrap_yz) if w}


def _wrap_dims(grid):
    """Per-dimension halo modes for y/z: `wrap` when the dim is periodic
    with a single device (the self-neighbor path handled in-VMEM).  x always
    goes through the plane exchange — its planes cross program boundaries
    anyway, and they are dense and cheap."""
    return tuple(grid.dims[d] == 1 and bool(grid.periods[d])
                 for d in (1, 2))


def _call_kernel(T, A, recv, scal, bx, interpret, wrap_yz):
    """pallas_call plumbing: returns `(out, *slabs)` where `slabs` are the
    boundary-slab outputs of the recv-mode dims only, in (y_lo, y_hi,
    z_lo, z_hi) order — wrap dims emit none.  The engine's keepdims recv
    planes are squeezed at this boundary (dense 2-D kernel operands; for
    wire-materialized planes the expand/squeeze pair cancels)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    s = T.shape
    S0, S1, S2 = s
    nb = S0 // bx
    wy, wz = wrap_yz
    recv = {d: (jnp.squeeze(a, d), jnp.squeeze(b, d))
            for d, (a, b) in recv.items()}
    rxf, rxl = recv[0]

    scal_t = (scal["rdx2"], scal["rdy2"], scal["rdz2"])
    kern = _make_kernel(wy, wz, scal_t, bx, nb)
    kwargs = {}
    if not interpret:
        from jax.experimental.pallas import tpu as pltpu
        kwargs["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024)

    plane_x = pl.BlockSpec((S1, S2), lambda i: (0, 0))
    operands = [T, T, T, A, rxf, rxl]
    in_specs = [
        pl.BlockSpec((bx, S1, S2), lambda i: (i, 0, 0)),
        pl.BlockSpec((1, S1, S2), lambda i: ((i * bx - 1) % S0, 0, 0)),
        pl.BlockSpec((1, S1, S2), lambda i: ((i * bx + bx) % S0, 0, 0)),
        pl.BlockSpec((bx, S1, S2), lambda i: (i, 0, 0)),
        plane_x,
        plane_x,
    ]
    if not wy:
        operands += list(recv[1])
        in_specs += [pl.BlockSpec((bx, S2), lambda i: (i, 0))] * 2
    if not wz:
        operands += list(recv[2])
        in_specs += [pl.BlockSpec((bx, S1), lambda i: (i, 0))] * 2

    # Under shard_map with varying-mesh-axes checking, out_shapes must carry
    # which axes the results vary over: the union of the operands'.
    vmas = [getattr(getattr(x, "aval", None), "vma", None) for x in operands]
    vma = frozenset().union(*[v for v in vmas if v])

    def shp(*dims):
        return (jax.ShapeDtypeStruct(dims, T.dtype, vma=vma) if vma
                else jax.ShapeDtypeStruct(dims, T.dtype))

    out_shape = [shp(S0, S1, S2)]
    out_specs = [pl.BlockSpec((bx, S1, S2), lambda i: (i, 0, 0))]
    if not wy:
        out_shape += [shp(S0, 3, S2)] * 2
        out_specs += [pl.BlockSpec((bx, 3, S2), lambda i: (i, 0, 0))] * 2
    if not wz:
        out_shape += [shp(S0, 3, S1)] * 2   # transposed z slabs
        out_specs += [pl.BlockSpec((bx, 3, S1), lambda i: (i, 0, 0))] * 2
    return pl.pallas_call(
        kern,
        name="diffusion3d_step",
        out_shape=tuple(out_shape),
        grid=(nb,),
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        interpret=interpret,
        **kwargs,
    )(*operands)


def _scal(dx, dy, dz):
    return dict(rdx2=1.0 / (dx * dx), rdy2=1.0 / (dy * dy),
                rdz2=1.0 / (dz * dz))


def _self_wrap_all(grid) -> bool:
    """All dims periodic with a single device: the reference's single-process
    fully-periodic configuration, where every exchange is the self-neighbor
    path (`/root/reference/src/update_halo.jl:516-532`)."""
    return (tuple(grid.dims) == (1, 1, 1)
            and all(bool(p) for p in grid.periods))


def _single_device_modes(grid):
    """Per-dim mega-kernel halo modes for a 1-device grid ("wrap" periodic
    self-neighbor / "frozen" open no-write — `diffusion_mega` docstring),
    or None when any dimension is split across devices."""
    if tuple(grid.dims) != (1, 1, 1):
        return None
    return tuple("wrap" if grid.periods[d] else "frozen" for d in range(3))


def _sends_and_stale(T, a_slabs, slabs, scal, wrap_yz):
    """Squeezed send planes (updated inner planes `ol-1`/`s-ol`) from compact
    boundary slabs, plus stale (outermost) planes for open-boundary dims — no
    reads of the big array beyond its two cheap contiguous x-end slabs.
    Wrapped y/z dims need neither sends nor slabs.

    z slabs arrive TRANSPOSED (S0,3,S1): the stencil is axis-symmetric, so
    applying it with swapped y/z coefficients produces the transposed update,
    whose middle plane is exactly the squeezed z send plane (S0,S1)."""
    from jax import lax

    s = T.shape
    wy, wz = wrap_yz
    ys_lo, ys_hi, zt_lo, zt_hi = slabs
    ax_lo, ax_hi, ay_lo, ay_hi, azt_lo, azt_hi = a_slabs
    xs_lo = lax.slice_in_dim(T, 0, 3, axis=0)          # contiguous: cheap
    xs_hi = lax.slice_in_dim(T, s[0] - 3, s[0], axis=0)

    # Keepdims planes for the exchange engine (squeezed only on the wire /
    # at the kernel boundary — see `igg.halo`); the lazy expand/squeeze
    # pairs are metadata reshapes that cancel.
    import jax.numpy as jnp

    send = {
        (0, 0): diffusion_compute(xs_lo, ax_lo, **scal)[1:2],
        (0, 1): diffusion_compute(xs_hi, ax_hi, **scal)[1:2],
    }
    stale = {(0, 0): xs_lo[0:1], (0, 1): xs_hi[2:3]}
    if not wy:
        send[(1, 0)] = diffusion_compute(ys_lo, ay_lo, **scal)[:, 1:2, :]
        send[(1, 1)] = diffusion_compute(ys_hi, ay_hi, **scal)[:, 1:2, :]
        stale[(1, 0)] = ys_lo[:, 0:1, :]
        stale[(1, 1)] = ys_hi[:, 2:3, :]
    if not wz:
        swapped = dict(rdx2=scal["rdx2"], rdy2=scal["rdz2"],
                       rdz2=scal["rdy2"])
        send[(2, 0)] = jnp.expand_dims(
            diffusion_compute(zt_lo, azt_lo, **swapped)[:, 1, :], 2)
        send[(2, 1)] = jnp.expand_dims(
            diffusion_compute(zt_hi, azt_hi, **swapped)[:, 1, :], 2)
        stale[(2, 0)] = jnp.expand_dims(zt_lo[:, 0, :], 2)
        stale[(2, 1)] = jnp.expand_dims(zt_hi[:, 2, :], 2)
    return send, stale


def _boundary_slabs(A, wrap_yz):
    """The y/z 3-plane boundary slabs of a block for the recv-mode dims
    (one-time strided extraction; thereafter the kernel re-emits them
    compactly, z TRANSPOSED to (S0,3,S1) to stay dense); `None` placeholders
    for wrapped dims — the expensive minor-dim slices are skipped entirely
    there."""
    import jax.numpy as jnp
    from jax import lax

    s = A.shape
    wy, wz = wrap_yz
    ys = (None, None) if wy else (
        lax.slice_in_dim(A, 0, 3, axis=1),
        lax.slice_in_dim(A, s[1] - 3, s[1], axis=1))
    zs = (None, None) if wz else (
        jnp.swapaxes(lax.slice_in_dim(A, 0, 3, axis=2), 1, 2),
        jnp.swapaxes(lax.slice_in_dim(A, s[2] - 3, s[2], axis=2), 1, 2))
    return (*ys, *zs)


def _coef_slabs(A, wrap_yz):
    from jax import lax

    s = A.shape
    return (lax.slice_in_dim(A, 0, 3, axis=0),
            lax.slice_in_dim(A, s[0] - 3, s[0], axis=0),
            *_boundary_slabs(A, wrap_yz))


def fused_diffusion_step(T, Cp, *, dx, dy, dz, dt, lam, bx: int = 16,
                         interpret: bool = False):
    """One diffusion step `(T, Cp) -> T_new` on a per-device *local* block,
    halo maintenance included.  Call inside SPMD code (`igg.sharded` /
    shard_map) like :func:`igg.update_halo_local`; on a 1-device grid the
    exchange degenerates to local copies and the function also works under
    plain `jax.jit`.  For time loops use :func:`fused_diffusion_steps`,
    which avoids the per-step strided slab extraction this entry pays."""
    from ..halo import exchange_all_dims
    from .. import shared

    grid = shared.global_grid()
    bx, dims_active = _check_applicable(grid, T.shape, bx)
    scal = _scal(dx, dy, dz)
    A = float(dt * lam) / Cp   # loop-invariant coefficient (no in-loop divide)
    wrap_yz = _wrap_dims(grid)
    send, stale = _sends_and_stale(T, _coef_slabs(A, wrap_yz),
                                   _boundary_slabs(T, wrap_yz), scal,
                                   wrap_yz)
    recv = exchange_all_dims(T, send, dims_active, grid, stale=stale,
                             wrap=_wrap_set(wrap_yz))
    return _call_kernel(T, A, recv, scal, bx, interpret, wrap_yz)[0]


def fused_diffusion_steps(T, Cp, *, n_inner, dx, dy, dz, dt, lam,
                          bx: int = 16, interpret: bool = False):
    """`n_inner` fused diffusion steps with boundary-slab carry (see module
    docstring): the y/z slabs feeding each step's send planes are emitted by
    the previous step's kernel, so the steady-state HBM traffic per step is
    `T*(1 + 2/bx) + Cp + T_out` + a few MB of compact slab I/O.  Wrapped y/z
    dims (single periodic device) skip sends, slabs, and carry entirely.
    Call inside SPMD code; returns the advanced block."""
    from jax import lax

    from ..halo import exchange_all_dims
    from .. import shared

    grid = shared.global_grid()
    bx, dims_active = _check_applicable(grid, T.shape, bx)
    scal = _scal(dx, dy, dz)
    A = float(dt * lam) / Cp   # loop-invariant coefficient (no in-loop divide)
    wrap_yz = _wrap_dims(grid)

    modes = _single_device_modes(grid)
    if modes is not None:
        from .diffusion_mega import fused_diffusion_megasteps, mega_supported

        # Fastest: the whole inner loop as ONE pallas_call, coefficient
        # VMEM-resident when it fits and slab-streamed otherwise; open
        # dims run the frozen-edge mode, and an open x the D-step
        # wavefront (see `diffusion_mega` — this is the reference's
        # published 510^3 open-boundary headline path).
        if mega_supported(T.shape, bx, n_inner, interpret, dtype=T.dtype):
            return fused_diffusion_megasteps(T, A, n_inner=n_inner, bx=bx,
                                             **scal, modes=modes)

    # Exchanged meshes — (N,1,1)/(N,M,1)/(N,M,K) rings and tori with any
    # per-dim periodicity (periodic dims self-wrapped or extended, OPEN
    # dims extended with per-device edge freezing — the reference-default
    # boundary condition, round 6): K-step trapezoidal chunks, one K-deep
    # slab ppermute pair per exchanged dim per K steps, the loop fused
    # in-kernel (see `diffusion_trapezoid`).  One per-step kernel step
    # runs FIRST: it consumes (and replaces) whatever is in the entry
    # halo rows exactly like every other path, establishing the
    # exchange-fresh window state the trapezoid's validity argument
    # requires — so this path is bit-equivalent to the per-step path for
    # ANY input, including never-exchanged arrays.  Remainder steps fall
    # through to the per-step loop below.
    from .diffusion_trapezoid import (fused_diffusion_trapezoid_steps,
                                      trapezoid_supported)
    if trapezoid_supported(grid, T.shape, bx, n_inner - 1, T.dtype,
                           allow_open=True):
        T = fused_diffusion_step(T, Cp, dx=dx, dy=dy, dz=dz, dt=dt,
                                 lam=lam, bx=bx, interpret=interpret)
        n_inner -= 1
        T, done = fused_diffusion_trapezoid_steps(
            T, A, n_inner=n_inner, bx=bx, grid=grid, interpret=interpret,
            **scal)
        n_inner -= done
        if n_inner == 0:
            return T

    a_slabs = _coef_slabs(A, wrap_yz)  # loop-invariant: sliced once
    init_slabs = _boundary_slabs(T, wrap_yz)
    keep = [j for j, sl in enumerate(init_slabs) if sl is not None]

    def body(_, carry):
        T = carry[0]
        slabs = [None] * 4
        for pos, val in zip(keep, carry[1:]):
            slabs[pos] = val
        send, stale = _sends_and_stale(T, a_slabs, slabs, scal, wrap_yz)
        recv = exchange_all_dims(T, send, dims_active, grid, stale=stale,
                                 wrap=_wrap_set(wrap_yz))
        # _call_kernel returns (out, *slabs-in-keep-order)
        return _call_kernel(T, A, recv, scal, bx, interpret, wrap_yz)

    out = lax.fori_loop(0, n_inner, body,
                        (T, *(init_slabs[j] for j in keep)))
    return out[0]
