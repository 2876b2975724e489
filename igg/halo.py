"""Halo-exchange engine — the performance core.

TPU-native re-design of `/root/reference/src/update_halo.jl`.  The reference's
machinery (persistent send/recv buffer pools, pinned host memory, CUDA
pack/unpack kernels, max-priority streams, MPI Isend/Irecv) collapses on TPU
into a single XLA program per call signature:

    pack   = plane slice of the boundary plane         (fused by XLA)
    send   = lax.ppermute shift along a mesh axis      (ICI collective-permute)
    unpack = aligned in-place slab updates, or one fused masked-select pass

Halos never touch the host; buffer management is XLA's job (donated inputs
make the update effectively in-place in HBM, matching the reference's
mutate-in-place semantics with zero extra copies).

**Plane representation (round 3).**  Internally, planes are rank-preserving
lazy slices (size 1 along the exchanged dimension) patched in masked-select
form, so plane algebra (corner propagation, open-boundary fallbacks) stays
in rank- and layout-homogeneous XLA fusions (a materialized keepdims
`(S0,S1,1)` plane is lane-padded up to ~40x).  Planes are squeezed to dense
2-D arrays (the reference's `halosize(dim,A)` shape,
`/root/reference/src/update_halo.jl:80`) only at the collective wire, where
they must materialize anyway — so ppermute traffic and multi-field stacking
move logical bytes, and nothing lane-padded ever reaches HBM or the ICI
links.

**Assembly strategies** (chosen per call signature by a static plan):
  - *in-place Pallas writers* (`igg.ops.halo_write`, TPU compiled mode —
    the default): partial-grid `pallas_call`s with the block aliased
    in-place that touch ONLY the dirty tiles.  When the lane dimension
    participates, that is one full RMW pass (the tile-granularity floor —
    see `igg/ops/halo_write.py` for the roofline argument); otherwise the
    dim-0/dim-1 slab writers touch a few MB.  Deterministic — XLA's layout
    assignment for the equivalent HLO is a compile lottery (171-516 us for
    the identical xyz update across surrounding-code variations, and
    grouped multi-field calls went superlinear); the writers pin it at
    203/102 us (f32/bf16 xyz at 256^3), ~22 us xy, cost strictly linear in
    the field count.  Self-wrap (single-device periodic) y/z sources are
    read from the block inside VMEM, so their planes never materialize.
  - *aligned-DUS* (XLA fallback — CPU meshes, rank != 3, unaligned or
    small shapes): per-dimension in-place updates — full planes along
    untiled (major) dimensions, tile-aligned slab read-modify-writes along
    the sublane/lane dimensions, performed in place on donated buffers.
  - *masked-select* (last resort, same fallbacks): ONE fused pass writing
    the whole block with received planes selected in (`jnp.where` on
    `broadcasted_iota`), in dimension order.

**Pair-emulated dtypes (round 5)** — f64 (the reference's Julia default),
int64, complex: the XLA plans are chosen by op-mix ('select' one-pass for
lane-active halo sets, all-DUS 'dus64' otherwise; `_assembly_plan`), the
received planes are fenced with `optimization_barrier` before assembly
(`_materialize_planes` — without the fence, copy-insertion charges
full-block defensive copies), and non-lane fields take the sequential
per-dim exchange+assemble form (`exchange_assemble_sequential` — the
reference's literal control flow, corner propagation for free).  Measured
at 256³/field: 519 µs xyz (2.49× the f32 writer for 2× the bytes), 53 µs
xy (2.1× the f32 slab writers).

The reference meets the same wall on GPUs — its maximally-strided dim-1
plane gets a dedicated custom kernel (`/root/reference/src/update_halo.jl:
439-462`); on TPU the tiled layout moves that worst case to the lane (minor)
dimension (the writer above), and the pack side of it is handled by a
Pallas one-pass plane extractor (`igg.ops.pack`, used for multi-plane
minor-dim sends where XLA materializes each plane in a separate relayout
pass — measured 491 us vs 92 us for the 4-plane y+z pack at 256^3).

Preserved reference semantics:
  - exactly one boundary plane is exchanged per side per dimension:
    send plane `ol-1` (left) / `s-ol` (right) (0-based; reference
    `/root/reference/src/update_halo.jl:386-394`), receive into plane `0` /
    `s-1` (`:397-405`);
  - per-array staggered overlap `ol(dim, A) = overlaps[dim] + (s_d - n_d)`
    (`/root/reference/src/shared.jl:81`); a dimension participates only when
    `ol >= 2` (`/root/reference/src/update_halo.jl:284`);
  - dimensions are exchanged **sequentially** (x, then y, then z) so corner
    and edge values propagate without diagonal messages
    (`/root/reference/src/update_halo.jl:36,130`);
  - open (non-periodic) boundaries: edge halos are simply not written
    (`/root/reference/test/test_update_halo.jl:727-732`) — realized here with
    `axis_index` masks instead of MPI_PROC_NULL neighbors;
  - periodic with one device along a dimension: a pure local copy, the analog
    of the reference's self-neighbor path
    (`/root/reference/src/update_halo.jl:516-532`).
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

from . import profiling, shared
from .fields import spec_for
from .shared import AXIS_NAMES, NDIMS, GridError


# Compiled update programs keyed by (grid epoch, per-field (shape, dtype)).
# The analog of the reference's grow-only buffer pool keyed by field count and
# dtype (`/root/reference/src/update_halo.jl:86-255`): it exists so the hot
# loop never re-traces/re-allocates.
_compiled: Dict[tuple, object] = {}

# Test seam: force the Pallas-writer assembly path (interpret mode) on
# non-TPU meshes, so the engine-side spec building (wrap/ext classification,
# squeeze axes, recv wiring) is exercised by the CPU suite.
_FORCE_WRITER_INTERPRET = False

# Test seam: engage the stacked lane-active pair-emulated group update
# (`_stacked_lane64_update`) on non-TPU meshes too — on CPU the dtypes are
# native (no pair emulation) but the stacked program is dtype-agnostic, so
# the CPU suite pins its plane wiring/corner propagation for equivalence.
_FORCE_STACKED64 = False

# Fault-injection seam (igg.chaos.halo_corruption): a callable
# `(d, first, last) -> (first, last)` applied to the planes
# `exchange_planes` returns — the single primitive every wire path (grouped,
# sequential, stacked-64) funnels through — so received-halo corruption is
# injectable deterministically for the resilience test matrix.  Read at
# TRACE time: installers must clear the compiled caches (igg.chaos does).
_CHAOS_PLANE_TAP = None


def _chaos_tap(d: int, first, last):
    tap = _CHAOS_PLANE_TAP
    if tap is None:
        return first, last
    return tap(d, first, last)


def free_update_halo_buffers() -> None:
    """Drop all compiled halo programs (reference
    `/root/reference/src/update_halo.jl:95-107`)."""
    _compiled.clear()


# ---------------------------------------------------------------------------
# Argument checking (`/root/reference/src/update_halo.jl:574-604`)
# ---------------------------------------------------------------------------

def check_fields(grid, fields, local_shapes) -> None:
    no_halo = [
        i for i, (A, s) in enumerate(zip(fields, local_shapes))
        if all(grid.ol_of_local(d, s) < 2 for d in range(min(A.ndim, NDIMS)))
    ]
    if len(no_halo) > 1:
        raise GridError(
            f"The fields at positions {', '.join(map(str, no_halo))} have no "
            f"halo; remove them from the call.")
    if no_halo:
        raise GridError(
            f"The field at position {no_halo[0]} has no halo; remove it from "
            f"the call.")

    dups = [(i, j) for i in range(len(fields)) for j in range(i + 1, len(fields))
            if fields[i] is fields[j]]
    if dups:
        i, j = dups[0]
        raise GridError(
            f"The field at position {j} is a duplicate of the one at the "
            f"position {i}; remove the duplicate from the call.")

    diff = [i for i in range(1, len(fields))
            if fields[i].dtype != fields[0].dtype]
    if diff:
        raise GridError(
            f"The field at position {diff[0]} is of different type than the "
            f"first field; make sure that in a same call all fields are of "
            f"the same type.")


# ---------------------------------------------------------------------------
# Plane primitives
#
# Internally, planes are RANK-PRESERVING lazy slices (size 1 along the
# exchanged dimension): every plane-consuming op is then rank- and
# layout-homogeneous with the block, so XLA keeps the whole update in
# default-layout fusions.  Handing XLA rank-2 (squeezed) plane arrays makes
# its layout assignment pick transposed layouts for the surrounding fusions
# and pay whole-array relayout copies each iteration (measured: 560 us
# instead of 160 us at 256^3 f32).  Planes are squeezed ONLY at the
# collective wire (see `_wire_exchange`) — a keepdims (S,S,1) array is
# lane-padded up to ~40x on TPU, so the padded form must never be
# materialized (and never ride the ICI links).
# ---------------------------------------------------------------------------

def _plane(A, d: int, i: int):
    """Rank-preserving boundary plane (size 1 along `d`); the squeezed shape
    is the reference's `halosize(dim,A)`
    (`/root/reference/src/update_halo.jl:80`)."""
    from jax import lax
    return lax.slice_in_dim(A, i, i + 1, axis=d)


def _put_row(P, row, axis: int, i: int, form: str = "where"):
    """Row substitution in a pending plane.  Default masked-select form
    rather than dynamic-update-slice: the result stays a lazy elementwise
    expression over `P` and `row`, so plane patches fuse into whatever
    consumes the plane.  A DUS here forces the (possibly lazily-sliced)
    plane to materialize, and materializing a minor-dim plane is a
    relayout pass over the source tiles — measured ~90 us per plane pair
    at 256^3 f32, turning a 160 us update into 560 us.

    `form="dus"` is for pair-emulated 8/16-byte dtypes on the all-DUS
    'dus64' assembly plan, where the rule is reversed: ONE select
    anywhere in the x64/complex-rewritten graph drags every in-place
    update into defensive pair-split copies (a 441-vs-134 us engine
    regression at 256^3 f64 x+y), while plane-level DUS is native data
    movement there — and dus64 planes must materialize for the wire and
    the block-level DUS anyway, so nothing is lost to the forced
    materialization.  See `_patch_form`."""
    import jax.numpy as jnp
    from jax import lax

    if form == "dus":
        return lax.dynamic_update_slice_in_dim(P, row, i, axis=axis)
    idx = lax.broadcasted_iota(jnp.int32, P.shape, axis)
    return jnp.where(idx == i, row, P)


def _patch_form(shape, dtype, dims, on_tpu: bool) -> str:
    """Corner-patch form matched to the field's assembly plan, so the
    pair-emulated graphs stay homogeneous (`_assembly_plan` docstring):
    'dus' exactly when the field takes the all-DUS 'dus64' plan."""
    return ("dus" if _assembly_plan(shape, dtype, dims, on_tpu) == "dus64"
            else "where")


def active_dims(shape, grid) -> List[Tuple[int, int]]:
    """The (dim, ol) pairs of a local block's shape that have a halo
    (per-array staggered overlap `ol >= 2`,
    `/root/reference/src/update_halo.jl:284`)."""
    return [(d, grid.ol_of_local(d, shape))
            for d in range(min(len(shape), NDIMS))
            if grid.ol_of_local(d, shape) >= 2]


def moving_dims(dims_active, grid) -> List[Tuple[int, int]]:
    """The subset of `dims_active` along which halo planes actually change:
    a dimension with one device and an open boundary never receives anything
    (both global edges live on the same device — the reference's
    `has_neighbor` returning false on both sides), so the verb-level update
    can skip it entirely: the block already holds the stale planes."""
    return [(d, ol) for d, ol in dims_active
            if grid.dims[d] > 1 or grid.periods[d]]


# ---------------------------------------------------------------------------
# Exchange (operates on per-device squeezed planes)
# ---------------------------------------------------------------------------

def exchange_planes(left_send, right_send, stale_first, stale_last,
                    d: int, n: int, periodic: bool, disp: int = 1):
    """Plane-level neighbor shift along mesh axis `d`: returns the
    (new_first, new_last) halo planes of the local block.

    Open-boundary edge devices receive zeros from the (non-wrapping) permute;
    the stale planes are returned there instead — the reference's no-write
    semantics (`/root/reference/test/test_update_halo.jl:727-732`).  With one
    device along the axis, periodic exchange degenerates to a pure local copy
    (self-neighbor path, `/root/reference/src/update_halo.jl:516-532`).

    `disp` is the Cartesian neighbor displacement: partners are the ranks
    `disp` steps away, the semantics `MPI.Cart_shift` gives the reference's
    neighbor table (`/root/reference/src/init_global_grid.jl:78-81`) —
    realized here as ppermute shift tables with stride `disp` (and, when
    `disp` is a multiple of a periodic axis size, the degenerate self-copy).
    """
    import jax.numpy as jnp
    from jax import lax

    axis = AXIS_NAMES[d]
    if periodic and disp % n == 0:
        # Every rank is its own partner (n == 1, or disp wrapping onto
        # itself): a pure local copy, no collective.
        return _chaos_tap(d, right_send, left_send)
    if not periodic and disp >= n:
        # No rank has a partner `disp` steps away inside an open axis
        # (includes the open n == 1 case).
        return _chaos_tap(d, stale_first, stale_last)

    shift_down = ([(i, i - disp) for i in range(disp, n)]
                  + ([(i, (i - disp) % n) for i in range(min(disp, n))]
                     if periodic else []))
    shift_up = ([(i, i + disp) for i in range(n - disp)]
                + ([(i, (i + disp) % n) for i in range(max(n - disp, 0), n)]
                   if periodic else []))
    from_right = lax.ppermute(left_send, axis, shift_down)   # right nb's inner plane
    from_left = lax.ppermute(right_send, axis, shift_up)     # left nb's inner plane
    if periodic:
        return _chaos_tap(d, from_left, from_right)
    idx = lax.axis_index(axis)
    return _chaos_tap(d,
                      jnp.where(idx >= disp, from_left, stale_first),
                      jnp.where(idx < n - disp, from_right, stale_last))


def _wire_exchange(members, sends, stales, d: int, n: int, periodic: bool,
                   disp: int = 1):
    """Exchange dim `d` for a group of same-plane-shape fields: planes are
    SQUEEZED for the wire (dense logical bytes — the keepdims form is
    lane-padded up to ~40x) and, for several fields, stacked so ONE
    `ppermute` per side serves the whole group; received planes are
    re-expanded to keepdims (a metadata reshape that fuses/cancels).
    With one device along the axis nothing materializes — the lazy keepdims
    planes pass straight through (self-neighbor/no-write paths)."""
    import jax.numpy as jnp

    if n == 1:
        return [exchange_planes(sends[i][(d, 0)], sends[i][(d, 1)],
                                stales[i][(d, 0)], stales[i][(d, 1)],
                                d, n, periodic, disp)
                for i in members]

    def squeeze(P):
        return None if P is None else jnp.squeeze(P, axis=d)

    if len(members) == 1:
        i = members[0]
        nf_, nl_ = exchange_planes(
            squeeze(sends[i][(d, 0)]), squeeze(sends[i][(d, 1)]),
            squeeze(stales[i][(d, 0)]), squeeze(stales[i][(d, 1)]),
            d, n, periodic, disp)
        return [(jnp.expand_dims(nf_, d), jnp.expand_dims(nl_, d))]

    ls = jnp.stack([squeeze(sends[i][(d, 0)]) for i in members])
    rs = jnp.stack([squeeze(sends[i][(d, 1)]) for i in members])
    if periodic:
        sf = sl = None
    else:
        sf = jnp.stack([squeeze(stales[i][(d, 0)]) for i in members])
        sl = jnp.stack([squeeze(stales[i][(d, 1)]) for i in members])
    nf_, nl_ = exchange_planes(ls, rs, sf, sl, d, n, periodic, disp)
    return [(jnp.expand_dims(nf_[k], d), jnp.expand_dims(nl_[k], d))
            for k in range(len(members))]


def _patch_pending(store, key, d: int, s, val_first, val_last, pos: int,
                   form: str = "where"):
    """Overwrite the edge rows along exchanged dimension `d` of a pending
    plane of a *later* dimension `d2 = key[0]` (`d < d2`) with the received
    planes' values at that plane's position `pos` — the sequential
    corner/edge propagation of `/root/reference/src/update_halo.jl:36,130`.
    All keepdims: the patch rows are size 1 along both `d` and `d2`."""
    P = store.get(key)
    if P is None:
        return
    d2 = key[0]
    P = _put_row(P, _plane(val_first, d2, pos), d, 0, form)
    P = _put_row(P, _plane(val_last, d2, pos), d, s[d] - 1, form)
    store[key] = P


def exchange_all_dims(A, send: Dict, dims_active, grid,
                      stale: Dict = None, wrap=()) -> Dict:
    """Dimension-sequential plane-level exchange with corner/edge propagation
    for ONE field.  `send[(d, side)]` are the packed KEEPDIMS send planes
    (size 1 along `d`; squeezing for the collective wire is internal);
    returns `recv[d] = (new_first, new_last)` keepdims halo planes per
    active dimension.  See :func:`exchange_all_dims_grouped` for the
    semantics; this wrapper is the single-field form used by the fused
    kernels and :func:`igg.hide_communication`."""
    recvs = exchange_all_dims_grouped(
        [A.shape], [send], [dims_active], grid,
        stales=[stale], wraps=[wrap], blocks=[A])
    return recvs[0]


@profiling.scoped(profiling.HALO)
def exchange_all_dims_grouped(shapes, sends, dims_actives, grid,
                              stales=None, wraps=None,
                              blocks=None) -> List[Dict]:
    """Dimension-sequential plane exchange for several fields at once, with
    corner/edge propagation.  All planes in and out are KEEPDIMS (size 1
    along their dimension); squeezing happens only on the collective wire.

    Equivalence with the reference's sequential per-dimension update of the
    full array (`/root/reference/src/update_halo.jl:36,130`): what later
    dimensions see of the dimensions already exchanged is the received halo
    values inside their edge rows — so after each dimension's exchange, the
    *pending* send planes AND the pending stale (open-boundary fallback)
    planes of all later dimensions get their edge rows overwritten with the
    received/stale result.  The caller must assemble the returned planes in
    dimension order (later dimensions win the shared corner/edge cells, like
    the reference's later exchanges overwrite them).

    Dims in a field's `wrap` set (single periodic device, halo assembled by
    the caller — e.g. in-VMEM by the fused Pallas kernel) are not exchanged
    and need no send planes; their contribution to the sequential semantics
    is the self-alias patch: later dims' pending planes get the wrapped halo
    rows, which are aliases of the plane's own inner rows.

    Multi-field grouping: fields whose planes share a shape are exchanged
    with ONE `ppermute` per (dim, side) — their planes squeezed for the wire
    and stacked along a new leading axis (dense, so the stack moves logical
    bytes only).  This is the TPU analog of the reference's grouped-call
    pipelining note (`/root/reference/src/update_halo.jl:19-20`) with the
    collective count made independent of the field count.

    `blocks[i]`, when given, supplies the source array for any stale planes
    not already present in `stales[i]` (open-boundary fallbacks).
    """
    nf = len(shapes)
    sends = [dict(s) for s in sends]
    stales = [dict(st) if st else {} for st in (stales or [None] * nf)]
    wraps = [frozenset(w or ()) for w in (wraps or [()] * nf)]

    # Corner-patch form per field, matched to its assembly plan so the
    # pair-emulated 8/16-byte graphs stay homogeneous (`_patch_form`).
    on_tpu = _is_tpu(grid)
    forms = []
    for i in range(nf):
        P = next(iter(sends[i].values()), None)
        dt = P.dtype if P is not None else (
            blocks[i].dtype if blocks is not None else None)
        forms.append("where" if dt is None else _patch_form(
            shapes[i], dt, [d for d, _ in dims_actives[i]], on_tpu))

    # Stale planes: what an open-boundary edge device keeps (the reference's
    # no-write semantics, `/root/reference/test/test_update_halo.jl:727-732`).
    # Extracted lazily from the block only for non-periodic dims — periodic
    # exchanges never read them.
    for i in range(nf):
        s = shapes[i]
        for d, ol in dims_actives[i]:
            if d in wraps[i] or grid.periods[d]:
                stales[i][(d, 0)] = stales[i][(d, 1)] = None
            else:
                for side, pos in ((0, 0), (1, s[d] - 1)):
                    if (d, side) not in stales[i]:
                        stales[i][(d, side)] = _plane(blocks[i], d, pos)

    all_dims = sorted({d for da in dims_actives for d, _ in da})
    recvs: List[Dict] = [{} for _ in range(nf)]
    for d in all_dims:
        fidx = [i for i in range(nf) if d in [x for x, _ in dims_actives[i]]]
        wrap_f = [i for i in fidx if d in wraps[i]]
        exch_f = [i for i in fidx if d not in wraps[i]]

        # Wrap dims (caller-assembled self-alias): patch every later pending
        # plane's edge rows with the plane's own inner (send-position) rows.
        for i in wrap_f:
            s = shapes[i]
            ol = dict(dims_actives[i])[d]
            later = [d2 for d2, _ in dims_actives[i] if d2 > d
                     and d2 not in wraps[i]]
            for d2 in later:
                for side2 in (0, 1):
                    for store in (sends[i], stales[i]):
                        P = store.get((d2, side2))
                        if P is None:
                            continue
                        P = _put_row(P, _plane(P, d, s[d] - ol), d, 0,
                                     forms[i])
                        P = _put_row(P, _plane(P, d, ol - 1), d, s[d] - 1,
                                     forms[i])
                        store[(d2, side2)] = P

        if not exch_f:
            continue

        # One collective per (dim, side) for all same-shaped planes
        # (squeezed + stacked on the wire; see `_wire_exchange`).
        n = grid.dims[d]
        periodic = bool(grid.periods[d])
        groups: Dict[tuple, List[int]] = {}
        for i in exch_f:
            P = sends[i][(d, 0)]
            groups.setdefault((tuple(P.shape), str(P.dtype)), []).append(i)
        for shape_key, members in groups.items():
            per_field = _wire_exchange(members, sends, stales, d, n, periodic,
                                       getattr(grid, "disp", 1))
            for i, (new_first, new_last) in zip(members, per_field):
                recvs[i][d] = (new_first, new_last)
                s = shapes[i]
                for d2, ol2 in dims_actives[i]:
                    if d2 <= d or d2 in wraps[i]:
                        continue
                    for side2, p_send, p_stale in ((0, ol2 - 1, 0),
                                                   (1, s[d2] - ol2,
                                                    s[d2] - 1)):
                        _patch_pending(sends[i], (d2, side2), d, s,
                                       new_first, new_last, p_send,
                                       forms[i])
                        _patch_pending(stales[i], (d2, side2), d, s,
                                       new_first, new_last, p_stale,
                                       forms[i])
    return recvs


def _pair_emulated(dtype) -> bool:
    """8/16-byte dtypes the XLA:TPU x64/complex rewriters emulate as pairs
    of 32-bit arrays (f64, i64/u64, complex64, complex128)."""
    import numpy as np

    return np.dtype(dtype).itemsize >= 8


def plane_bytes_by_mode(local_shapes, dtypes, grid
                        ) -> Dict[Tuple[str, str], int]:
    """The `igg_halo_plane_bytes_total` accounting of one `update_halo`
    call, broken down by ``(dim, mode)``: per field and per moving dim,
    two boundary planes per device (each exchanged plane counted once),
    summed over the mesh.  `mode` is ``{wire|local}_{grouped|stacked}``:

    - *wire* — the dim is split across devices, so the planes ride the
      collective (ICI links on a real slice); *local* — single-device
      periodic self-wrap, pure HBM traffic;
    - *stacked* — the field rides the pair-emulated lane-active group
      program (`_stacked_lane64_update`: >= 2 same-shaped 8/16-byte
      fields through ONE stacked block); *grouped* — every other engine
      path (grouped pre-extracted, sequential per-dim, and the Pallas
      writers — one collective per (dim, side) for same-shaped planes in
      all of them).  The classification mirrors the engine's
      stacked-group election on local shapes (pair-emulated fields never
      take the writer path on hardware, so writer eligibility cannot
      flip it).

    Host arithmetic only — this is also the analytic model
    :func:`igg.comm.plane_bytes_model` exposes, so counter deltas
    reconcile against it exactly."""
    import numpy as np

    local_shapes = [tuple(s) for s in local_shapes]
    movings = [moving_dims(active_dims(ls, grid), grid)
               for ls in local_shapes]
    stack_on = _is_tpu(grid) or _FORCE_STACKED64
    groups: Dict[tuple, List[int]] = {}
    for i, ls in enumerate(local_shapes):
        if (stack_on and len(ls) == 3 and _pair_emulated(dtypes[i])
                and any(d == len(ls) - 1 for d, _ in movings[i])):
            key = (ls, str(np.dtype(dtypes[i])), tuple(movings[i]))
            groups.setdefault(key, []).append(i)
    stacked = {i for g in groups.values() if len(g) >= 2 for i in g}
    out: Dict[Tuple[str, str], int] = {}
    for i, ls in enumerate(local_shapes):
        elems = 1
        for v in ls:
            elems *= int(v)
        itemsize = np.dtype(dtypes[i]).itemsize
        path = "stacked" if i in stacked else "grouped"
        for d, _ in movings[i]:
            transport = "wire" if grid.dims[d] > 1 else "local"
            key = ("xyz"[d] if d < 3 else str(d), f"{transport}_{path}")
            out[key] = out.get(key, 0) + (2 * (elems // int(ls[d]))
                                          * itemsize * grid.nprocs)
    return out


def _materialize_planes(out, planes):
    """`optimization_barrier` fence between a block and the halo planes
    about to be written into it — the KEY unlock for pair-emulated dtypes
    (round-5 on-chip study): without it, the planes are lazy slices of the
    very buffer the in-place updates overwrite, XLA's copy-insertion sees a
    read-after-write hazard against the whole block, and every loop
    iteration pays full-block defensive copies (the f64 x+y update at
    256^3 measured 466 us with 4 full copies; with the fence the SAME
    program is 35 us with zero copies — the fence forces the ~MB of planes
    to materialize first, which the exchange wire needs anyway).  Returns
    `(out, planes)` re-fenced; `planes` is a flat list."""
    from jax import lax

    fenced = lax.optimization_barrier((out, *planes))
    return fenced[0], list(fenced[1:])


def _fence_recv(out, recv: Dict, dims_active, on_tpu: bool):
    """Apply the `_materialize_planes` fence to a block and its received
    planes when the dtype is pair-emulated (no-op otherwise); returns the
    re-fenced `(out, recv)`.  Shared by the engine's grouped XLA assembly
    and `assemble_field` so the fence invariant cannot desynchronize."""
    if not (_pair_emulated(out.dtype) and on_tpu):
        return out, recv
    dd = [d for d, _ in dims_active]
    out, flat = _materialize_planes(out, [p for d in dd for p in recv[d]])
    return out, {d: (flat[2 * j], flat[2 * j + 1])
                 for j, d in enumerate(dd)}


def exchange_assemble_sequential(fields, dims_actives, grid, plans):
    """Sequential per-dimension exchange-and-assemble for XLA-plan fields:
    for each dimension in ascending order, send planes are extracted from
    the current (partially updated) blocks — as LAZY slices, except
    minor-dim planes of 32-bit fields that must materialize for the wire,
    which ride the `pack_planes` one-pass extractor — exchanged, and
    assembled straight back into the blocks with the field's plan form.

    This is the reference's literal control flow
    (`/root/reference/src/update_halo.jl:36,130` — pack/exchange/unpack one
    dimension at a time), and on TPU it is the right shape for the
    pair-emulated 8/16-byte dtypes: corner/edge propagation comes for free
    (later dims' planes are sliced from blocks that already contain the
    earlier dims' received values), so no `_put_row` patches are needed —
    and it was exactly those plane-space patches that broke the
    homogeneous-graph rule of `_assembly_plan` (engine 448 us vs 134 us
    standalone for the f64 x+y update at 256^3; with this path the engine
    matches the standalone number).  With one device along a periodic
    dimension everything stays lazy end-to-end, and the fully fused
    'select' program runs at the byte-proportional floor (f64 xyz 256^3:
    one pass at HBM streaming rate).

    The grouped pre-extracted form (:func:`exchange_all_dims_grouped`)
    remains the engine path for Pallas-writer fields, whose assembly is an
    opaque kernel that needs all planes materialized up front."""
    import jax.numpy as jnp

    from .ops.pack import pack_planes, pack_planes_supported

    nf = len(fields)
    vb = list(fields)
    on_tpu = _is_tpu(grid)
    all_dims = sorted({d for da in dims_actives for d, _ in da})
    for d in all_dims:
        fidx = [i for i in range(nf) if d in dict(dims_actives[i])]
        if not fidx:
            continue
        n = grid.dims[d]
        periodic = bool(grid.periods[d])
        sends: Dict[int, Dict] = {}
        stales: Dict[int, Dict] = {}
        for i in fidx:
            s = vb[i].shape
            ol = dict(dims_actives[i])[d]
            reqs = [(d, ol - 1), (d, s[d] - ol)]       # send lo/hi
            if not periodic:
                reqs += [(d, 0), (d, s[d] - 1)]        # stale lo/hi
            # Minor-dim planes that must materialize for a ppermute ride
            # the pack_planes one-pass extractor, exactly like the grouped
            # path (ADVICE r5 item 1): XLA otherwise pays one relayout per
            # y/z plane (measured 491 vs 92 us for the 4-plane pack at
            # 256^3 f32).  Pair-emulated dtypes keep the lazy slices — the
            # sequential form exists for their homogeneous-graph rule, and
            # the measured win was for 32-bit fields (pack is 32-bit-only
            # in Mosaic anyway).
            if (on_tpu and n > 1 and d >= 1 and vb[i].ndim == 3
                    and not _pair_emulated(vb[i].dtype)
                    and pack_planes_supported(s, vb[i].dtype)):
                planes = [jnp.expand_dims(p, d)
                          for p in pack_planes(vb[i], reqs)]
            else:
                planes = [_plane(vb[i], d, pos) for _, pos in reqs]
            sends[i] = {(d, 0): planes[0], (d, 1): planes[1]}
            stales[i] = ({(d, 0): None, (d, 1): None} if periodic
                         else {(d, 0): planes[2], (d, 1): planes[3]})
        groups: Dict[tuple, List[int]] = {}
        for i in fidx:
            P = sends[i][(d, 0)]
            groups.setdefault((tuple(P.shape), str(P.dtype)), []).append(i)
        for members in groups.values():
            per_field = _wire_exchange(members, sends, stales, d, n,
                                       periodic, getattr(grid, "disp", 1))
            for i, (first, last) in zip(members, per_field):
                ol = dict(dims_actives[i])[d]
                B, rv = _fence_recv(vb[i], {d: (first, last)}, [(d, ol)],
                                    on_tpu)
                vb[i] = assemble_planes(B, rv, [(d, ol)], plan=plans[i])
    return vb


def _stacked_lane64_update(blocks, dims, grid):
    """Grouped update of >= 2 same-shaped lane-active PAIR-EMULATED
    fields (f64 — the reference's Julia default — i64, complex) through
    ONE stacked array: the blocks are stacked along a new leading axis,
    the pre-extracted pending planes (lazy keepdims slices of the stack)
    ride one ppermute per (dim, side) for the whole group, corners
    propagate by where-form pending-plane patches, and assembly is ONE
    fenced select pass over the stacked block.

    Why (VERDICT r5 weak #1): the per-field grouped path gives each f64
    field its own pair-emulated buffer through the composed program, and
    the XLA:TPU buffer assigner charges per-field while-loop carry
    copies — 691/807 us *per field* at 2/4 fields vs the 519 us
    single-field round-4 bar at 256^3.  One stacked block is ONE pair
    buffer: one set of carry copies and one homogeneous select chain
    over all fields (the `_assembly_plan` 'select' op-mix rules), with
    the stack/unstack reshapes fusing into the pass.  Array axes are the
    mesh axes shifted by one (array axis d+1 <-> mesh dim d)."""
    import jax.numpy as jnp
    from jax import lax

    s = blocks[0].shape
    nf = len(blocks)
    B = jnp.stack(blocks)
    dd = sorted(d for d, _ in dims)
    ols = dict(dims)
    disp = getattr(grid, "disp", 1)

    sends: Dict = {}
    stales: Dict = {}
    for d in dd:
        ax = d + 1
        ol = ols[d]
        sends[(d, 0)] = _plane(B, ax, ol - 1)
        sends[(d, 1)] = _plane(B, ax, s[d] - ol)
        if grid.periods[d]:
            stales[(d, 0)] = stales[(d, 1)] = None
        else:
            stales[(d, 0)] = _plane(B, ax, 0)
            stales[(d, 1)] = _plane(B, ax, s[d] - 1)

    recv: Dict = {}
    for d in dd:
        ax = d + 1
        n = grid.dims[d]
        periodic = bool(grid.periods[d])
        if n == 1:
            first, last = exchange_planes(
                sends[(d, 0)], sends[(d, 1)], stales[(d, 0)],
                stales[(d, 1)], d, n, periodic, disp)
        else:
            sq = (lambda P: None if P is None
                  else jnp.squeeze(P, axis=ax))
            first, last = exchange_planes(
                sq(sends[(d, 0)]), sq(sends[(d, 1)]), sq(stales[(d, 0)]),
                sq(stales[(d, 1)]), d, n, periodic, disp)
            first = jnp.expand_dims(first, ax)
            last = jnp.expand_dims(last, ax)
        recv[d] = (first, last)
        # Sequential corner/edge propagation into the later dims' pending
        # planes (where-form — the 'select' plan's homogeneous patch).
        for d2 in dd:
            if d2 <= d:
                continue
            ax2 = d2 + 1
            ol2 = ols[d2]
            for side2, p_send, p_stale in ((0, ol2 - 1, 0),
                                           (1, s[d2] - ol2, s[d2] - 1)):
                for store, pos in ((sends, p_send), (stales, p_stale)):
                    P = store.get((d2, side2))
                    if P is None:
                        continue
                    P = _put_row(P, _plane(first, ax2, pos), ax, 0)
                    P = _put_row(P, _plane(last, ax2, pos), ax, s[d] - 1)
                    store[(d2, side2)] = P

    B, flat = _materialize_planes(B, [p for d in dd for p in recv[d]])
    for j, d in enumerate(dd):
        idx = lax.broadcasted_iota(jnp.int32, B.shape, d + 1)
        B = jnp.where(idx == 0, flat[2 * j],
                      jnp.where(idx == s[d] - 1, flat[2 * j + 1], B))
    return [B[k] for k in range(nf)]


# ---------------------------------------------------------------------------
# Assembly
# ---------------------------------------------------------------------------

# Sublane tile height by itemsize (TPU (8,128)-class tiling; 16-bit packs two
# values per sublane row pair, 8-bit four).
_SUBLANE = {1: 32, 2: 16, 4: 8, 8: 8}
_LANE = 128


def _slab_sizes(shape, dtype) -> Dict[int, int]:
    """Minimal tile-aligned in-place write granularity per dimension: 1 for
    untiled (major) dims, the sublane tile for dim N-2, the lane tile for
    dim N-1."""
    import numpy as np

    nd = len(shape)
    ts = _SUBLANE.get(np.dtype(dtype).itemsize, 8)
    out = {}
    for d in range(nd):
        if d == nd - 1:
            out[d] = _LANE
        elif d == nd - 2:
            out[d] = ts
        else:
            out[d] = 1
    return out


def _assembly_plan(shape, dtype, dims, on_tpu: bool = False) -> str:
    """'dus' when every participating dimension admits a tile-aligned
    in-place slab update (size a multiple of its tile and at least two
    tiles), else 'select'.  Measured at 256^3: the two plans tie for f32
    xyz (~165 us), DUS wins for bf16 xyz (138 vs 211 us) and wins big when
    the lane dim does not participate (xy: 9-20 us vs a full pass), so DUS
    is preferred whenever feasible; select is the fallback for small or
    unaligned local shapes (e.g. the CPU-mesh test grids).

    8-byte dtypes on TPU (f64 — the reference's Julia default — plus
    complex64, and complex128 at 16 bytes) are emulated by the XLA:TPU
    x64/complex rewriter as pairs of f32 arrays.  Under that emulation the
    op mix decides everything (round-5 on-chip study, 256^3 f64):

      - graphs of bare `dynamic_update_slice` ops stay native data
        movement — the whole x+y boundary-slab update compiles to
        in-place plane writes;
      - ONE select/where anywhere drags the entire graph into pair land:
        the block is X64Split into two f32 arrays, every DUS is rewritten
        against the halves, and defensive full-array copies appear around
        the in-place updates (measured: the same x+y DUS chain jumps to
        ~0.9-1.3 ms when a lane select joins the program; 3 chained
        selects never fuse — 1473 us — the round-4 superlinear grouped
        rows).

    The round-5 variant matrix sharpened the rule to two invariants:

      1. Keep the pair graph HOMOGENEOUS.  All-DUS graphs are native
         data movement and an all-select chain compiles to ONE fused
         pass; MIXING the two poisons the program with defensive
         pair-split copies (the xyz update as bare DUS x/y + one lane
         select: 1314 us vs the 508 us all-select engine number; the
         corner-patch form is matched to the plan by `_patch_form`).
      2. Fence the planes (`_materialize_planes`): planes left as lazy
         slices of the block being updated read-after-write-hazard the
         whole buffer, and copy-insertion charges full-block copies per
         loop iteration (x+y at 256^3: 441 us -> 58 us engine-measured
         once fenced).

    With both applied, lane-ACTIVE 8/16-byte sets take the all-'select'
    plan (the only form that can touch the lane dim without a relayout:
    per-lane DUS costs a full relayout pass, 348 us; lane concat 920 us;
    barrier-fenced all-DUS incl. lane planes 930 us) and run at 508 us
    for xyz at 256^3 — 2.50x the f32 writer pass for 2x the bytes, the
    residual being while-loop carry copies the XLA:TPU buffer assigner
    inserts for pair types (single-application compiles are copy-free).
    Sets that DON'T touch the lane dim take the all-DUS 'dus64' plan:
    58 us x+y at 256^3, 2.2x the f32 slab writers."""
    if on_tpu and _pair_emulated(dtype):
        if (len(shape) - 1) in dims:
            return "select"
        return "dus64"
    slabs = _slab_sizes(shape, dtype)
    for d in dims:
        t = slabs[d]
        if t > 1 and (shape[d] % t != 0 or shape[d] < 2 * t):
            return "select"
    return "dus"


def assemble_planes(out, recv: Dict, dims_active, plan: Optional[str] = None):
    """Write the received (keepdims) halo planes into `out` in dimension
    order (later dimensions win the shared corner cells), using the
    aligned-DUS or masked-select strategy (module docstring).

    Why masked-select instead of naive per-plane `dynamic_update_slice` (the
    direct translation of the reference's in-place unpack,
    `/root/reference/src/update_halo.jl:397-405`): an unaligned minor-dim
    plane write makes XLA materialize a full-array copy per dimension —
    measured 3 full copies per update at 256^3 on TPU v5e.  The masked-select
    chain fuses into a single read+write pass; the aligned-DUS path goes
    further and writes only the boundary slabs in place (donated buffers)."""
    import jax.numpy as jnp
    from jax import lax

    s = out.shape
    dims = [d for d, _ in dims_active]
    if plan is None:
        plan = _assembly_plan(s, out.dtype, dims)
    if plan == "select":
        for d in dims:
            idx = lax.broadcasted_iota(jnp.int32, s, d)
            out = jnp.where(idx == 0, recv[d][0],
                            jnp.where(idx == s[d] - 1, recv[d][1], out))
        return out
    if plan == "dus64":
        # Pair-emulated 8/16-byte dtypes, lane dim NOT in the halo set
        # (see `_assembly_plan`): bare plane DUSes only — pure data
        # movement under the x64/complex rewriter, nothing elementwise.
        for d in dims:
            first, last = recv[d]
            out = lax.dynamic_update_slice_in_dim(out, first, 0, axis=d)
            out = lax.dynamic_update_slice_in_dim(out, last, s[d] - 1,
                                                  axis=d)
        return out

    slabs = _slab_sizes(s, out.dtype)
    for d in dims:
        first, last = recv[d]
        t = slabs[d]
        if t == 1:
            out = lax.dynamic_update_slice_in_dim(out, first, 0, axis=d)
            out = lax.dynamic_update_slice_in_dim(out, last, s[d] - 1,
                                                  axis=d)
        else:
            slab = lax.slice_in_dim(out, 0, t, axis=d)
            idx = lax.broadcasted_iota(jnp.int32, slab.shape, d)
            slab = jnp.where(idx == 0, first, slab)
            out = lax.dynamic_update_slice_in_dim(out, slab, 0, axis=d)
            slab = lax.slice_in_dim(out, s[d] - t, s[d], axis=d)
            idx = lax.broadcasted_iota(jnp.int32, slab.shape, d)
            slab = jnp.where(idx == t - 1, last, slab)
            out = lax.dynamic_update_slice_in_dim(out, slab, s[d] - t,
                                                  axis=d)
    return out


# ---------------------------------------------------------------------------
# The update itself
# ---------------------------------------------------------------------------

def _is_tpu(grid) -> bool:
    try:
        return grid.mesh.devices.flat[0].platform == "tpu"
    except Exception:
        return False


_ASSEMBLY_MODES = (None, "xla", "pallas")

_PALLAS_NEEDS_TPU = (
    "assembly='pallas' requires TPU devices (the writers are TPU kernels); "
    "use the default or 'xla' elsewhere.")
_PALLAS_UNSUPPORTED = (
    "assembly='pallas' was forced but the Pallas writers do not support "
    "this field (rank-3 blocks of 16/32-bit elements with tile-compatible "
    "shapes; 64-bit dtypes are toolchain-blocked on TPU — see "
    "igg/ops/halo_write.py); use the default or 'xla'.")


def _raise_pallas_unsupported():
    """The forced-writer (`assembly='pallas'`) refusal: names the
    quarantine when that is why the writers cannot serve, the capability
    contract otherwise."""
    from . import degrade

    q = degrade.status().get(degrade.HALO_WRITER_TIER)
    if q is not None:
        raise GridError(
            f"assembly='pallas' was forced but the writer tier is "
            f"quarantined ({q.reason}): {q.error or '<no capture>'}.  "
            f"igg.degrade.reset({degrade.HALO_WRITER_TIER!r}) re-admits "
            f"it.")
    raise GridError(_PALLAS_UNSUPPORTED)


def _check_assembly(assembly):
    if assembly not in _ASSEMBLY_MODES:
        raise GridError(
            f"assembly={assembly!r}: expected one of None (default: the "
            f"in-place Pallas writers on TPU), 'xla' (masked-select/"
            f"aligned-DUS plans, fusable into a producing stencil), or "
            f"'pallas' (explicitly request the writers).")


def assemble_field(out, recv: Dict, dims_active, grid, assembly=None):
    """Write received (keepdims) halo planes into `out` with the best
    available strategy: the in-place Pallas writers on TPU (deterministic,
    see :mod:`igg.ops.halo_write`), the XLA plans elsewhere — or the plan
    forced by `assembly` ("pallas"/"xla"; see :func:`update_halo` for when
    each wins).  Unlike the engine-internal writer path, every dim's planes
    come from `recv` ("ext" sources) — used by
    :func:`igg.hide_communication`, whose planes are slab-computed arrays
    rather than slices of the block."""
    import jax.numpy as jnp

    from .ops.halo_write import halo_write_slabs, write_lane_active

    _check_assembly(assembly)
    on_tpu = _is_tpu(grid)
    xla_plan = _assembly_plan(out.shape, out.dtype,
                              [d for d, _ in dims_active],
                              on_tpu=on_tpu)

    def xla_assemble(out, recv):
        out, recv = _fence_recv(out, recv, dims_active, on_tpu)
        return assemble_planes(out, recv, dims_active, plan=xla_plan)

    if assembly == "xla" or not (on_tpu or _FORCE_WRITER_INTERPRET):
        if assembly == "pallas":
            raise GridError(_PALLAS_NEEDS_TPU)
        return xla_assemble(out, recv)
    _, use_writer = _writer_dims(out, dims_active, grid, all_ext=True)
    if not use_writer:
        if assembly == "pallas":
            _raise_pallas_unsupported()
        return xla_assemble(out, recv)
    specs = [(d, "ext", jnp.squeeze(recv[d][0], d),
              jnp.squeeze(recv[d][1], d)) for d, _ in dims_active]
    interp = _FORCE_WRITER_INTERPRET
    if any(d == out.ndim - 1 for d, _ in dims_active):
        return write_lane_active(out, specs, frozenset(), interpret=interp)
    return halo_write_slabs(out, specs, interpret=interp)


def _writer_dims(A, dims, grid, all_ext: bool = False):
    """Partition a field's moving dims for the one-pass Pallas writer path:
    returns `(wraps, use_writer)` where `wraps` are the single-device
    periodic dims whose halos the writer assembles from in-VMEM self-wrap
    sources (never materializing a lane-padded plane), and `use_writer` says
    the field's assembly goes through :func:`igg.ops.halo_write.halo_write`
    (TPU, rank-3, supported dtype, lane dim participating — elsewhere the
    XLA aligned-DUS/select plans are faster or required).

    64-bit dtypes: the writers' u32 lane-paired view is implemented and
    tested (interpret seam), but BLOCKED on current XLA:TPU — the x64
    rewriter has no 64-bit `bitcast-convert` and Mosaic rejects f64
    kernels outright (gated in `halo_write_supported`) — so on hardware
    f64 rides the PINNED XLA plan: `_assembly_plan` deterministically
    picks aligned-DUS for tile-aligned shapes (masked-select otherwise),
    the reference-default-Float64 story of VERDICT r3 item 4's fallback
    clause.

    A quarantined writer tier (`igg.degrade.HALO_WRITER_TIER` — a captured
    Mosaic compile failure, or an explicit `igg.degrade.quarantine`) turns
    the writers off here, the single election point every assembly path
    consults, so the XLA plans serve instead; quarantining/resetting the
    tier clears the compiled halo caches because this decision is read at
    TRACE time."""
    from . import degrade
    from .ops.halo_write import (ext_planes_supported, halo_write_supported,
                                 slab_write_supported)

    wraps = frozenset(d for d, _ in dims
                      if grid.dims[d] == 1 and grid.periods[d])
    if degrade.is_quarantined(degrade.HALO_WRITER_TIER):
        return wraps, False
    dd = [d for d, _ in dims]
    lane_active = any(d == A.ndim - 1 for d, _ in dims)
    interp = _FORCE_WRITER_INTERPRET
    if lane_active:
        use_writer = (halo_write_supported(A.shape, A.dtype, interp)
                      and _assembly_plan(A.shape, A.dtype, dd) != "select")
    else:
        use_writer = slab_write_supported(A.shape, A.dtype, dd, interp)
    # Received (ext) planes ride partial-grid BlockSpecs with Mosaic
    # tile-alignment requirements; self-wrap planes never materialize and
    # dim-0 planes are passed whole (`ext_planes_supported`).  With
    # `all_ext` (assemble_field: every plane arrives dense) wrap dims
    # count as ext too.  The gate receives the FULL spec dim list and the
    # wrap set the dispatcher will see, so its col/bx pricing runs the
    # same `lane_dispatch` the writer does.
    ext_dims = [d for d in dd if d != 0 and (all_ext or d not in wraps)]
    if use_writer and not interp:
        use_writer = ext_planes_supported(
            A.shape, A.dtype, ext_dims, dd,
            frozenset() if all_ext else wraps)
    return wraps, use_writer


@profiling.scoped(profiling.HALO)
def _update_halo_impl(fields: List, grid, assembly=None) -> Tuple:
    """Halo update of all fields' local blocks: pack squeezed send planes
    (inner plane `ol-1` / `s-ol`, `/root/reference/src/update_halo.jl:
    386-394`), exchange dimension-sequentially with grouped collectives and
    corner propagation, then assemble — with the one-pass in-place Pallas
    writer when the lane dimension participates (see
    :mod:`igg.ops.halo_write` for why), the XLA plans otherwise.

    (When every active dimension is periodic with a single device and
    overlap 2, the update is algebraically `pad(interior, mode='wrap')`;
    measured on TPU v5e that form does NOT fuse — it regressed both here
    and as a model-level fast path, so the plane machinery below is used
    everywhere.)"""
    import jax.numpy as jnp

    from .ops.pack import pack_planes_supported, pack_planes
    from .ops.halo_write import halo_write_slabs, write_lane_active

    _check_assembly(assembly)
    on_tpu = _is_tpu(grid)
    if assembly == "pallas" and not (on_tpu or _FORCE_WRITER_INTERPRET):
        raise GridError(_PALLAS_NEEDS_TPU)
    shapes, dims_moving, wraps, writer = [], [], [], []
    for A in fields:
        s = A.shape
        dims = moving_dims(active_dims(s, grid), grid)
        w, use_writer = (_writer_dims(A, dims, grid)
                         if (on_tpu or _FORCE_WRITER_INTERPRET)
                         and assembly != "xla"
                         else (frozenset(), False))
        if assembly == "pallas" and dims and not use_writer:
            _raise_pallas_unsupported()
        dims_moving.append(dims)
        writer.append(use_writer)
        wraps.append(w if use_writer else frozenset())
        shapes.append(s)

    # XLA-plan fields whose halo set misses the lane dimension take the
    # sequential per-dim form (free corner propagation, homogeneous
    # pair-emulated graphs).  Lane-ACTIVE XLA fields stay on the grouped
    # pre-extracted form below: their assembly is one fused select pass
    # over the whole block, and sequential re-extraction would split it
    # into one unfusable pass per dimension (measured 1367 vs 545 us for
    # the f64 xyz update at 256^3).  Writer fields are grouped too — their
    # assembly is an opaque kernel needing all planes up front.
    seq_idx = [i for i in range(len(fields))
               if not writer[i]
               and not any(d == fields[i].ndim - 1
                           for d, _ in dims_moving[i])]
    seq_out: Dict[int, object] = {}
    if seq_idx:
        plans = [_assembly_plan(shapes[i], fields[i].dtype,
                                [d for d, _ in dims_moving[i]],
                                on_tpu=on_tpu) for i in seq_idx]
        upd = exchange_assemble_sequential(
            [fields[i] for i in seq_idx], [dims_moving[i] for i in seq_idx],
            grid, plans)
        seq_out = dict(zip(seq_idx, upd))
    widx = [i for i in range(len(fields)) if writer[i] or i not in seq_out]

    # Lane-active pair-emulated fields in groups of >= 2 with identical
    # (shape, dtype, dims): ONE stacked block through exchange + select
    # assembly, so the composed program carries one pair buffer instead
    # of nf — the per-field while-loop carry copies were the 691/807 us
    # per-field cost of the grouped f64 update (VERDICT r5 weak #1).
    stack_groups: Dict[tuple, List[int]] = {}
    if on_tpu or _FORCE_STACKED64:
        for i in widx:
            A = fields[i]
            if (not writer[i] and A.ndim == 3
                    and _pair_emulated(A.dtype)
                    and any(d == A.ndim - 1 for d, _ in dims_moving[i])):
                key = (A.shape, str(A.dtype), tuple(dims_moving[i]))
                stack_groups.setdefault(key, []).append(i)
    stacked = [g for g in stack_groups.values() if len(g) >= 2]
    sidx = {i for g in stacked for i in g}
    widx = [i for i in widx if i not in sidx]
    for members in stacked:
        upd = _stacked_lane64_update([fields[i] for i in members],
                                     dims_moving[members[0]], grid)
        seq_out.update(zip(members, upd))

    if not widx:
        return tuple(seq_out[i] for i in range(len(fields)))

    w_sends = []
    for i in widx:
        A = fields[i]
        s = A.shape
        dims = dims_moving[i]
        w = wraps[i]
        # Send planes are needed for exchanged dims only: the exchange
        # never reads a wrap dim's sends, and the writer sources wrap
        # halos itself (y/z from the block in VMEM, dim 0 from its own
        # lazy slices).
        plane_req = {}
        for d, ol in dims:
            if d in w:
                continue
            plane_req[(d, 0)] = (d, ol - 1)
            plane_req[(d, 1)] = (d, s[d] - ol)
        send = {}
        # Minor-dim planes that must materialize for a ppermute are extracted
        # in ONE Pallas pass (XLA relayouts each separately — measured 491 us
        # vs 92 us for the 4-plane y+z pack at 256^3 f32); everything else
        # stays a lazy slice that fuses into its consumer.
        minor = [k for k, (d, _) in plane_req.items()
                 if grid.dims[d] > 1 and d >= A.ndim - 2 and A.ndim == 3]
        if on_tpu and len(minor) >= 2 and pack_planes_supported(s, A.dtype):
            packed = pack_planes(A, [plane_req[k] for k in minor])
            send.update({k: jnp.expand_dims(p, plane_req[k][0])
                         for k, p in zip(minor, packed)})
        for k, (d, pos) in plane_req.items():
            if k not in send:
                send[k] = _plane(A, d, pos)
        w_sends.append(send)

    recvs = exchange_all_dims_grouped(
        [shapes[i] for i in widx], w_sends, [dims_moving[i] for i in widx],
        grid, wraps=[wraps[i] for i in widx], blocks=[fields[i] for i in widx])

    out = dict(seq_out)
    for k, i in enumerate(widx):
        A = fields[i]
        dims = dims_moving[i]
        if not writer[i]:
            plan = _assembly_plan(A.shape, A.dtype, [d for d, _ in dims],
                                  on_tpu=on_tpu)
            A, rv = _fence_recv(A, recvs[k], dims, on_tpu)
            out[i] = assemble_planes(A, rv, dims, plan=plan)
            continue
        s = A.shape
        lane_active = any(d == A.ndim - 1 for d, _ in dims)
        specs = []
        for d, ol in dims:
            if d in wraps[i]:
                if d == 0:
                    specs.append((0, "ext",
                                  jnp.squeeze(_plane(A, 0, s[0] - ol), 0),
                                  jnp.squeeze(_plane(A, 0, ol - 1), 0)))
                else:
                    specs.append((d, "wrap", ol))
            else:
                first, last = recvs[k][d]
                specs.append((d, "ext", jnp.squeeze(first, d),
                              jnp.squeeze(last, d)))
        interp = _FORCE_WRITER_INTERPRET
        out[i] = (write_lane_active(A, specs, wraps[i], interpret=interp)
                  if lane_active
                  else halo_write_slabs(A, specs, interpret=interp))
    return tuple(out[i] for i in range(len(fields)))


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

def update_halo_local(*fields, assembly=None):
    """Halo update for use *inside* SPMD code (shard_map / `igg.sharded`),
    where arrays are per-device local blocks.  Returns updated block(s).

    `assembly` selects the halo-plane write strategy:
      - `None` (default) — the in-place Pallas writers on TPU
        (deterministic, linear in the field count; the right choice for
        standalone updates and multi-field steps);
      - `"xla"` — the masked-select/aligned-DUS XLA plans.  When the update
        is composed with a producing stencil in ONE traced step, XLA can
        fuse the select chain into the stencil's output pass, beating the
        writer's extra kernel boundary (measured on the radius-1 single
        field diffusion step: 0.70 ms vs 1.12 ms at 256^3) — but the plan
        is a compile lottery for standalone or multi-field programs;
      - `"pallas"` — force the writers; raises `GridError` when they
        cannot serve the call (non-TPU devices, unsupported rank/dtype/
        shape), so the force is a real contract rather than a silent
        fallback.
    """
    shared.check_initialized()
    grid = shared.global_grid()
    out = _update_halo_impl(list(fields), grid, assembly=assembly)
    return out[0] if len(fields) == 1 else out


def update_halo(*fields, assembly=None):
    """Update the halo of the given grid array(s); returns the updated
    array(s) (functional counterpart of the reference's `update_halo!(A...)`,
    `/root/reference/src/update_halo.jl:23-28`).

    Grouping several fields into one call compiles a single XLA program with
    ONE collective per (dimension, side) for all same-shaped fields — group
    subsequent calls for performance, exactly like the reference's
    performance note (`/root/reference/src/update_halo.jl:19-20`).  Inputs
    are donated, so with `T = igg.update_halo(T)` the update is in-place in
    device HBM (and on tile-aligned grids touches only the dirty tiles).
    See :func:`update_halo_local` for the `assembly` strategies (the default
    in-place Pallas writers are the right choice here: a standalone update
    program has no producer to fuse into).
    """
    import jax

    shared.check_initialized()
    grid = shared.global_grid()
    local_shapes = [grid.local_shape(A) for A in fields]
    check_fields(grid, fields, local_shapes)

    key = (shared.grid_epoch(), assembly,
           tuple((A.shape, str(A.dtype)) for A in fields))

    def build():
        specs = tuple(spec_for(A.ndim) for A in fields)
        sm = jax.shard_map(
            lambda *fs: _update_halo_impl(list(fs), grid, assembly=assembly),
            mesh=grid.mesh, in_specs=specs, out_specs=specs)
        return jax.jit(sm, donate_argnums=tuple(range(len(fields))))

    from . import degrade
    from . import telemetry as _telemetry

    fn = _compiled.get(key)
    first = fn is None
    if first:
        fn = _compiled[key] = build()
    writer_possible = (
        assembly is None and (_is_tpu(grid) or _FORCE_WRITER_INTERPRET)
        and not degrade.is_quarantined(degrade.HALO_WRITER_TIER))
    if first:
        # Observability (igg.telemetry): one writer-election record per
        # compiled program — which assembly tier this program was traced
        # against (quarantine flips re-trace, emitting a fresh record).
        _telemetry.emit(
            "halo_writer_election", assembly=assembly,
            writer_possible=bool(writer_possible), n_fields=len(fields),
            quarantined=degrade.is_quarantined(degrade.HALO_WRITER_TIER))
    # Halo traffic: every exchanged boundary plane of this call — per
    # DEVICE, two sides per moving dim of a local-block cross-section,
    # summed over the mesh (the dim classification and plane sizes are
    # local-shape questions: `active_dims`/`ol_of_local` are defined on
    # per-device blocks, not the stacked global array).  Pure host
    # arithmetic, counted once per call.  The unlabeled total is kept
    # for dashboard continuity; the (dim, mode) breakdown (wire vs
    # local, grouped vs stacked — `plane_bytes_by_mode`) lets byte
    # accounting reconcile against the analytic plane-bytes model per
    # exchange path (igg.comm.plane_bytes_model is this same function).
    by_mode = plane_bytes_by_mode(local_shapes,
                                  [A.dtype for A in fields], grid)
    _telemetry.counter("igg_halo_plane_bytes_total").inc(
        sum(by_mode.values()))
    for (dim, mode), nbytes in sorted(by_mode.items()):
        _telemetry.counter("igg_halo_plane_bytes_total",
                           dim=dim, mode=mode).inc(nbytes)
    try:
        if first and writer_possible:
            # Chaos seam (igg.chaos.kernel_compile_fail("halo.writer")).
            degrade._chaos_compile_check(degrade.HALO_WRITER_TIER)
        out = fn(*fields)
    except Exception as e:
        # Compile-failure capture for the writer tier (igg.degrade): a
        # Mosaic/XLA lowering error on the FIRST build of this program,
        # while the writers could have been elected, quarantines the tier
        # and re-traces with the XLA plans — the fast tier is an
        # optimization, never a correctness dependency.  (Errors on an
        # already-serving program, forced assemblies, and programs the
        # writers never entered propagate: they are real.)  The program
        # donates its inputs, so only pre-execution failures — which leave
        # the arguments alive — are capturable; a post-donation runtime
        # error has consumed the buffers, cannot be retried, and says
        # nothing about the writer kernels, so it propagates unclaimed.
        if not (first and writer_possible):
            raise
        if any(getattr(a, "is_deleted", lambda: False)() for a in fields):
            raise
        _compiled.pop(key, None)
        degrade.quarantine(degrade.HALO_WRITER_TIER, 0, "compile_failed", e)
        fn = _compiled[key] = build()   # re-trace: _writer_dims now refuses
        out = fn(*fields)
    if grid.needs_cpu_sync:
        jax.block_until_ready(out)
    return out[0] if len(fields) == 1 else out
