"""The shared K-step chunk engine — one implementation of the trapezoidal
temporal-blocking machinery every model family instantiates.

Rounds 4-7 built the K-step chunk tiers twice: `diffusion_trapezoid`
(single-field, HBM-streaming ping-pong kernel) and `stokes_trapezoid`
(four-field staggered, VMEM-resident banded kernel) each carried their own
copy of the halo-extension slab permutes, the freeze-plane gating, the
window/margin analysis, the K-remainder handling, and the VMEM fitting.
This module is the extraction: the family-independent machinery lives here
ONCE, parameterized by a family's field set, per-row read margin, and
band-update core — and the missing speed rungs (`hm3d_trapezoid`, the
wave2d chunk tier) are generated from it rather than hand-written a third
and fourth time.

What the engine owns:

- **Per-dimension window modes** (:func:`dim_modes`) and the per-device
  SMEM edge-flag vector (:func:`edge_flags`) — moved verbatim from
  `diffusion_trapezoid` (which re-exports them for compatibility).
- **The grouped K-deep slab extension** (:func:`extend_dim_grouped`,
  :func:`extend_fields`): dimension-sequential `ppermute` pairs with
  per-field staggered overlaps, same-shaped slabs stacked onto one wire,
  z slabs transpose-carried, open-edge no-write restoration — the
  superset of `diffusion_trapezoid._extend_dim` (a one-field group) and
  `stokes_trapezoid._extend_dim_grouped` (moved here).
- **Window-realization building blocks**: the staggered periodic
  self-wrap (:func:`wrap_edges`) and the open-dim freeze masks
  (:func:`freeze_open_dim`) both pure-XLA realizations apply per
  iteration, plus the generic per-iteration window loop
  (:func:`window_chunk_xla`) the NEW families' interpret realizations run
  on (the existing families keep their proven iteration orderings — the
  oracle for this refactor is bit-exactness against the per-step
  composition, pinned by the unchanged `tests/test_trapezoid.py` /
  `tests/test_stokes_trapezoid.py` matrices).
- **Admission scaffolding** (:func:`admit_chunk_common`,
  :func:`admit_send_slabs`): the structural gates every chunk tier shares
  — full-chunk count, `disp == 1` permute tables, K-deep send slabs
  inside every extended dimension's block per staggered field — returning
  structured :class:`igg.degrade.Admission` refusals.  The VMEM half of
  admission goes through the single budget authority in
  `igg/ops/_vmem.py` (`chunk_budget`, `fit_chunk_K`).
- **The chunk driver** (:func:`run_chunks`): `n_inner // K` fused chunks
  inside one `lax.fori_loop`, the K-remainder left to the caller's
  per-step path.
- **The generic VMEM-resident banded Mosaic kernel**
  (:func:`resident_chunk_call`): the compiled realization of
  `stokes_trapezoid._kernel`, generalized to any (updated fields, const
  fields, per-field high margins, freeze set, band-update core) — all
  fields VMEM-resident for the whole chunk, grid `(K, nb)`, in-place
  x-row bands with one-row lag carry, chunk-entry freeze planes gated by
  SMEM `axis_index` edge flags.  `stokes_trapezoid` instantiates it with
  its proven config (the TPU-gated
  `test_stokes_trapezoid_matches_per_iteration` is the hardware oracle);
  `hm3d_trapezoid` instantiates it fresh.  In interpret mode every
  instantiation falls to its pure-XLA window realization, so CPU meshes
  and the driver dryrun exercise the same chunked-exchange structure.

Families keep for themselves exactly what is family physics: the
band-update arithmetic (`iteration_core` / `step_core` / the wave2d
leapfrog), the VMEM footprint model, and any family-specific kernel
realization (the diffusion HBM-streaming ping-pong kernel stays in
`diffusion_trapezoid` — its memory scheme is unique to blocks that exceed
VMEM).
"""

from __future__ import annotations

from functools import partial

from .. import profiling
from ._vmem import chunk_budget, fit_chunk_K  # noqa: F401  (re-exported)


# ---------------------------------------------------------------------------
# Per-dimension window modes + edge flags (moved from diffusion_trapezoid)
# ---------------------------------------------------------------------------

def dim_modes(grid, force_y_ext=None, force_z_ext=None):
    """Per-dimension window mode for the chunk evolution:

      - ``"ext"``    periodic ring, K-extended by ppermute slabs (x is
                     always extended when periodic — on one device the
                     self-neighbor slabs are local wrap values);
      - ``"wrap"``   periodic single device, y/z in-buffer self-wrap;
      - ``"oext"``   open with >1 devices: extended like "ext" but with
                     non-wrapping permutes, and the GLOBAL-edge devices
                     re-freeze their boundary slab every step (the
                     reference's no-write halo semantics,
                     `/root/reference/test/test_update_halo.jl:727-732` —
                     a frozen boundary row is genuinely local, so the
                     validity front never shrinks from that side);
      - ``"frozen"`` open single device: no extension, both edge rows
                     re-frozen every step on every device.

    All chunk realizations implement the four modes; open dims must be
    admitted explicitly (`allow_open=True` on the family gates — the
    compiled dispatchers pass it)."""
    modes = []
    for d in range(3):
        if grid.periods[d]:
            modes.append("ext" if (d == 0 or grid.dims[d] > 1) else "wrap")
        else:
            modes.append("oext" if grid.dims[d] > 1 else "frozen")
    # The force flags benchmark the (N,M,K) program shapes on a 1-device
    # self-torus; they only rewire PERIODIC dims (ext <-> wrap) — an open
    # dim keeps its open mode so the compiled-path gates still reject it
    # (forcing 'ext' onto an open boundary would silently wrap it).
    if force_y_ext is not None and grid.periods[1]:
        modes[1] = "ext" if force_y_ext else "wrap"
    if force_z_ext is not None and grid.periods[2]:
        modes[2] = "ext" if force_z_ext else "wrap"
    return tuple(modes)


def edge_flags(modes, grid):
    """Per-device SMEM edge-flag vector shared by the chunk kernels: two
    i32 flags per dim — "frozen" dims statically flag both sides (one
    device IS both global edges, and no `axis_index` is traced, so
    1-device frozen grids still run under plain `jax.jit`), "oext" dims
    flag the global-edge devices via `axis_index`, periodic dims carry
    zeros."""
    import jax.numpy as jnp
    from jax import lax

    from ..shared import AXIS_NAMES

    flag_vals = []
    for d in range(3):
        if modes[d] == "frozen":
            flag_vals += [1, 1]
        elif modes[d] == "oext":
            ai = lax.axis_index(AXIS_NAMES[d])
            flag_vals += [(ai == 0).astype(jnp.int32),
                          (ai == grid.dims[d] - 1).astype(jnp.int32)]
        else:
            flag_vals += [0, 0]
    return jnp.stack([jnp.asarray(v, jnp.int32) for v in flag_vals])


# ---------------------------------------------------------------------------
# Staggered-field helpers
# ---------------------------------------------------------------------------

def field_ols(grid, shapes):
    """Per-field per-dim staggered overlaps (`ol(dim, A)`,
    `/root/reference/src/shared.jl:81`)."""
    return [tuple(grid.ol_of_local(d, s) for d in range(len(s)))
            for s in shapes]


def ext_shape(s, E, modes):
    """A field's extended-window shape: +2E along every extended dim."""
    return tuple(s[d] + (2 * E if modes[d] in ("ext", "oext") else 0)
                 for d in range(len(s)))


def wrap_edges(v, axis, size, ol):
    """Per-field staggered periodic self-wrap of the outermost planes
    along `axis`: edge 0 <- inner `size-ol`, edge `size-1` <- inner
    `ol-1` (`/root/reference/src/update_halo.jl:516-532`)."""
    import jax.numpy as jnp
    from jax import lax

    idx = lax.broadcasted_iota(jnp.int32, v.shape, axis)
    v = jnp.where(idx == 0,
                  lax.slice_in_dim(v, size - ol, size - ol + 1, axis=axis),
                  v)
    return jnp.where(idx == size - 1,
                     lax.slice_in_dim(v, ol - 1, ol, axis=axis), v)


def freeze_open_dim(U, F, d, mode, lo, hi, grid):
    """Open-dim freeze mask of the window realizations: ``"frozen"``
    re-freezes exactly the boundary planes `lo`/`hi` from the chunk-entry
    buffer `F` on every device; ``"oext"`` re-freezes the whole shoulder+
    boundary band (`idx <= lo` / `idx >= hi`) on the global-edge devices
    only (`axis_index` gated) — the no-write halo semantics, which both
    preserves the frozen rows bit-for-bit and quarantines the
    beyond-domain shoulder garbage."""
    import jax.numpy as jnp
    from jax import lax

    from ..shared import AXIS_NAMES

    idx = lax.broadcasted_iota(jnp.int32, U.shape, d)
    if mode == "frozen":
        return jnp.where((idx == lo) | (idx == hi), F, U)
    ai = lax.axis_index(AXIS_NAMES[d])
    n = grid.dims[d]
    U = jnp.where((ai == 0) & (idx <= lo), F, U)
    return jnp.where((ai == n - 1) & (idx >= hi), F, U)


# ---------------------------------------------------------------------------
# The grouped K-deep slab extension (moved from stokes_trapezoid)
# ---------------------------------------------------------------------------

@profiling.scoped(profiling.HALO)
def extend_dim_grouped(arrs, ols, E, grid, d, mode="ext"):
    """The `size + 2E` contiguous global window along dim `d` for a GROUP
    of fields with per-field staggered overlaps: E extension rows beyond
    each end PLUS neighbor-fresh values for each block's own halo rows,
    all from one ppermute pair of `(E+1)`-row slabs per shape group —
    same-shaped slabs are stacked and ride ONE ppermute per direction
    (the halo engine's grouped plane wire); a single field goes alone
    (the `diffusion_trapezoid._extend_dim` case).  z slabs of 3-D fields
    ride the wire TRANSPOSED (z on the sublane axis) so nothing
    lane-padded materializes.

    Replacing the local halo rows with the neighbors' send-position rows
    makes the window exchange-fresh at chunk entry — the invariant the
    trapezoidal validity argument needs.  When the entry halos are
    already fresh (any state produced by `update_halo`, a model step, or
    a previous chunk) the replacement is a bit-exact no-op."""
    import jax.numpy as jnp
    from jax import lax

    from ..shared import AXIS_NAMES

    n = grid.dims[d]
    axis = AXIS_NAMES[d]
    open_edges = mode == "oext"
    tw = d == 2 and arrs[0].ndim == 3   # transpose-carried lane-dim slabs

    slabs = []
    for A, ol in zip(arrs, ols):
        S = A.shape[d]
        left = lax.slice_in_dim(A, S - ol - E, S - ol + 1, axis=d)
        right = lax.slice_in_dim(A, ol - 1, ol + E, axis=d)
        if tw:
            left, right = (jnp.swapaxes(x, 1, 2) for x in (left, right))
        slabs.append([left, right])

    if n > 1:
        if open_edges:
            to_right = [(i, i + 1) for i in range(n - 1)]
            to_left = [(i, i - 1) for i in range(1, n)]
        else:
            to_right = [(i, (i + 1) % n) for i in range(n)]
            to_left = [(i, (i - 1) % n) for i in range(n)]
        groups = {}
        for j, (left, right) in enumerate(slabs):
            groups.setdefault(tuple(left.shape), []).append(j)
        for members in groups.values():
            for side, table in ((0, to_right), (1, to_left)):
                if len(members) == 1:
                    j = members[0]
                    slabs[j][side] = lax.ppermute(slabs[j][side], axis,
                                                  table)
                else:
                    stacked = jnp.stack([slabs[j][side] for j in members])
                    stacked = lax.ppermute(stacked, axis, table)
                    for k, j in enumerate(members):
                        slabs[j][side] = stacked[k]

    out = []
    for A, ol, (left, right) in zip(arrs, ols, slabs):
        if tw:
            left, right = (jnp.swapaxes(x, 1, 2) for x in (left, right))
        S = A.shape[d]
        Text = jnp.concatenate(
            [left, lax.slice_in_dim(A, 1, S - 1, axis=d), right], axis=d)
        if open_edges:
            # Global-edge devices received zeros: rows [0, E) / [Se-E, Se)
            # lie beyond the domain (garbage the step-level freeze
            # quarantines), but ext row E / Se-1-E replaced the block's
            # own boundary rows — restore their no-write (stale) values
            # there.
            idx = lax.axis_index(axis)
            Se = S + 2 * E
            fixed_l = lax.dynamic_update_slice_in_dim(
                Text, lax.slice_in_dim(A, 0, 1, axis=d), E, axis=d)
            Text = jnp.where(idx == 0, fixed_l, Text)
            fixed_r = lax.dynamic_update_slice_in_dim(
                Text, lax.slice_in_dim(A, S - 1, S, axis=d), Se - 1 - E,
                axis=d)
            Text = jnp.where(idx == n - 1, fixed_r, Text)
        out.append(Text)
    return out


def extend_fields(arrs, ols, E, grid, modes):
    """Dimension-sequential extension of a list of fields: x first, then
    the y extension OF the x-extended buffers, then z of the x/y-extended
    — corner and edge regions arrive via the later neighbors' own
    earlier-dim extensions (the halo engine's sequential-exchange corner
    trick).  wrap/frozen dims are not extended."""
    out = list(arrs)
    for d in range(arrs[0].ndim):
        if modes[d] in ("ext", "oext"):
            out = extend_dim_grouped(out, [ol[d] for ol in ols], E, grid,
                                     d, modes[d])
    return out


# ---------------------------------------------------------------------------
# Admission scaffolding (shared structural gates)
# ---------------------------------------------------------------------------

def admit_chunk_common(grid, K, n_inner):
    """The gates every chunk tier shares: at least one full K-chunk and
    unit-displacement permute tables.  Returns a falsy Admission carrying
    the refusal, or None when the common gates pass (the family gate
    continues)."""
    from ..degrade import Admission

    if K < 2 or n_inner < K:
        return Admission.no(f"n_inner={n_inner} holds no full K={K} chunk "
                            f"(needs n_inner >= K >= 2)")
    if getattr(grid, "disp", 1) != 1:
        # The chunked slab exchange hardwires +-1 ppermute tables.
        return Admission.no(f"grid disp {grid.disp} != 1 (chunk slab "
                            f"exchange hardwires +-1 ppermute tables)")
    return None


def admit_send_slabs(shapes, ols, E, modes, *, grid=None, min_ol: int = 2):
    """E-deep send slabs must lie inside every extended dimension's block
    for every (staggered) field, with overlap >= `min_ol` — AND, when
    the `grid` is supplied, stay out of the sender's SHARED region:
    neighbor blocks duplicate `ol` base rows (the inter-block shift is
    `S - ol`), so the rightward slab `[S - ol - E, S - ol + 1)` consists
    of sender-OWNED rows only when `E <= S - 2*ol` per base dimension.
    A deeper slab ships rows the sender itself merely mirrors — on small
    blocks (e.g. an 8-row overlap-3 dimension, 2 owned rows) those are
    not the global rows the receiver's extension window claims, and the
    chunk serves quietly wrong values (the round-21 stokes `(2, 2, 2)`
    small-block incident).  Returns a falsy Admission or None."""
    from ..degrade import Admission

    nd = len(shapes[0])
    for d in range(nd):
        if modes[d] not in ("ext", "oext"):
            continue
        if grid is not None:
            nb, olb = grid.nxyz[d], grid.overlaps[d]
            if E > nb - 2 * olb:
                return Admission.no(
                    f"E={E} dim-{d} send slabs enter the sender's shared "
                    f"region (base extent {nb}, ol {olb}: needs "
                    f"E <= {nb - 2 * olb})")
        for s, ol in zip(shapes, ols):
            if ol[d] < min_ol:
                return Admission.no(
                    f"dim-{d} overlap {ol[d]} < {min_ol} (field shape {s})")
            if s[d] - ol[d] - E < 0 or ol[d] + E > s[d]:
                return Admission.no(
                    f"E={E} dim-{d} send slabs fall outside a field block "
                    f"(shape {s}, ol {ol[d]})")
    return None


def admit_sublane_extension(E, modes, *, tile: int = 8):
    """The sublane-tile-extension gate every banded/resident chunk kernel
    shares: a y-extension that is not a whole number of sublane tiles
    shifts every leading-dim row slice (and the central y window) off the
    Mosaic `(8, 128)` tile grid — configurations Mosaic refuses DEEP in
    lowering (a GridError crash, the round-17 hm3d `(2, 2, 2)` incident)
    rather than at admission.  One structured gate, one place: returns a
    falsy :class:`igg.degrade.Admission` carrying the reason, or None
    when the geometry is tileable."""
    from ..degrade import Admission

    if len(modes) > 1 and modes[1] in ("ext", "oext") and E % tile != 0:
        return Admission.no(f"y-extension E={E} not on sublane tiles "
                            f"(E % {tile} != 0)")
    return None


def admit_banded_geometry(shapes, E, modes, *, B, extras, lo=1,
                          interpret=False):
    """Structural gates of the streaming banded realization (shared by
    every family's `*_banded_supported`): sublane-tiled band depth, a
    band-divisible extended x span with at least two bands (the
    ping-pong out-write pipeline drains slot pairs), read margins inside
    one band, and — compiled mode only — 3-D fields plus the shared
    sublane-extension geometry (the pure-XLA banded realization has no
    tile grid, so interpret meshes skip the Mosaic-only gates).  Returns
    a falsy Admission or None."""
    from ..degrade import Admission

    nd = len(shapes[0])
    ext_shapes = [ext_shape(s, E, modes) for s in shapes]
    base = min(s[0] for s in ext_shapes)
    if B < 8 or B % 8 != 0:
        return Admission.no(f"band depth B={B} not on sublane tiles "
                            f"(needs B % 8 == 0, B >= 8)")
    if base % B != 0:
        return Admission.no(f"extended x span {base} not band-divisible "
                            f"by B={B}")
    if base // B < 2:
        return Admission.no(f"extended x span {base} holds fewer than 2 "
                            f"bands of B={B} (the streaming out-write "
                            f"pipeline ping-pongs two slots)")
    if max(extras) + lo > B:
        return Admission.no(f"read margins lo={lo}/extras={tuple(extras)} "
                            f"exceed one band of B={B}")
    if not interpret:
        if nd != 3:
            return Admission.no(f"compiled streaming kernel is 3-D only "
                                f"({nd}-D x-row bands are not "
                                f"sublane-tileable; interpret mode serves)")
        sub = admit_sublane_extension(E, modes)
        if sub is not None:
            return sub
    return None


# ---------------------------------------------------------------------------
# Generic window realization (the NEW families' pure-XLA chunk evolution)
# ---------------------------------------------------------------------------

def window_chunk_xla(fields, *, K, E, modes, grid, ols, shapes,
                     freeze_fields, core):
    """K iterations of a family's update on the extended windows:
    `core(*fields)` returns the updated full-window fields (the family's
    whole-block arithmetic — interior updates, stale edges); then per-dim
    halo handling IN DIMENSION ORDER (later dims win shared cells, the
    per-step exchange-assembly order): wrap dims re-apply the per-field
    staggered self-wrap, open dims re-freeze the freeze set's shoulder+
    boundary band from the chunk-entry buffers (`freeze_fields` may be a
    uniform sequence or a per-dim dict — :func:`normalize_freeze`).
    Returns the evolved extended windows (central slicing is the
    caller's — :func:`central_window`)."""
    from jax import lax

    entry = tuple(fields)
    nd = fields[0].ndim
    freeze = normalize_freeze(freeze_fields, nd)

    def step(_, S):
        S = list(core(*S))
        for d in range(nd):
            if modes[d] == "wrap":
                for f in range(len(S)):
                    S[f] = wrap_edges(S[f], d, S[f].shape[d], ols[f][d])
            elif modes[d] in ("oext", "frozen"):
                lo = E if modes[d] == "oext" else 0
                for f in freeze[d]:
                    hi = lo + shapes[f][d] - 1
                    S[f] = freeze_open_dim(S[f], entry[f], d, modes[d],
                                           lo, hi, grid)
        return tuple(S)

    return lax.fori_loop(0, K, step, entry)


def central_window(F, shape, E, modes):
    """Slice a field's central `shape` window out of its evolved extended
    buffer (extended dims only)."""
    from jax import lax

    for d in range(len(shape)):
        if modes[d] in ("ext", "oext"):
            F = lax.slice_in_dim(F, E, E + shape[d], axis=d)
    return F


def run_chunks(fields, *, n_inner, K, one_chunk):
    """`n_inner // K` full chunks inside one `lax.fori_loop`; the
    K-remainder is the caller's (served by its per-step path).  Returns
    `(*fields, steps_done)`."""
    from jax import lax

    chunks = n_inner // K
    out = lax.fori_loop(0, chunks, lambda _, S: tuple(one_chunk(*S)),
                        tuple(fields))
    return (*out, chunks * K)


# ---------------------------------------------------------------------------
# The generic WHOLE-WINDOW resident Mosaic kernel (compiled realization)
# ---------------------------------------------------------------------------

def normalize_freeze(freeze_fields, nd):
    """Per-dim freeze sets: a plain sequence applies to every dim (the
    stokes convention — velocities frozen on all open dims); a dict
    `{dim: (field indices)}` freezes per dim (a spec's face field is
    no-write only along its staggered dim — `igg.stencil.analyze`)."""
    if isinstance(freeze_fields, dict):
        return {d: tuple(freeze_fields.get(d, ())) for d in range(nd)}
    return {d: tuple(freeze_fields) for d in range(nd)}


def _whole_window_kernel(*refs, K, cfg, core, nfr):
    """Whole-window VMEM-resident chunk kernel (the wave2d scheme,
    generalized): grid `(K,)`, ALL extended fields loaded into VMEM
    scratch once, K coupled full-window steps evolved in place, written
    back once — `n(R+W)/K` HBM traffic per step.  Per iteration the
    per-dim halo handling runs in dimension order: wrap dims re-apply
    the staggered self-wrap; open dims re-freeze the per-dim freeze
    set's boundary PLANES from chunk-entry values, gated by the SMEM
    edge flags (the plane-only freeze — the shoulder garbage beyond is
    quarantined by the analyzer's boundary-validity recurrence, the
    Stokes "one frozen plane" rule)."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    modes, ols, ext_shapes, E = (cfg["modes"], cfg["ols"],
                                 cfg["ext_shapes"], cfg["E"])
    shapes = cfg["shapes"]
    freeze = cfg["freeze"]
    n = len(ext_shapes)
    it = iter(refs)
    text_hbm = [next(it) for _ in range(n)]
    flags_ref = next(it) if nfr else None
    fr_hbm = [next(it) for _ in range(nfr)]
    outs = [next(it) for _ in range(n)]
    fv = [next(it) for _ in range(n)]
    fr_v = [next(it) for _ in range(nfr)]
    lsem = next(it)
    osem = next(it)
    fsem = next(it) if nfr else None

    k = pl.program_id(0)

    @pl.when(k == 0)
    def _():
        cs = [pltpu.make_async_copy(text_hbm[j], fv[j], lsem.at[j])
              for j in range(n)]
        for c in cs:
            c.start()
        for c in cs:
            c.wait()

    if nfr:
        @pl.when(k == 0)
        def _():
            cs = [pltpu.make_async_copy(fr_hbm[j], fr_v[j], fsem.at[j])
                  for j in range(nfr)]
            for c in cs:
                c.start()
            for c in cs:
                c.wait()

    fields = [fv[f][...] for f in range(n)]
    news = list(core(*fields))
    nd = fields[0].ndim
    flags = ([flags_ref[j] for j in range(6)] if nfr else [0] * 6)
    plane = {}
    j = 0
    for d in range(nd):
        if modes[d] not in ("oext", "frozen"):
            continue
        for f in freeze[d]:
            for side in (0, 1):
                plane[(f, d, side)] = fr_v[j][...]
                j += 1
    for d in range(nd):
        if modes[d] == "wrap":
            for f in range(n):
                news[f] = wrap_edges(news[f], d, ext_shapes[f][d],
                                     ols[f][d])
        elif modes[d] in ("oext", "frozen"):
            lo = E if modes[d] == "oext" else 0
            for f in freeze[d]:
                hi = lo + shapes[f][d] - 1
                idx = lax.broadcasted_iota(jnp.int32, news[f].shape, d)
                p0 = jnp.expand_dims(plane[(f, d, 0)], d)
                p1 = jnp.expand_dims(plane[(f, d, 1)], d)
                news[f] = jnp.where((idx == lo) & (flags[2 * d] == 1),
                                    p0, news[f])
                news[f] = jnp.where((idx == hi) & (flags[2 * d + 1] == 1),
                                    p1, news[f])
    for f in range(n):
        fv[f][...] = news[f]

    @pl.when(k == K - 1)
    def _():
        cs = [pltpu.make_async_copy(fv[f], outs[f], osem.at[f])
              for f in range(n)]
        for c in cs:
            c.start()
        for c in cs:
            c.wait()


def whole_window_chunk_call(exts, *, K, E, modes, grid, ols, shapes,
                            core, freeze_fields=(), window_fallback,
                            interpret=False):
    """Advance K coupled iterations on the extended buffers with the
    whole-window resident kernel; returns every field's central local
    block.  `core(*windows)` is the family's full-window arithmetic
    (the same callable the pure-XLA window realization evolves);
    `freeze_fields` the per-dim (or uniform) open-boundary no-write
    set (:func:`normalize_freeze`).  In interpret mode the chunk runs
    `window_fallback()` — the pure-XLA window realization — so CPU
    meshes exercise the same admission gates and chunked-exchange
    structure (the kernel's manual DMA is TPU-only)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nd = exts[0].ndim
    ext_shapes = [tuple(x.shape) for x in exts]
    freeze = normalize_freeze(freeze_fields, nd)

    def central(F, f):
        return central_window(F, shapes[f], E, modes)

    if interpret:
        out = window_fallback()
        return tuple(central(F, f) for f, F in enumerate(out))

    cfg = dict(modes=tuple(modes), ols=tuple(ols),
               ext_shapes=tuple(ext_shapes), E=E,
               shapes=tuple(shapes), freeze=freeze)

    # Open-dim entry freeze planes + per-device SMEM edge flags (the
    # resident_chunk_call pattern; "frozen" dims statically flag both
    # sides, so 1-device frozen grids run under plain jax.jit).
    fr_planes = []
    flag_ops = []
    any_open = any(modes[d] in ("oext", "frozen") for d in range(nd))
    if any_open:
        for d in range(nd):
            if modes[d] not in ("oext", "frozen"):
                continue
            lo = E if modes[d] == "oext" else 0
            for f in freeze[d]:
                hi = lo + shapes[f][d] - 1
                for idx in (lo, hi):
                    p = jnp.squeeze(
                        lax.slice_in_dim(exts[f], idx, idx + 1, axis=d), d)
                    fr_planes.append(p)
        # The kernel unpacks the SMEM flags operand iff freeze planes
        # exist (its refs iterator is keyed on nfr): an open-dim spec
        # whose per-dim freeze sets are all empty needs neither — the
        # per-iteration freeze loop has nothing to gate.
        if fr_planes:
            flag_ops = [edge_flags(tuple(modes) + ("wrap",) * (3 - nd),
                                   grid)]
    nfr = len(fr_planes)

    kern = partial(_whole_window_kernel, K=K, cfg=cfg, core=core, nfr=nfr)

    operands = [*exts, *flag_ops, *fr_planes]
    vmas = [getattr(getattr(x, "aval", None), "vma", None)
            for x in operands]
    vma = frozenset().union(*[v for v in vmas if v]) if any(vmas) else None

    def shp(a):
        return (jax.ShapeDtypeStruct(a.shape, a.dtype, vma=vma) if vma
                else jax.ShapeDtypeStruct(a.shape, a.dtype))

    out = pl.pallas_call(
        kern,
        name="chunk_whole_window",
        grid=(K,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * len(exts)
        + [pl.BlockSpec(memory_space=pltpu.SMEM)] * len(flag_ops)
        + [pl.BlockSpec(memory_space=pl.ANY)] * nfr,
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * len(exts),
        out_shape=[shp(F) for F in exts],
        input_output_aliases={f: f for f in range(len(exts))},
        scratch_shapes=[pltpu.VMEM(F.shape, F.dtype) for F in exts]
        + [pltpu.VMEM(p.shape, p.dtype) for p in fr_planes]
        + [pltpu.SemaphoreType.DMA((len(exts),)),
           pltpu.SemaphoreType.DMA((len(exts),))]
        + ([pltpu.SemaphoreType.DMA((nfr,))] if nfr else []),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=128 * 1024 * 1024,
            dimension_semantics=("arbitrary",)),
    )(*operands)
    return tuple(central(F, f) for f, F in enumerate(out))


# ---------------------------------------------------------------------------
# The generic VMEM-resident banded Mosaic kernel (compiled realization)
# ---------------------------------------------------------------------------

def pad8(v: int) -> int:
    """Round up to the Mosaic sublane tile (f32) — the shared helper
    every chunk module's VMEM-footprint model uses, so the models can
    never drift from the kernels' actual padding."""
    return -(-v // 8) * 8


def pad128(v: int) -> int:
    """Round up to the Mosaic lane tile."""
    return -(-v // 128) * 128


_pad8, _pad128 = pad8, pad128


def band_halo(news, a, bx, flags, frx, fryz, cfg):
    """Per-band halo handling of the updated fields' new-band value
    arrays, in dimension order (later dims win shared cells, the
    per-step path's assembly order): x freeze rows (open dims,
    `freeze_fields` only), then y wrap/freeze, then z wrap/freeze
    (2-D fields stop at y).
    `flags` is the 6-vector of edge flags as VALUES (SMEM scalars in the
    kernel, python ints in the banded-scheme simulation);
    `frx[(f, side)]` are whole x freeze planes and `fryz[(f, d, side)]`
    the band-sliced y/z freeze rows of field f (logical trailing
    extents).  `cfg` carries modes/ols/ext_shapes/shapes/E and
    `freeze_fields` (which updated fields the open-dim no-write applies
    to — uniform sequence or per-dim dict, :func:`normalize_freeze`).
    Pure values — shared by the generic Mosaic kernels, the streaming
    banded kernel, the pure-XLA banded realization, and the
    banded-scheme simulation tests."""
    import jax.numpy as jnp
    from jax import lax

    modes, ols, ext_shapes, E = (cfg["modes"], cfg["ols"],
                                 cfg["ext_shapes"], cfg["E"])
    nd = news[0].ndim
    freeze = normalize_freeze(cfg.get("freeze_fields", (1, 2, 3)), nd)
    news = list(news)

    if modes[0] in ("oext", "frozen"):
        lo = E if modes[0] == "oext" else 0
        for f in freeze[0]:
            hi = lo + cfg["shapes"][f][0] - 1
            rows = lax.broadcasted_iota(jnp.int32, news[f].shape, 0) + a
            news[f] = jnp.where((rows == lo) & (flags[0] == 1),
                                frx[(f, 0)][None], news[f])
            news[f] = jnp.where((rows == hi) & (flags[1] == 1),
                                frx[(f, 1)][None], news[f])
    for d in range(1, nd):
        if modes[d] == "wrap":
            for f in range(len(news)):
                sd = ext_shapes[f][d]
                ol = ols[f][d]
                news[f] = wrap_edges(news[f], d, sd, ol)
        elif modes[d] in ("oext", "frozen"):
            lo = E if modes[d] == "oext" else 0
            for f in freeze[d]:
                hi = lo + cfg["shapes"][f][d] - 1
                idx = lax.broadcasted_iota(jnp.int32, news[f].shape, d)
                exp = (lambda P: jnp.expand_dims(P, d))
                news[f] = jnp.where((idx == lo) & (flags[2 * d] == 1),
                                    exp(fryz[(f, d, 0)]), news[f])
                news[f] = jnp.where((idx == hi) & (flags[2 * d + 1] == 1),
                                    exp(fryz[(f, d, 1)]), news[f])
    return tuple(news)


def _resident_kernel(*refs, K, bx, cfg, nfr, pads, band_update, extras):
    """The generic VMEM-resident in-place banded chunk kernel (the
    `stokes_trapezoid` scheme, parameterized): `n_up` updated fields plus
    `n_fields - n_up` const fields, all resident for the whole chunk
    (grid `(K, nb)`, "arbitrary" semantics), updated IN PLACE in x-row
    bands with a one-row lag buffer carrying each band's overwritten tail
    row to its successor.  HBM traffic per chunk is ONE read of the
    extended fields and ONE write of the updated ones — the 1/K
    amortization the rooflines demand."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    shapes = cfg["shapes"]            # local (unextended) field shapes
    ext_shapes = cfg["ext_shapes"]    # logical extended shapes
    modes = cfg["modes"]
    n_fields = len(ext_shapes)
    n_up = cfg["n_up"]
    freeze = cfg.get("freeze_fields", ())

    it = iter(refs)
    text_hbm = [next(it) for _ in range(n_fields)]  # padded extended fields
    flags_ref = next(it) if nfr else None           # SMEM (6,) i32
    fr_hbm = [next(it) for _ in range(nfr)]         # padded freeze planes
    outs = [next(it) for _ in range(n_up)]          # aliased to text inputs
    fv = [next(it) for _ in range(n_fields)]        # resident field scratch
    lag = [next(it) for _ in range(n_up)]           # (2, 1, S1p, S2p)-ish
    fr_v = [next(it) for _ in range(nfr)]
    lsem = next(it)
    osem = next(it)
    fsem = next(it) if nfr else None

    k = pl.program_id(0)
    i = pl.program_id(1)
    a = i * bx
    sl = i % 2

    # One-time chunk-entry load: the padded extended fields (and the
    # freeze planes) HBM -> VMEM.  Synchronous — once per K iterations.
    @pl.when((k == 0) & (i == 0))
    def _():
        cs = [pltpu.make_async_copy(text_hbm[j], fv[j], lsem.at[j])
              for j in range(n_fields)]
        for c in cs:
            c.start()
        for c in cs:
            c.wait()

    if nfr:
        @pl.when((k == 0) & (i == 0))
        def _():
            cs = [pltpu.make_async_copy(fr_hbm[j], fr_v[j], fsem.at[j])
                  for j in range(nfr)]
            for c in cs:
                c.start()
            for c in cs:
                c.wait()

    # Band 0 has no predecessor: seed its low-margin lag slot with the
    # clamped duplicate of row 0 (the dup feeds only rows the validity
    # argument never reads back — shoulder garbage or frozen planes).
    @pl.when(i == 0)
    def _():
        for f in range(n_up):
            lag_w = lag[f].at[pl.ds(1, 1)]
            lag_w[:] = fv[f][pl.ds(0, 1)]

    # Save this band's tail row (about to be overwritten) for the next
    # band's low margin — VMEM-to-VMEM, one row per updated field,
    # slot-alternated (band i writes slot i%2, band i+1 reads it back as
    # 1-(i+1)%2; band 0 reads the seed above from the same expression).
    for f in range(n_up):
        lag_w = lag[f].at[pl.ds(sl, 1)]
        lag_w[:] = fv[f][pl.ds(a + bx - 1, 1)]

    # Margin-1 windows.  Low margin: row a-1 — band i-1 already overwrote
    # it, so every band reads its lag slot (const fields are never
    # overwritten: clamped margin read straight from the buffer).  High
    # margins clamp at the buffer end (top-band dups feed only
    # shoulder/frozen rows).
    nrows = [ext_shapes[f][0] for f in range(n_fields)]

    def window(f, extra):
        if f < n_up:
            m1 = lag[f][pl.ds(1 - sl, 1)]
        else:
            m1 = fv[f][pl.ds(jnp.maximum(a - 1, 0), 1)]
        parts = [m1, fv[f][pl.ds(a, bx)]]
        top = nrows[f] - 1
        for e in range(1, extra + 1):
            parts.append(fv[f][pl.ds(jnp.minimum(a + bx + e - 1, top), 1)])
        return jnp.concatenate(parts, axis=0)

    def logical(W, f):
        # Slice the tile-padded trailing extents back to the field's
        # logical extended shape (values; Mosaic masks the lanes).
        return W[:, :ext_shapes[f][1], :ext_shapes[f][2]]

    Ws = [logical(window(f, extras[f]), f) for f in range(n_fields)]

    news = band_update(*Ws, bx=bx)

    # Halo handling on the new band values (freeze planes band-sliced to
    # logical extents; SMEM flags read as scalars).
    flags = ([flags_ref[j] for j in range(6)] if nfr else [0] * 6)
    frx, fryz = {}, {}
    j = 0
    for d in range(3):
        if modes[d] not in ("oext", "frozen"):
            continue
        for f in freeze:
            pl_shape = [ext_shapes[f][x] for x in range(3) if x != d]
            for side in (0, 1):
                if d == 0:
                    frx[(f, side)] = fr_v[j][...][:pl_shape[0],
                                                  :pl_shape[1]]
                else:
                    fryz[(f, d, side)] = fr_v[j][pl.ds(a, bx)][
                        :, :pl_shape[1]]
                j += 1
    news = band_halo(news, a, bx, flags, frx, fryz, cfg)

    # In-place write, padded back with the old trailing columns.
    for f in range(n_up):
        new = news[f]
        pady, padz = pads[f]
        old = fv[f][pl.ds(a, bx)]
        if padz:
            new = jnp.concatenate([new, old[:, :new.shape[1], -padz:]],
                                  axis=2)
        if pady:
            new = jnp.concatenate([new, old[:, -pady:, :]], axis=1)
        fv[f][pl.ds(a, bx)] = new

    # Final iteration: band write-back to the (aliased) outputs.
    # Synchronous — once per chunk; rows outside the band grid (a
    # staggered field's top face) keep their aliased entry values,
    # exactly the frozen/no-write semantics they need.
    @pl.when(k == K - 1)
    def _():
        cs = [pltpu.make_async_copy(fv[f].at[pl.ds(a, bx)],
                                    outs[f].at[pl.ds(a, bx)], osem.at[f])
              for f in range(n_up)]
        for c in cs:
            c.start()
        for c in cs:
            c.wait()


def resident_chunk_call(exts, const_exts, *, K, bx, modes, grid, ols,
                        shapes, E, band_update, extras, freeze_fields,
                        window_fallback, interpret=False):
    """Advance K coupled iterations on the extended buffers with the
    generic VMEM-resident banded kernel; returns the updated fields'
    central local blocks.  `exts` are the updated fields' extended
    windows (aliased input->output), `const_exts` the loop-invariant
    ones; `extras[f]` is field f's high-margin row count (its read
    radius above the band); `freeze_fields` the updated-field indices the
    open-dim no-write semantics apply to; `band_update(*windows, bx=)`
    the family's pure-value band arithmetic.  In interpret mode the
    chunk runs `window_fallback()` — the family's pure-XLA window
    realization — so CPU meshes exercise the same admission gates and
    chunked-exchange structure (the kernel itself is manual-DMA,
    TPU-only)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_up = len(exts)
    ext_shapes = ([tuple(x.shape) for x in exts]
                  + [tuple(x.shape) for x in const_exts])

    def central(F, f):
        return central_window(F, shapes[f], E, modes)

    if interpret:
        out = window_fallback()
        return tuple(central(F, f) for f, F in enumerate(out[:n_up]))

    S0e = ext_shapes[0][0]
    nb = S0e // bx
    cfg = dict(modes=tuple(modes), ols=tuple(ols[:n_up]),
               ext_shapes=tuple(ext_shapes), E=E,
               shapes=tuple(shapes[:n_up]), n_up=n_up,
               freeze_fields=tuple(freeze_fields))

    # Tile-pad the staggered trailing extents so every leading-dim VMEM
    # slice in the kernel is tile-aligned; the pad columns carry garbage
    # the central slices never see.
    def padded(F):
        s = F.shape
        py = _pad8(s[1]) - s[1]
        pz = _pad128(s[2]) - s[2]
        if py or pz:
            F = jnp.pad(F, [(0, 0), (0, py), (0, pz)])
        return F

    fields_all = [padded(F) for F in list(exts) + list(const_exts)]
    pads = [(_pad8(s[1]) - s[1], _pad128(s[2]) - s[2])
            for s in ext_shapes[:n_up]]

    # Open-dim freeze planes (chunk-entry boundary planes of the frozen
    # fields) + per-device SMEM edge flags ("frozen" dims statically flag
    # both sides, so 1-device frozen grids run under plain jax.jit).
    fr_planes = []
    flag_ops = []
    any_open = any(m in ("oext", "frozen") for m in modes)
    if any_open:
        for d in range(3):
            if modes[d] not in ("oext", "frozen"):
                continue
            lo = E if modes[d] == "oext" else 0
            for f in freeze_fields:
                hi = lo + shapes[f][d] - 1
                for idx in (lo, hi):
                    p = jnp.squeeze(
                        lax.slice_in_dim(exts[f], idx, idx + 1, axis=d), d)
                    ps = p.shape
                    py = _pad8(ps[0]) - ps[0]
                    pz = _pad128(ps[1]) - ps[1]
                    if py or pz:
                        p = jnp.pad(p, [(0, py), (0, pz)])
                    fr_planes.append(p)
        flag_ops = [edge_flags(modes, grid)]
    nfr = len(fr_planes)

    kern = partial(_resident_kernel, K=K, bx=bx, cfg=cfg, nfr=nfr,
                   pads=pads, band_update=band_update, extras=extras)

    operands = [*fields_all, *flag_ops, *fr_planes]
    vmas = [getattr(getattr(x, "aval", None), "vma", None)
            for x in operands]
    vma = frozenset().union(*[v for v in vmas if v])

    def shp(s):
        return (jax.ShapeDtypeStruct(s, exts[0].dtype, vma=vma) if vma
                else jax.ShapeDtypeStruct(s, exts[0].dtype))

    # Scratch order MUST mirror the kernel's unpack: field/lag VMEM,
    # freeze-plane VMEM, load semaphores, out semaphores, then the
    # freeze-plane semaphore LAST (present only when a dim is open).
    fr_scratch = [pltpu.VMEM(p.shape, p.dtype) for p in fr_planes]
    out = pl.pallas_call(
        kern,
        name="chunk_resident",
        grid=(K, nb),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * len(fields_all)
        + [pl.BlockSpec(memory_space=pltpu.SMEM)] * len(flag_ops)
        + [pl.BlockSpec(memory_space=pl.ANY)] * nfr,
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * n_up,
        out_shape=[shp(F.shape) for F in fields_all[:n_up]],
        # The entry buffers are dead after the (k==0, i==0) load; rows
        # the band grid never writes keep their entry values.
        input_output_aliases={f: f for f in range(n_up)},
        scratch_shapes=[pltpu.VMEM(F.shape, F.dtype) for F in fields_all]
        + [pltpu.VMEM((2, F.shape[1], F.shape[2]), F.dtype)
           for F in fields_all[:n_up]]
        + fr_scratch
        + [pltpu.SemaphoreType.DMA((len(fields_all),)),
           pltpu.SemaphoreType.DMA((n_up,))]
        + ([pltpu.SemaphoreType.DMA((nfr,))] if nfr else []),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=128 * 1024 * 1024,
            dimension_semantics=("arbitrary", "arbitrary")),
    )(*operands)
    out = [F[:, :ext_shapes[f][1], :ext_shapes[f][2]]
           for f, F in enumerate(out)]
    return tuple(central(F, f) for f, F in enumerate(out))


# ---------------------------------------------------------------------------
# The STREAMING banded chunk realization (HBM ping-pong, rolling VMEM window)
# ---------------------------------------------------------------------------
#
# The resident kernels above hold the full K-extended block in VMEM, so
# `fit_chunk_K` gates them off at exactly the headline shapes (the 160^3+
# refusal in `_vmem`).  The streaming realization below generalizes the
# diffusion ping-pong scheme (`diffusion_trapezoid._kernel`) to the
# engine's N-field/freeze-set/margin config: per iteration the x-row
# bands sweep the 2K-extended block through a rolling VMEM window of
# `lo + B + extras[f]` rows per field, writing B-row out slabs to an HBM
# ping-pong pair — the full extended block NEVER materializes in VMEM,
# so VMEM need is O(B * S1 * S2) instead of O(S0e * S1 * S2) and the
# tier admits wherever the band window fits.  HBM traffic per chunk is
# K reads + K writes of the extended fields (vs the per-step path's K
# reads + K writes of the fields PLUS K exchanges): only the exchange
# amortizes, which is what `igg.perf.bytes_per_step` models for the
# `.banded` tiers.
#
# Every band's window reads ALL-OLD values (the ping-pong source buffer
# holds the previous iteration), which is exactly the data the resident
# kernel's lag-slot scheme feeds its bands — so each family's proven
# `band_update` core transfers unchanged (lo margin 1), and derived
# cores come from :func:`band_core_from_window`.


def band_core_from_window(core, lo, n_up=None):
    """Derive a `band_update(*windows, bx=)` from a family's full-window
    `core(*fields)` (the same callable the window realizations evolve):
    apply the core to the small band windows (rows `[a-lo, a+bx+extras)`
    of each field — staggered-consistent shapes, so the shape-driven
    cores evaluate unchanged) and slice the central `bx` rows.  `lo`
    must be the per-iteration margin loss (`analysis.margin_after(1)`
    for spec families) so the central rows are at full validity
    distance from both window edges; `n_up` truncates cores that return
    const fields too."""
    def band_update(*Ws, bx):
        outs = core(*Ws)
        if n_up is not None:
            outs = outs[:n_up]
        return tuple(o[lo:lo + bx] for o in outs)

    return band_update


def banded_window_xla(fields, *, K, B, lo, modes, grid, ols, shapes, E,
                      band_update, extras, n_up, freeze_fields):
    """Pure-XLA realization of the streaming banded scheme: K iterations,
    each sweeping x-row bands over the previous iteration's buffers
    (ping-pong semantics — every band window reads all-OLD values) with
    clamped-duplicate margins at the buffer ends and the engine's
    per-band halo handling (:func:`band_halo`, the same callable the
    compiled kernels run).  Interpret/CPU meshes prove the banded data
    flow against the window-realization truth rung with this function;
    the contaminated shoulder rows the clamped margins produce differ
    from the window realization's but never reach the central window
    (the trapezoidal validity argument).  Returns the evolved extended
    buffers (updated fields first, const fields passed through);
    central slicing is the caller's."""
    import jax.numpy as jnp
    from jax import lax

    entry = tuple(fields)
    nd = fields[0].ndim
    n_fields = len(fields)
    ext_shapes = [tuple(F.shape) for F in fields]
    base = min(s[0] for s in ext_shapes)
    nb = base // B
    freeze = normalize_freeze(freeze_fields, nd)
    cfg = dict(modes=tuple(modes), ols=tuple(ols), E=E,
               ext_shapes=tuple(ext_shapes), shapes=tuple(shapes),
               freeze_fields=freeze_fields)

    any_open = any(modes[d] in ("oext", "frozen") for d in range(nd))
    flags = ([edge_flags(tuple(modes) + ("wrap",) * (3 - nd), grid)[j]
              for j in range(6)] if any_open else [0] * 6)

    # Chunk-entry freeze planes (whole planes; y/z ones band-sliced per
    # band below, the kernel's fr_vmem convention).
    frx, fryz_full = {}, {}
    for d in range(nd):
        if modes[d] not in ("oext", "frozen"):
            continue
        fr = E if modes[d] == "oext" else 0
        for f in freeze[d]:
            hi = fr + shapes[f][d] - 1
            for side, idx in ((0, fr), (1, hi)):
                p = jnp.squeeze(
                    lax.slice_in_dim(entry[f], idx, idx + 1, axis=d), d)
                if d == 0:
                    frx[(f, side)] = p
                else:
                    fryz_full[(f, d, side)] = p

    def one_iter(_, S):
        padded = []
        for f in range(n_fields):
            F = S[f]
            top = extras[f] - (ext_shapes[f][0] - base)
            parts = []
            if lo:
                parts.append(jnp.concatenate(
                    [lax.slice_in_dim(F, 0, 1, axis=0)] * lo, axis=0))
            parts.append(F)
            if top > 0:
                last = lax.slice_in_dim(F, ext_shapes[f][0] - 1,
                                        ext_shapes[f][0], axis=0)
                parts.append(jnp.concatenate([last] * top, axis=0))
            padded.append(jnp.concatenate(parts, axis=0)
                          if len(parts) > 1 else parts[0])

        def band(i, D):
            a = i * B
            Ws = [lax.dynamic_slice_in_dim(P, a, lo + B + extras[f],
                                           axis=0)
                  for f, P in enumerate(padded)]
            news = band_update(*Ws, bx=B)
            fryz = {key: lax.dynamic_slice_in_dim(p, a, B, axis=0)
                    for key, p in fryz_full.items()}
            news = band_halo(news, a, B, flags, frx, fryz, cfg)
            return tuple(
                lax.dynamic_update_slice_in_dim(D[f], news[f], a, axis=0)
                for f in range(n_up))

        # DST starts from the OLD buffers: rows the band grid never
        # writes (a staggered field's top face) keep their values,
        # exactly the compiled kernel's aliasing semantics.
        new_up = lax.fori_loop(0, nb, band, tuple(S[:n_up]))
        return (*new_up, *S[n_up:])

    return lax.fori_loop(0, K, one_iter, entry)


def _streaming_kernel(*refs, K, B, nb, lo, cfg, nfr, pads, band_update,
                      extras, stags):
    """The streaming banded chunk kernel: grid `(K, nb)`, HBM ping-pong
    (the `diffusion_trapezoid._kernel` scheme generalized).  Per program
    `(k, i)`: fetch each field's rolling window (rows
    `[i*B - lo, i*B + B + extras[f])`, clamped-duplicated at the buffer
    ends) from the iteration-k source — the input extended buffers at
    k=0, then the ping-pong pair by parity — compute the B-row band with
    the family's `band_update` + :func:`band_halo`, and write it to the
    iteration's destination (the other ping-pong buffer; the out buffers
    at k=K-1) through slot-alternated async DMA (drain both slots at
    each step boundary, wait the reused slot at i>=2, final drain —
    the diffusion out-write bookkeeping).  Const fields stream from
    their single HBM buffer every iteration (never resident: a 256^3
    coefficient would blow the budget the tier exists to escape)."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    ext_shapes = cfg["ext_shapes"]    # logical extended shapes
    modes = cfg["modes"]
    n_fields = len(ext_shapes)
    n_up = cfg["n_up"]
    freeze = normalize_freeze(cfg.get("freeze_fields", ()), 3)

    it = iter(refs)
    src_hbm = [next(it) for _ in range(n_fields)]   # padded extended fields
    flags_ref = next(it) if nfr else None           # SMEM (6,) i32
    fr_hbm = [next(it) for _ in range(nfr)]         # padded freeze planes
    outs, b0, b1 = [], [], []
    for _ in range(n_up):                           # (out, ping, pong) * n_up
        outs.append(next(it))
        b0.append(next(it))
        b1.append(next(it))
    wv = [next(it) for _ in range(n_fields)]        # rolling window scratch
    o2 = [next(it) for _ in range(n_up)]            # (2, B, S1p, S2p) slabs
    fr_v = [next(it) for _ in range(nfr)]
    wsem = next(it)
    osem = next(it)
    fsem = next(it) if nfr else None

    k = pl.program_id(0)
    i = pl.program_id(1)
    a = i * B
    sl = i % 2

    # One-time: freeze planes HBM -> VMEM; the ping buffer's staggered
    # tail rows seeded from the entry values (the pong buffer IS the
    # aliased input, so its tail is already correct).
    if nfr:
        @pl.when((k == 0) & (i == 0))
        def _():
            cs = [pltpu.make_async_copy(fr_hbm[j], fr_v[j], fsem.at[j])
                  for j in range(nfr)]
            for c in cs:
                c.start()
            for c in cs:
                c.wait()

    @pl.when((k == 0) & (i == 0))
    def _():
        for f in range(n_up):
            if stags[f]:
                c = pltpu.make_async_copy(
                    src_hbm[f].at[pl.ds(nb * B, stags[f])],
                    b0[f].at[pl.ds(nb * B, stags[f])], wsem.at[f])
                c.start()
                c.wait()

    # Out-write bookkeeping (the diffusion ping-pong scheme): drain both
    # slots at each step boundary, else wait the slot being reused.
    @pl.when((i == 0) & (k > 0))
    def _():
        for f in range(n_up):
            for s in (0, 1):
                pltpu.make_async_copy(o2[f].at[s], o2[f].at[s],
                                      osem.at[f, s]).wait()

    @pl.when(i >= 2)
    def _():
        for f in range(n_up):
            pltpu.make_async_copy(o2[f].at[sl], o2[f].at[sl],
                                  osem.at[f, sl]).wait()

    # Rolling-window fetches, synchronous (once per band per field; the
    # out-writes overlap the next band's fetch+compute).  Clamped
    # duplicate rows at the buffer ends feed only shoulder rows outside
    # the validity trapezoid.
    def fetch(f, src):
        w = wv[f]
        e = extras[f]
        stg = stags[f] if f < n_up else ext_shapes[f][0] - nb * B
        nrows = lo + B + e

        def copy(src_at, w_at):
            c = pltpu.make_async_copy(src_at, w_at, wsem.at[f])
            c.start()
            c.wait()

        @pl.when(i == 0)
        def _():
            for r in range(lo):
                copy(src.at[pl.ds(0, 1)], w.at[pl.ds(r, 1)])
            copy(src.at[pl.ds(0, B + e)], w.at[pl.ds(lo, B + e)])

        if nb > 2:
            @pl.when((i > 0) & (i < nb - 1))
            def _():
                copy(src.at[pl.ds(a - lo, nrows)], w.at[pl.ds(0, nrows)])

        @pl.when(i == nb - 1)
        def _():
            copy(src.at[pl.ds(a - lo, lo + B + stg)],
                 w.at[pl.ds(0, lo + B + stg)])
            for r in range(stg, e):
                copy(src.at[pl.ds(nb * B + stg - 1, 1)],
                     w.at[pl.ds(lo + B + r, 1)])

    for f in range(n_fields):
        if f < n_up:
            @pl.when(k == 0)
            def _(f=f):
                fetch(f, src_hbm[f])

            @pl.when((k > 0) & (k % 2 == 1))
            def _(f=f):
                fetch(f, b0[f])

            @pl.when((k > 0) & (k % 2 == 0))
            def _(f=f):
                fetch(f, b1[f])
        else:
            fetch(f, src_hbm[f])

    def logical(W, f):
        return W[:, :ext_shapes[f][1], :ext_shapes[f][2]]

    Ws = [logical(wv[f][...], f) for f in range(n_fields)]
    news = band_update(*Ws, bx=B)

    # Per-band halo handling (freeze planes band-sliced to logical
    # extents; SMEM flags read as scalars) — the resident kernel's exact
    # assembly.
    flags = ([flags_ref[j] for j in range(6)] if nfr else [0] * 6)
    frx, fryz = {}, {}
    j = 0
    for d in range(3):
        if modes[d] not in ("oext", "frozen"):
            continue
        for f in freeze[d]:
            pl_shape = [ext_shapes[f][x] for x in range(3) if x != d]
            for side in (0, 1):
                if d == 0:
                    frx[(f, side)] = fr_v[j][...][:pl_shape[0],
                                                  :pl_shape[1]]
                else:
                    fryz[(f, d, side)] = fr_v[j][pl.ds(a, B)][
                        :, :pl_shape[1]]
                j += 1
    news = band_halo(news, a, B, flags, frx, fryz, cfg)

    # Stage the band in this program's out slot, padded back with the
    # window's old trailing columns, and launch the async put to the
    # iteration's destination.
    for f in range(n_up):
        new = news[f]
        pady, padz = pads[f]
        old = wv[f][pl.ds(lo, B)]
        if padz:
            new = jnp.concatenate([new, old[:, :new.shape[1], -padz:]],
                                  axis=2)
        if pady:
            new = jnp.concatenate([new, old[:, -pady:, :]], axis=1)
        o2[f][pl.ds(sl, 1)] = new[None]

        def put(dst, f=f):
            pltpu.make_async_copy(o2[f].at[sl], dst.at[pl.ds(a, B)],
                                  osem.at[f, sl]).start()

        @pl.when(k == K - 1)
        def _(put=put, f=f):
            put(outs[f])

        @pl.when((k < K - 1) & (k % 2 == 0))
        def _(put=put, f=f):
            put(b0[f])

        @pl.when((k < K - 1) & (k % 2 == 1))
        def _(put=put, f=f):
            put(b1[f])

    # Final drain: the last two out DMAs have no successor to wait them.
    @pl.when((k == K - 1) & (i == nb - 1))
    def _():
        for f in range(n_up):
            pltpu.make_async_copy(o2[f].at[1 - sl], o2[f].at[1 - sl],
                                  osem.at[f, 1 - sl]).wait()
            pltpu.make_async_copy(o2[f].at[sl], o2[f].at[sl],
                                  osem.at[f, sl]).wait()


def streaming_chunk_call(exts, const_exts, *, K, B, modes, grid, ols,
                         shapes, E, band_update, extras, freeze_fields,
                         lo=1, interpret=False):
    """Advance K coupled iterations on the extended buffers with the
    STREAMING banded kernel — the chunk realization that never holds the
    K-extended block in VMEM; returns the updated fields' central local
    blocks.  Same contract as :func:`resident_chunk_call` (`exts`
    updated/aliased, `const_exts` loop-invariant, `extras[f]` the high
    read margin, `band_update(*windows, bx=)` the family band core) plus
    `lo`, the low read margin (1 for the hand band cores; the
    per-iteration margin loss for :func:`band_core_from_window` cores).
    In interpret mode the chunk runs :func:`banded_window_xla` — the
    pure-XLA realization of the SAME banded data flow — so CPU meshes
    prove the scheme itself against the window-realization truth rung,
    not just the admission gates."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_up = len(exts)
    fields = list(exts) + list(const_exts)
    ext_shapes = [tuple(x.shape) for x in fields]
    nd = exts[0].ndim

    def central(F, f):
        return central_window(F, shapes[f], E, modes)

    if interpret:
        out = banded_window_xla(
            fields, K=K, B=B, lo=lo, modes=modes, grid=grid, ols=ols,
            shapes=shapes, E=E, band_update=band_update, extras=extras,
            n_up=n_up, freeze_fields=freeze_fields)
        return tuple(central(F, f) for f, F in enumerate(out[:n_up]))

    base = min(s[0] for s in ext_shapes)
    nb = base // B
    stags = [ext_shapes[f][0] - base for f in range(n_up)]
    freeze = normalize_freeze(freeze_fields, nd)
    cfg = dict(modes=tuple(modes), ols=tuple(ols[:n_up]),
               ext_shapes=tuple(ext_shapes), E=E,
               shapes=tuple(shapes[:n_up]), n_up=n_up,
               freeze_fields=freeze_fields)

    def padded(F):
        s = F.shape
        py = _pad8(s[1]) - s[1]
        pz = _pad128(s[2]) - s[2]
        if py or pz:
            F = jnp.pad(F, [(0, 0), (0, py), (0, pz)])
        return F

    fields_all = [padded(F) for F in fields]
    pads = [(_pad8(s[1]) - s[1], _pad128(s[2]) - s[2])
            for s in ext_shapes[:n_up]]

    fr_planes = []
    flag_ops = []
    any_open = any(m in ("oext", "frozen") for m in modes)
    if any_open:
        for d in range(3):
            if modes[d] not in ("oext", "frozen"):
                continue
            fr = E if modes[d] == "oext" else 0
            for f in freeze[d]:
                hi = fr + shapes[f][d] - 1
                for idx in (fr, hi):
                    p = jnp.squeeze(
                        lax.slice_in_dim(exts[f], idx, idx + 1, axis=d), d)
                    ps = p.shape
                    py = _pad8(ps[0]) - ps[0]
                    pz = _pad128(ps[1]) - ps[1]
                    if py or pz:
                        p = jnp.pad(p, [(0, py), (0, pz)])
                    fr_planes.append(p)
        if fr_planes:
            flag_ops = [edge_flags(modes, grid)]
    nfr = len(fr_planes)

    kern = partial(_streaming_kernel, K=K, B=B, nb=nb, lo=lo, cfg=cfg,
                   nfr=nfr, pads=pads, band_update=band_update,
                   extras=extras, stags=stags)

    operands = [*fields_all, *flag_ops, *fr_planes]
    vmas = [getattr(getattr(x, "aval", None), "vma", None)
            for x in operands]
    vma = frozenset().union(*[v for v in vmas if v])

    def shp(s):
        return (jax.ShapeDtypeStruct(s, exts[0].dtype, vma=vma) if vma
                else jax.ShapeDtypeStruct(s, exts[0].dtype))

    # Per updated field: out + ping + pong, all full padded extended
    # shape; the input buffer aliases the PONG slot (first written at
    # k=1 — dead after the k=0 reads), so unwritten tail rows keep their
    # entry values there for the odd-iteration fetches.
    out_shapes = []
    aliases = {}
    for f in range(n_up):
        out_shapes += [shp(fields_all[f].shape)] * 3
        aliases[f] = 3 * f + 2

    # Scratch order MUST mirror the kernel's unpack: rolling windows,
    # out slot pairs, freeze-plane VMEM, window semaphores, out
    # semaphores, then the freeze-plane semaphore LAST.
    win_scratch = [
        pltpu.VMEM((lo + B + extras[f], F.shape[1], F.shape[2]), F.dtype)
        for f, F in enumerate(fields_all)]
    out = pl.pallas_call(
        kern,
        name="chunk_streaming",
        grid=(K, nb),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * len(fields_all)
        + [pl.BlockSpec(memory_space=pltpu.SMEM)] * len(flag_ops)
        + [pl.BlockSpec(memory_space=pl.ANY)] * nfr,
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * (3 * n_up),
        out_shape=out_shapes,
        input_output_aliases=aliases,
        scratch_shapes=win_scratch
        + [pltpu.VMEM((2, B, F.shape[1], F.shape[2]), F.dtype)
           for F in fields_all[:n_up]]
        + [pltpu.VMEM(p.shape, p.dtype) for p in fr_planes]
        + [pltpu.SemaphoreType.DMA((len(fields_all),)),
           pltpu.SemaphoreType.DMA((n_up, 2))]
        + ([pltpu.SemaphoreType.DMA((nfr,))] if nfr else []),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=128 * 1024 * 1024,
            dimension_semantics=("arbitrary", "arbitrary")),
    )(*operands)
    evolved = [out[3 * f][:, :ext_shapes[f][1], :ext_shapes[f][2]]
               for f in range(n_up)]
    return tuple(central(F, f) for f, F in enumerate(evolved))
