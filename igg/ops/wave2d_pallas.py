"""Fused Pallas tiers for wave2d — the 2-D staggered leapfrog's missing
speed rungs (ROADMAP item 1: "wave2d has neither a Mosaic nor a chunk
tier"), both generated from the shared K-step chunk engine
(`igg.ops.chunk_engine`).

**Per-step Mosaic tier** (`fused_wave2d_step`): ONE `pallas_call`
computes the whole coupled leapfrog update — `Vx`/`Vy` from the pressure
gradient, then the pressure from the FRESH velocity divergence
(Gauss-Seidel flavor) — reading each field once and writing it once,
where the XLA composition pays a separate HBM-bound fusion per
sub-update; the grouped halo update then runs through the existing
exchange engine (`igg.update_halo_local`), so the step's semantics are
EXACTLY the sequential composition `wave2d.compute_step` +
`update_halo_local` on every mesh and boundary condition.  The kernel is
a single whole-block program (2-D fields are plane-sized, not
volume-sized — the VMEM gate in `wave2d_pallas_supported` does the
accounting), interpret-capable, so CPU meshes run the real kernel body.

**2-D chunk tier** (`fused_wave2d_chunk_steps`): K-step trapezoidal
temporal blocking over the exchanged mesh dims — both fields extended
`E = 2K` deep per split dim by the engine's grouped slab ppermutes (one
pair per dim for all three staggered fields), K steps evolved with NO
exchange (the coupled chain loses at most 2 rows of validity per side
per step: the pressure reads the fresh velocities which read the
pressure at +-1 — the same radius-2 contract as the Stokes chain, so
`2K` margins hold the front exactly), central blocks sliced out.
PERIODIC dims only: the per-step path updates the pressure's boundary
plane full-shape and the open-boundary no-write interplay differs per
field, so open meshes are refused with a structured Admission (the
per-step tiers serve them) rather than risking silently-wrong physics.
Two realizations: the engine's pure-XLA window loop (interpret mode —
the 8-device CPU mesh equivalence tests), and a whole-window
VMEM-resident Mosaic kernel (grid `(K,)`, all three extended fields in
VMEM scratch for the whole chunk, one HBM read + one write per chunk —
`3(R+W)/K` traffic per step; TPU-gated test in `tests/test_mega_tpu.py`,
verify-on-first-use guarding production dispatch).

Both tiers ride the `wave2d` degradation ladder
(`wave2d.make_step`: `wave2d.chunk` → `wave2d.mosaic` → `wave2d.xla`).
"""

from __future__ import annotations

from functools import partial

from ._vmem import chunk_budget, fit_chunk_K
from .chunk_engine import (admit_chunk_common, admit_send_slabs, dim_modes,
                           extend_fields, field_ols, run_chunks,
                           whole_window_chunk_call, window_chunk_xla)


def _field_shapes(shape):
    """Local shapes of (P, Vx, Vy) from the unstaggered P shape."""
    S0, S1 = shape
    return [(S0, S1), (S0 + 1, S1), (S0, S1 + 1)]


def _compute(P, Vx, Vy, *, dx, dy, dt, rho, bulk):
    """The pure coupled leapfrog update (no halo exchange) —
    `wave2d.compute_step`, the single source of arithmetic truth shared
    with the XLA composition (`bulk` is the model's `K`, renamed here so
    the chunk depth keeps the trapezoid modules' `K` convention)."""
    from ..models.wave2d import compute_step

    return compute_step(P, Vx, Vy, dx=dx, dy=dy, dt=dt, rho=rho, K=bulk)


# ---------------------------------------------------------------------------
# Per-step Mosaic tier
# ---------------------------------------------------------------------------

def _whole_block_vmem(shapes, itemsize: int = 4) -> int:
    """The shared whole-block footprint model (round 17: moved next to
    the budget it gates, `igg.ops._vmem.whole_block_vmem`)."""
    from ._vmem import whole_block_vmem

    return whole_block_vmem(shapes, itemsize)


def wave2d_pallas_supported(grid, P, interpret: bool = False):
    """Whether the fused per-step kernel applies: 2-D decomposition
    (`dims[2] == 1`), overlap-2 grid, unstaggered 2-D pressure matching
    the grid block, and — in compiled mode — the three whole blocks
    fitting the VMEM budget.  Any periodicity: the halo half of the step
    is the existing exchange engine.  Returns an
    :class:`igg.degrade.Admission`."""
    from ..degrade import Admission

    if grid.overlaps != (2, 2, 2):
        return Admission.no(f"grid overlaps {grid.overlaps} != (2, 2, 2)")
    if getattr(P, "ndim", 0) != 2:
        return Admission.no(f"field rank {getattr(P, 'ndim', 0)} != 2")
    if grid.dims[2] != 1 or grid.nxyz[2] != 1:
        return Admission.no(f"grid is not a 2-D decomposition "
                            f"(dims={tuple(grid.dims)}, nz={grid.nxyz[2]})")
    s = tuple(grid.local_shape_any(P))
    if s != tuple(grid.nxyz[:2]):
        return Admission.no(f"local shape {s} != grid block "
                            f"{tuple(grid.nxyz[:2])}")
    if s[0] < 4 or s[1] < 4:
        return Admission.no(f"local block {s} too small (needs x >= 4, "
                            f"y >= 4)")
    if not interpret:
        need = _whole_block_vmem(_field_shapes(s))
        if need > chunk_budget():
            return Admission.no(f"whole-block working set {need} bytes "
                                f"exceeds the VMEM budget "
                                f"{chunk_budget()}")
    return Admission.yes()


def _step_kernel(p_ref, vx_ref, vy_ref, op_ref, ovx_ref, ovy_ref, *, scal):
    P, Vx, Vy = p_ref[...], vx_ref[...], vy_ref[...]
    Pn, Vxn, Vyn = _compute(P, Vx, Vy, **scal)
    op_ref[...] = Pn
    ovx_ref[...] = Vxn
    ovy_ref[...] = Vyn


def _call_step_kernel(P, Vx, Vy, scal, interpret):
    import jax
    from jax.experimental import pallas as pl

    operands = [P, Vx, Vy]
    vmas = [getattr(getattr(x, "aval", None), "vma", None)
            for x in operands]
    vma = frozenset().union(*[v for v in vmas if v])

    def shp(a):
        return (jax.ShapeDtypeStruct(a.shape, a.dtype, vma=vma) if vma
                else jax.ShapeDtypeStruct(a.shape, a.dtype))

    kwargs = {}
    if not interpret:
        from jax.experimental.pallas import tpu as pltpu

        from ._vmem import vmem_limit

        kwargs["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit(
                _whole_block_vmem([a.shape for a in operands])))
    return pl.pallas_call(
        partial(_step_kernel, scal=scal),
        name="wave2d_step",
        out_shape=tuple(shp(a) for a in operands),
        interpret=interpret,
        **kwargs,
    )(*operands)


def fused_wave2d_step(P, Vx, Vy, *, dx, dy, dt, rho, K,
                      interpret: bool = False):
    """One fused wave2d step `(P, Vx, Vy) -> (P', Vx', Vy')` — the whole
    coupled update in ONE kernel, then the grouped halo update through
    the exchange engine.  Semantics are exactly the sequential
    composition (`wave2d.local_step`) on every mesh and boundary
    condition.  Call inside SPMD code (`igg.sharded` / shard_map)."""
    from .. import halo

    scal = dict(dx=dx, dy=dy, dt=dt, rho=rho, bulk=K)
    Pn, Vxn, Vyn = _call_step_kernel(P, Vx, Vy, scal, interpret)
    return halo.update_halo_local(Pn, Vxn, Vyn)


def fused_wave2d_steps(P, Vx, Vy, *, n_inner, dx, dy, dt, rho, K,
                       interpret: bool = False):
    """`n_inner` fused steps in one `lax.fori_loop`."""
    from jax import lax

    return lax.fori_loop(
        0, n_inner,
        lambda _, S: tuple(fused_wave2d_step(*S, dx=dx, dy=dy, dt=dt,
                                             rho=rho, K=K,
                                             interpret=interpret)),
        (P, Vx, Vy))


# ---------------------------------------------------------------------------
# The 2-D chunk tier
# ---------------------------------------------------------------------------

def wave2d_chunk_supported(grid, shape, K: int, n_inner: int, dtype,
                           interpret: bool = False):
    """Whether the K-step wave2d chunk tier applies: the per-step
    kernel's prerequisites, PERIODIC dims only (open-boundary no-write
    interplay differs per field on this family — the per-step tiers
    serve open meshes), at least one full chunk, `E = 2K` send slabs
    inside every split dimension's block (per-field staggered ol), and
    the extended working set within the VMEM budget.  Returns an
    :class:`igg.degrade.Admission`."""
    import numpy as np

    from ..degrade import Admission

    common = admit_chunk_common(grid, K, n_inner)
    if common is not None:
        return common
    if grid.overlaps != (2, 2, 2):
        return Admission.no(f"grid overlaps {grid.overlaps} != (2, 2, 2)")
    if grid.dims[2] != 1 or grid.nxyz[2] != 1:
        return Admission.no(f"grid is not a 2-D decomposition "
                            f"(dims={tuple(grid.dims)}, nz={grid.nxyz[2]})")
    if tuple(shape) != tuple(grid.nxyz[:2]):
        return Admission.no(f"local shape {tuple(shape)} != grid block "
                            f"{tuple(grid.nxyz[:2])}")
    if np.dtype(dtype) != np.float32:
        return Admission.no(f"dtype {np.dtype(dtype)} is not float32")
    modes = dim_modes(grid)[:2]
    if any(m in ("oext", "frozen") for m in modes):
        return Admission.no(
            f"open (non-periodic) dimensions {modes}: the wave2d chunk "
            f"tier serves periodic meshes only (the per-step tiers carry "
            f"open boundaries)")
    E = 2 * K
    shapes = _field_shapes(shape)
    ols = field_ols(grid, shapes)
    slabs = admit_send_slabs(shapes, ols, E, modes, grid=grid)
    if slabs is not None:
        return slabs
    exts = [tuple(s[d] + (2 * E if modes[d] == "ext" else 0)
                  for d in range(2)) for s in shapes]
    need = _whole_block_vmem(exts)
    if need > chunk_budget():
        return Admission.no(f"extended working set {need} bytes exceeds "
                            f"the VMEM budget {chunk_budget()}")
    return Admission.yes()


def fit_wave2d_K(grid, shape, n_inner: int, dtype,
                 interpret: bool = False, kmax: int = 8) -> int:
    """Largest admissible chunk depth K <= kmax (halving, >= 2;
    `_vmem.fit_chunk_K`); 0 when none applies."""
    return fit_chunk_K(
        lambda K: wave2d_chunk_supported(grid, tuple(shape), K, n_inner,
                                         dtype, interpret=interpret),
        kmax)


def _window_core(kw):
    def core(P, Vx, Vy):
        return _compute(P, Vx, Vy, **kw)

    return core


def _window_steps_xla(Pe, Vxe, Vye, *, Kc, E, modes, grid, kw, ols,
                      shapes):
    """Pure-XLA realization of the chunk evolution (interpret mode):
    the engine's generic window loop — periodic modes only, so the halo
    handling is pure staggered self-wrap on wrap dims."""
    return window_chunk_xla((Pe, Vxe, Vye), K=Kc, E=E, modes=modes,
                            grid=grid, ols=ols, shapes=shapes,
                            freeze_fields=(), core=_window_core(kw))


def _chunk_call(exts, *, Kc, modes, grid, kw, ols, shapes,
                interpret=False):
    """Advance Kc coupled steps on the extended buffers; returns the
    three central local blocks.  Round 17: the whole-window resident
    kernel moved into the chunk engine (`whole_window_chunk_call` — the
    same grid-`(Kc,)` scheme, generalized to N fields and open-dim
    freeze planes so `igg.stencil`'s generated chunk tiers instantiate
    it too); wave2d passes its proven periodic-only config."""
    E = 2 * Kc
    return whole_window_chunk_call(
        list(exts), K=Kc, E=E, modes=modes, grid=grid, ols=ols,
        shapes=shapes, core=_window_core(kw), freeze_fields=(),
        window_fallback=lambda: _window_steps_xla(
            *exts, Kc=Kc, E=E, modes=modes, grid=grid, kw=kw, ols=ols,
            shapes=shapes),
        interpret=interpret)


def fused_wave2d_chunk_steps(P, Vx, Vy, *, n_inner: int, K: int,
                             dx, dy, dt, rho, bulk,
                             interpret: bool = False):
    """Advance `n_inner // K` full K-step chunks (warm-up and remainder
    are the caller's, through the per-step tier); returns
    `(P, Vx, Vy, steps_done)`.

    Entry contract: OVERLAP-CONSISTENT, exchange-fresh state (the model
    init evolved by per-step iterations is; `Vx`'s x-overlap is 3, so
    `update_halo` alone cannot synchronize arbitrary interior
    duplicates — the Stokes chunk tier's contract).  Call inside SPMD
    code (`igg.sharded` / shard_map)."""
    from .. import shared

    grid = shared.global_grid()
    modes = dim_modes(grid)[:2]
    E = 2 * K
    shapes = _field_shapes(P.shape)
    ols = field_ols(grid, shapes)
    kw = dict(dx=dx, dy=dy, dt=dt, rho=rho, bulk=bulk)

    def one(P, Vx, Vy):
        exts = extend_fields([P, Vx, Vy], ols, E, grid, modes)
        return _chunk_call(exts, Kc=K, modes=modes, grid=grid, kw=kw,
                           ols=ols, shapes=shapes, interpret=interpret)

    *S, done = run_chunks((P, Vx, Vy), n_inner=n_inner, K=K, one_chunk=one)
    return (*S, done)
