"""Lowering — one spec, three realizations on the tier ladder.

A single expression evaluator (:func:`apply_updates`) is the arithmetic
truth shared by every rung, the repo-wide design rule that makes
verify-on-first-use meaningful:

- **XLA composition truth** (:func:`local_step_fn`): the update chain
  as slice algebra (`igg.ops.stencil.interior_add` for no-write
  increments, plain expressions for full-shape assigns) + ONE grouped
  `igg.update_halo_local` over every field — generated for free from
  the spec, serving any mesh, boundary condition, and dtype.
- **Per-step Mosaic tier** (:func:`fused_spec_step`): the whole chain
  in ONE whole-block `pallas_call` (each field read once, written
  once), then the grouped exchange — the wave2d-mosaic scheme,
  interpret-capable so CPU meshes run the real kernel body.
- **K-step chunk tier** (:func:`spec_chunk_steps`): temporal blocking
  on the shared chunk engine — fields extended `E` deep per split dim
  by the engine's grouped slab ppermutes with `E` COMPUTED by the
  analyzer's margin recurrence (`Analysis.margin_after(K)`), K steps
  evolved without exchange (the engine's pure-XLA window loop in
  interpret mode, the whole-window resident Mosaic kernel compiled),
  central blocks sliced out.  Open dims are admitted only when the
  analyzer's boundary-validity recurrence proves the plane-freeze
  scheme stays bit-exact (`Analysis.open_chunk_ok`).

Scalar subtrees evaluate in host floats and float-vs-array ops go
through the jnp dunders, so a spec mirroring a hand-written module
expression-for-expression produces BITWISE the hand module's results
(`tests/test_stencil.py` pins spec-wave2d against `igg/models/wave2d.py`
on every rung).
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Sequence

from ..shared import GridError
from .analyze import Analysis
from .spec import (BinOp, Const, Expr, ParamRef, Read, StencilSpec, UnOp,
                   Where)

__all__ = ["apply_updates", "local_step_fn", "fused_spec_step",
           "spec_chunk_steps", "mosaic_supported_fn", "chunk_supported_fn",
           "fit_spec_K", "whole_block_vmem"]

_OPS = {
    "add": lambda a, b: a + b,
    "sub": lambda a, b: a - b,
    "mul": lambda a, b: a * b,
    "truediv": lambda a, b: a / b,
    "pow": lambda a, b: a ** b,
    "lt": lambda a, b: a < b,
    "le": lambda a, b: a <= b,
    "gt": lambda a, b: a > b,
    "ge": lambda a, b: a >= b,
    "eq": lambda a, b: a == b,
    "ne": lambda a, b: a != b,
}


def _eval(expr: Expr, arrays: Dict[str, object], starts, extents, coeffs):
    """Evaluate one expression over the write region: `starts[d]` is the
    region's first index in the OUTPUT field's index space, `extents[d]`
    its size; a Read slices its source at `starts + offset` (the
    analyzer guaranteed the slice is in bounds).  Scalars stay python
    scalars until they meet an array."""
    if isinstance(expr, Const):
        return expr.value
    if isinstance(expr, ParamRef):
        try:
            return coeffs[expr.param.name]
        except KeyError:
            raise GridError(f"igg.stencil: param {expr.param.name!r} has "
                            f"no bound value.")
    if isinstance(expr, Read):
        A = arrays[expr.field.name]
        sl = tuple(slice(starts[d] + expr.offset[d],
                         starts[d] + expr.offset[d] + extents[d])
                   for d in range(len(starts)))
        return A[sl]
    if isinstance(expr, UnOp):
        return -_eval(expr.a, arrays, starts, extents, coeffs)
    if isinstance(expr, BinOp):
        return _OPS[expr.op](_eval(expr.a, arrays, starts, extents, coeffs),
                             _eval(expr.b, arrays, starts, extents, coeffs))
    if isinstance(expr, Where):
        c = _eval(expr.cond, arrays, starts, extents, coeffs)
        a = _eval(expr.a, arrays, starts, extents, coeffs)
        b = _eval(expr.b, arrays, starts, extents, coeffs)
        if isinstance(c, bool):
            return a if c else b
        import jax.numpy as jnp

        return jnp.where(c, a, b)
    raise GridError(f"igg.stencil: cannot lower {expr!r}.")


def apply_updates(spec: StencilSpec, fields: Sequence, coeffs: Dict):
    """One step of the spec's update chain over same-shaped arrays
    (local blocks OR extended chunk windows — the evaluator is
    shape-driven).  Later updates read the fresh values of earlier
    ones.  Returns the new field tuple in spec order."""
    from ..ops.stencil import interior_add

    arrays = {f.name: a for f, a in zip(spec.fields, fields)}
    for u in spec.updates:
        U = arrays[u.field.name]
        starts = [lo for lo, _ in u.pad]
        extents = [U.shape[d] - lo - hi
                   for d, (lo, hi) in enumerate(u.pad)]
        val = _eval(u.expr, arrays, starts, extents, coeffs)
        if u.mode == "add":
            arrays[u.field.name] = interior_add(U, val, tuple(u.pad))
        else:
            arrays[u.field.name] = val
    return tuple(arrays[f.name] for f in spec.fields)


def local_step_fn(spec: StencilSpec, coeffs: Dict):
    """The per-device (inside-SPMD) step: the update chain + one grouped
    halo update over every field — the generated XLA composition truth,
    and the member-step shape `igg.run_ensemble` consumes."""
    from .. import halo

    def step(*fields):
        out = apply_updates(spec, fields, coeffs)
        new = halo.update_halo_local(*out)
        return new if isinstance(new, tuple) else (new,)

    return step


# ---------------------------------------------------------------------------
# Per-step Mosaic tier
# ---------------------------------------------------------------------------

def whole_block_vmem(shapes, itemsize: int = 4) -> int:
    """The shared whole-block footprint model
    (`igg.ops._vmem.whole_block_vmem` — one model next to the budget it
    is compared against, shared with the wave2d gates)."""
    from ..ops._vmem import whole_block_vmem as model

    return model(shapes, itemsize)


def _field_shapes(spec: StencilSpec, base_shape):
    """Local shapes of every field from the grid block shape."""
    return [tuple(base_shape[d] + f.stagger[d] for d in range(spec.ndim))
            for f in spec.fields]


def mosaic_supported_fn(spec: StencilSpec):
    """`supported(grid, field, interpret=False)` for the generated
    per-step Mosaic tier: overlap-2 grid, rank-matching decomposition
    (2-D specs need `dims[2] == 1`), field-0 local shape matching the
    grid block + staggering, minimum block size, and — compiled — the
    whole-block working set within the VMEM budget.  Any periodicity:
    the halo half of the step is the existing exchange engine."""
    from ..degrade import Admission
    from ..ops._vmem import chunk_budget

    def supported(grid, A, interpret: bool = False):
        nd = spec.ndim
        if grid.overlaps[:nd] != (2,) * nd:
            return Admission.no(f"grid overlaps {grid.overlaps} != 2 on "
                                f"the spec's {nd} dims")
        if getattr(A, "ndim", 0) != nd:
            return Admission.no(f"field rank {getattr(A, 'ndim', 0)} != "
                                f"spec rank {nd}")
        if nd == 2 and (grid.dims[2] != 1 or grid.nxyz[2] != 1):
            return Admission.no(
                f"grid is not a 2-D decomposition "
                f"(dims={tuple(grid.dims)}, nz={grid.nxyz[2]})")
        s = tuple(grid.local_shape_any(A))
        want = tuple(grid.nxyz[d] + spec.fields[0].stagger[d]
                     for d in range(nd))
        if s != want:
            return Admission.no(f"local shape {s} != grid block {want} "
                                f"(field {spec.fields[0].name!r})")
        base = tuple(grid.nxyz[:nd])
        if any(b < 4 for b in base):
            return Admission.no(f"local block {base} too small (needs "
                                f">= 4 cells per dim)")
        if not interpret:
            need = whole_block_vmem(_field_shapes(spec, base))
            if need > chunk_budget():
                return Admission.no(
                    f"whole-block working set {need} bytes exceeds the "
                    f"VMEM budget {chunk_budget()}")
        return Admission.yes()

    return supported


def _step_kernel(*refs, spec, coeffs):
    n = len(spec.fields)
    fields = [r[...] for r in refs[:n]]
    news = apply_updates(spec, fields, coeffs)
    for r, v in zip(refs[n:], news):
        r[...] = v


def fused_spec_step(spec: StencilSpec, coeffs: Dict, fields,
                    interpret: bool = False):
    """One fused step: the whole update chain in ONE kernel, then the
    grouped halo update through the exchange engine — semantics exactly
    the sequential composition on every mesh and boundary condition.
    Call inside SPMD code (`igg.sharded` / shard_map)."""
    import jax
    from jax.experimental import pallas as pl

    from .. import halo

    operands = list(fields)
    vmas = [getattr(getattr(x, "aval", None), "vma", None)
            for x in operands]
    vma = frozenset().union(*[v for v in vmas if v]) if any(vmas) else None

    def shp(a):
        return (jax.ShapeDtypeStruct(a.shape, a.dtype, vma=vma) if vma
                else jax.ShapeDtypeStruct(a.shape, a.dtype))

    kwargs = {}
    if not interpret:
        from jax.experimental.pallas import tpu as pltpu

        from ..ops._vmem import vmem_limit

        kwargs["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit(
                whole_block_vmem([a.shape for a in operands])))
    news = pl.pallas_call(
        partial(_step_kernel, spec=spec, coeffs=coeffs),
        name="stencil_spec_step",
        out_shape=tuple(shp(a) for a in operands),
        interpret=interpret,
        **kwargs,
    )(*operands)
    out = halo.update_halo_local(*news)
    return out if isinstance(out, tuple) else (out,)


def fused_spec_steps(spec, coeffs, fields, *, n_inner,
                     interpret: bool = False):
    """`n_inner` fused steps in one `lax.fori_loop`."""
    from jax import lax

    return lax.fori_loop(
        0, n_inner,
        lambda _, S: tuple(fused_spec_step(spec, coeffs, S,
                                           interpret=interpret)),
        tuple(fields))


# ---------------------------------------------------------------------------
# K-step chunk tier (on the shared chunk engine)
# ---------------------------------------------------------------------------

def chunk_supported_fn(spec: StencilSpec, analysis: Analysis):
    """`supported(grid, shape, K, n_inner, dtype, interpret=False)` for
    the generated chunk tier: the per-step kernel's prerequisites, at
    least one full chunk, analyzer-computed `E = margin_after(K)` send
    slabs inside every split dimension's block, open dims only when the
    boundary-validity recurrence admits them, and the extended working
    set within the VMEM budget."""
    import numpy as np

    from ..degrade import Admission
    from ..ops._vmem import chunk_budget
    from ..ops.chunk_engine import (admit_chunk_common, admit_send_slabs,
                                    dim_modes, field_ols)

    def supported(grid, shape, K, n_inner, dtype, interpret: bool = False):
        nd = spec.ndim
        common = admit_chunk_common(grid, K, n_inner)
        if common is not None:
            return common
        if grid.overlaps[:nd] != (2,) * nd:
            return Admission.no(f"grid overlaps {grid.overlaps} != 2 on "
                                f"the spec's {nd} dims")
        if nd == 2 and (grid.dims[2] != 1 or grid.nxyz[2] != 1):
            return Admission.no(
                f"grid is not a 2-D decomposition "
                f"(dims={tuple(grid.dims)}, nz={grid.nxyz[2]})")
        if tuple(shape) != tuple(grid.nxyz[:nd]):
            return Admission.no(f"local shape {tuple(shape)} != grid "
                                f"block {tuple(grid.nxyz[:nd])}")
        if np.dtype(dtype) != np.float32:
            return Admission.no(f"dtype {np.dtype(dtype)} is not float32")
        modes = dim_modes(grid)[:nd]
        if any(m in ("oext", "frozen") for m in modes) \
                and not analysis.open_chunk_ok(K):
            return Admission.no(
                f"open (non-periodic) dimensions {modes}: the analyzer's "
                f"boundary-validity recurrence refuses the plane-freeze "
                f"chunk evolution for spec {spec.name!r} (a "
                f"boundary-adjacent read would land on shoulder garbage); "
                f"the per-step tiers carry open boundaries")
        E = analysis.margin_after(K)
        shapes = _field_shapes(spec, tuple(shape))
        ols = field_ols(grid, shapes)
        slabs = admit_send_slabs(shapes, ols, E, modes, grid=grid)
        if slabs is not None:
            return slabs
        exts = [tuple(s[d] + (2 * E if modes[d] in ("ext", "oext") else 0)
                      for d in range(nd)) for s in shapes]
        need = whole_block_vmem(exts)
        if need > chunk_budget():
            return Admission.no(f"extended working set {need} bytes "
                                f"exceeds the VMEM budget "
                                f"{chunk_budget()}")
        return Admission.yes()

    return supported


def fit_spec_K(spec, analysis, grid, shape, n_inner, dtype,
               interpret: bool = False, kmax: int = 8) -> int:
    """Largest admissible chunk depth K <= kmax (halving, >= 2); 0 when
    none applies."""
    from ..ops._vmem import fit_chunk_K

    sup = chunk_supported_fn(spec, analysis)
    return fit_chunk_K(
        lambda K: sup(grid, tuple(shape), K, n_inner, dtype,
                      interpret=interpret), kmax)


def spec_chunk_steps(spec: StencilSpec, analysis: Analysis, coeffs, fields,
                     *, n_inner: int, K: int, interpret: bool = False):
    """Advance `n_inner // K` full K-step chunks (warm-up and remainder
    are the caller's, through the per-step tier); returns
    `(*fields, steps_done)`.  Entry contract: overlap-consistent,
    exchange-fresh state (any state produced by `update_halo`, a model
    step, or a previous chunk).  Call inside SPMD code."""
    from .. import shared
    from ..ops.chunk_engine import (dim_modes, extend_fields, field_ols,
                                    run_chunks, whole_window_chunk_call,
                                    window_chunk_xla)

    grid = shared.global_grid()
    nd = spec.ndim
    modes = dim_modes(grid)[:nd]
    E = analysis.margin_after(K)
    shapes = _field_shapes(spec, tuple(fields[0].shape[d] -
                                       spec.fields[0].stagger[d]
                                       for d in range(nd)))
    ols = field_ols(grid, shapes)
    freeze = {d: analysis.freeze[d] for d in range(nd)}

    def core(*windows):
        return apply_updates(spec, windows, coeffs)

    def one(*S):
        exts = extend_fields(list(S), ols, E, grid, modes)
        return whole_window_chunk_call(
            exts, K=K, E=E, modes=modes, grid=grid, ols=ols,
            shapes=shapes, core=core, freeze_fields=freeze,
            window_fallback=lambda: window_chunk_xla(
                tuple(exts), K=K, E=E, modes=modes, grid=grid, ols=ols,
                shapes=shapes, freeze_fields=freeze, core=core),
            interpret=interpret)

    *S, done = run_chunks(tuple(fields), n_inner=n_inner, K=K,
                          one_chunk=one)
    return (*S, done)
