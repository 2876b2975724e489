"""K-step temporal-blocking (trapezoid chunk) tier for HM3D — the
two-field coupled instance of the shared K-step chunk engine
(`igg.ops.chunk_engine`), closing the "HM3D has no temporal-blocking
tier" gap (ROADMAP item 1).

The coupled hydro-mechanical update (`hm3d.step_core`) is radius-1 in
BOTH fields: `dPe` reads `Pe`/`phi` at +-1 (face permeabilities and
Darcy fluxes), and `dphi` reads the freshly-updated `Pe` at the SAME
cell (Gauss-Seidel coupling, no extra radius) — so the validity front
shrinks ONE row per extended side per step and the margin is `E = K`,
the diffusion trapezoid's geometry, not the Stokes `2K` one.

Chunk structure (all engine machinery):

  1. Once per K-step chunk, both fields are extended `E = K` deep along
     every exchanged dimension by ONE grouped `ppermute` pair per dim
     (the two fields share shapes and ride one wire —
     `chunk_engine.extend_fields`), dimension-sequentially so corners
     arrive via the later neighbors' earlier-dim extensions.
  2. K coupled steps run on the extended windows with NO exchange.
     Open dims re-freeze BOTH fields' boundary planes from the
     chunk-entry buffers (the per-step path's no-write semantics: the
     composition writes interior cells only, so open boundary planes
     never change) — `freeze_fields = (0, 1)`.
  3. The central local blocks are sliced out.

Two realizations of the same window dynamics:

  - **Pure-XLA window path** (`_window_steps_xla`, the engine's
    `window_chunk_xla`): interpret mode / CPU meshes / the driver
    dryrun — pinned per-step-equivalent on 8-device periodic, open, and
    mixed meshes by `tests/test_chunk_engine.py`.
  - **Mosaic chunk kernel**: the engine's generic VMEM-resident banded
    kernel (`chunk_engine.resident_chunk_call`) with this family's
    config — both fields resident for the whole chunk, in-place x-row
    bands with one-row lag carry, high margin 1 per field.  HBM traffic
    per chunk: ONE read + ONE write of both fields — `(2R+2W)/K` per
    step against the per-step fused kernel's `2R+2W`.  TPU-gated
    equivalence test in `tests/test_mega_tpu.py`; verify-on-first-use
    guards it in production dispatch (`igg.degrade`).

VMEM is the K-bound (both extended fields resident): ~24 MB at 128^3
f32 K=8, ~44 MB at 160^3 — `hm3d_trapezoid_supported` does the
accounting against the shared budget authority
(`igg.ops._vmem.chunk_budget`) and `fit_hm3d_K` (`_vmem.fit_chunk_K`)
picks the largest admissible K.

The compiled dispatcher (`hm3d.make_step`) runs one per-step fused
kernel FIRST (consuming the entry halos — the exchange-fresh window
contract), then `(n_inner - 1) // K` chunks, then the remainder through
the per-step kernel.
"""

from __future__ import annotations

from functools import partial

from ._vmem import chunk_budget, fit_chunk_K
from .chunk_engine import (admit_chunk_common, admit_send_slabs,
                           admit_sublane_extension, dim_modes, ext_shape,
                           extend_fields, field_ols, pad8 as _pad8,
                           pad128 as _pad128, resident_chunk_call,
                           run_chunks, window_chunk_xla)

_BX = 8          # x band height of the resident chunk kernel


def _vmem_need(shape, K, modes, itemsize: int = 4) -> int:
    """Modeled VMEM bytes of the resident chunk kernel at depth K: the
    two tile-padded K-extended fields, the lag rows, the open-dim freeze
    planes, and a 2x-margin band-temporary term for `step_core`'s
    permeability/flux chain (~12 band-row intermediates; the 2x absorbs
    Mosaic's own scratch — the `stokes_trapezoid._vmem_need` calibration
    style)."""
    E = K
    ext = ext_shape(shape, E, modes)
    a, b, c = ext
    row = _pad8(b) * _pad128(c) * itemsize
    need = 2 * a * row                         # both resident fields
    need += 2 * 2 * row                        # lag rows (2 slots x 2)
    for d in range(3):                         # freeze planes (2 fields)
        if modes[d] in ("oext", "frozen"):
            plane = (a, b, c)[:d] + (a, b, c)[d + 1:]
            need += (2 * 2 * _pad8(plane[0]) * _pad128(plane[1])
                     * itemsize)
    need += 2 * 12 * (_BX + 2) * row           # band temporaries, 2x margin
    return need


def hm3d_trapezoid_supported(grid, shape, K: int, n_inner: int, dtype,
                             interpret: bool = False,
                             allow_open: bool = True):
    """Whether the K-step HM3D chunk tier applies: overlap-2 grid (the
    per-step fused kernel's prerequisite — it runs the warm-up and
    remainder steps), at least one full chunk, K-deep send slabs inside
    every extended dimension's block, the resident kernel's band/tile
    geometry, and the resident working set within the VMEM budget.  Both
    realizations take the same gates (the trapezoid convention), so
    interpret meshes exercise the compiled tier's exact admission
    decisions.  Returns an :class:`igg.degrade.Admission`."""
    import numpy as np

    from ..degrade import Admission

    common = admit_chunk_common(grid, K, n_inner)
    if common is not None:
        return common
    if grid.overlaps != (2, 2, 2):
        return Admission.no(f"grid overlaps {grid.overlaps} != (2, 2, 2)")
    if tuple(shape) != tuple(grid.nxyz):
        return Admission.no(f"local shape {tuple(shape)} != grid block "
                            f"{tuple(grid.nxyz)}")
    if np.dtype(dtype) != np.float32:
        return Admission.no(f"dtype {np.dtype(dtype)} is not float32")
    modes = dim_modes(grid)
    if not allow_open and any(m in ("oext", "frozen") for m in modes):
        return Admission.no(f"open (non-periodic) dimensions {modes} and "
                            f"the caller did not pass allow_open=True")
    E = K
    S0, S1, S2 = shape
    if S0 % _BX != 0 or S0 < 2 * _BX:
        return Admission.no(f"x extent {S0} not band-divisible "
                            f"(needs S0 % {_BX} == 0, S0 >= {2 * _BX})")
    if S1 % 8 != 0 or S2 % 128 != 0:
        return Admission.no(f"local y/z extents ({S1}, {S2}) not Mosaic "
                            f"tile-aligned (y % 8, z % 128)")
    if modes[0] != "frozen" and (2 * E) % _BX != 0:
        # S0e = S0 + 2E must stay band-divisible.
        return Admission.no(f"extended x span S0 + {2 * E} not "
                            f"band-divisible by {_BX}")
    sub = admit_sublane_extension(E, modes)
    if sub is not None:
        # Central y window slice offset on sublane tiles (the shared
        # engine gate — a structured refusal where Mosaic would crash
        # deep in lowering).
        return sub
    shapes = [tuple(shape), tuple(shape)]
    ols = field_ols(grid, shapes)
    slabs = admit_send_slabs(shapes, ols, E, modes, grid=grid)
    if slabs is not None:
        return slabs
    need = _vmem_need(shape, K, modes)
    if need > chunk_budget():
        return Admission.no(f"resident working set {need} bytes exceeds "
                            f"the VMEM budget {chunk_budget()}")
    return Admission.yes()


def fit_hm3d_K(grid, shape, n_inner: int, dtype,
               interpret: bool = False, kmax: int = 8) -> int:
    """Largest admissible chunk depth K <= kmax (halving, >= 2;
    `_vmem.fit_chunk_K`); 0 when none applies."""
    return fit_chunk_K(
        lambda K: hm3d_trapezoid_supported(grid, tuple(shape), K, n_inner,
                                           dtype, interpret=interpret),
        kmax)


# ---------------------------------------------------------------------------
# The family physics: full-window core + per-band value computation
# ---------------------------------------------------------------------------

def _core(kw):
    """The full-window coupled update: `hm3d.compute_step` (interior
    cells of both fields, stale edges) — the single source of arithmetic
    truth shared with the XLA composition and the per-step fused
    kernel."""
    def core(Pe, phi):
        from ..models.hm3d import compute_step

        return compute_step(Pe, phi, **kw)

    return core


def _band_update(Wpe, Wphi, *, bx, kw):
    """New band values (rows [a, a+bx), window row offset 1) from
    margin-1 windows of both fields — the `hm3d_mega`/`hm3d_pallas`
    assembly: interior cells take `step_core` increments, y/z edge rows
    keep their old values (owned by the band-halo wrap/freeze).  Pure
    values: shared by the engine's resident kernel and the banded-scheme
    simulation test."""
    import jax.numpy as jnp

    from ..models.hm3d import step_core

    dPe, dphi = step_core(Wpe, Wphi, **kw)
    outs = []
    for W, dF in ((Wpe, dPe), (Wphi, dphi)):
        o = W[1:1 + bx]
        inner = o[:, 1:-1, 1:-1] + dF[0:bx]
        mid = jnp.concatenate([o[:, 1:-1, 0:1], inner, o[:, 1:-1, -1:]],
                              axis=2)
        outs.append(jnp.concatenate([o[:, 0:1, :], mid, o[:, -1:, :]],
                                    axis=1))
    return tuple(outs)


def _window_steps_xla(Pee, phie, *, K, E, modes, grid, kw, ols, shapes):
    """Pure-XLA realization of the chunk evolution (interpret mode / CPU
    meshes): the engine's generic window loop with both fields frozen on
    open dims."""
    return window_chunk_xla((Pee, phie), K=K, E=E, modes=modes, grid=grid,
                            ols=ols, shapes=shapes, freeze_fields=(0, 1),
                            core=_core(kw))


def _chunk_call(exts, *, K, modes, grid, kw, ols, shapes, interpret=False):
    """Advance K coupled steps on the extended buffers; returns the two
    central local blocks (engine resident kernel / XLA window)."""
    E = K

    def window():
        return _window_steps_xla(*exts, K=K, E=E, modes=modes, grid=grid,
                                 kw=kw, ols=ols, shapes=shapes)

    return resident_chunk_call(
        list(exts), [], K=K, bx=_BX, modes=modes, grid=grid, ols=ols,
        shapes=shapes, E=E, band_update=partial(_band_update, kw=kw),
        extras=(1, 1), freeze_fields=(0, 1), window_fallback=window,
        interpret=interpret)


# ---------------------------------------------------------------------------
# Chunk driver
# ---------------------------------------------------------------------------

def fused_hm3d_trapezoid_steps(Pe, phi, *, n_inner: int, K: int,
                               dx, dy, dz, dt, phi0, npow, eta,
                               interpret: bool = False):
    """Advance `n_inner // K` full K-step chunks (the caller handles the
    warm-up step before and the per-K remainder after, through the
    per-step fused kernel); returns `(Pe, phi, steps_done)`.

    Entry contract: exchange-fresh halos (any state produced by
    `update_halo`, a model step, or a previous chunk).  Call inside SPMD
    code (`igg.sharded` / shard_map); fully-frozen 1-device grids also
    run under plain `jax.jit`."""
    from .. import shared

    grid = shared.global_grid()
    modes = dim_modes(grid)
    E = K
    shapes = [Pe.shape, phi.shape]
    ols = field_ols(grid, shapes)
    kw = dict(dx=dx, dy=dy, dz=dz, dt=dt, phi0=phi0, npow=npow, eta=eta)

    def one(Pe, phi):
        exts = extend_fields([Pe, phi], ols, E, grid, modes)
        return _chunk_call(exts, K=K, modes=modes, grid=grid, kw=kw,
                           ols=ols, shapes=shapes, interpret=interpret)

    *S, done = run_chunks((Pe, phi), n_inner=n_inner, K=K, one_chunk=one)
    return (*S, done)
