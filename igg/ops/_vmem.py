"""Shared per-call scoped-VMEM budgeting for the fused model kernels.

The stokes/hm3d fused kernels keep a deliberately TIGHT vmem budget when
their working set allows (small budgets steer Mosaic to the best
DMA/compute interleave — see the sweep in `stokes_pallas.py`), but large
y*z window areas NEED more than the floor: round 5 found both kernels
OOM-ing at Mosaic compile on 256^3/512^3-class blocks under their fixed
32 MB budgets, with `use_pallas="auto"` users crashing instead of falling
back.  Each kernel supplies its own first-order window-footprint model
(`need_fn(bx, S1, S2)`); this module owns the shared floor/cap and the
slab-height fitting so the two cannot drift.

Round 16: this module is also the single budget authority for the K-step
CHUNK tiers — `CHUNK_VMEM_BUDGET` (the resident-working-set ceiling the
trapezoid gates used to copy from `diffusion_mega`) and
:func:`fit_chunk_K` (the fit-K-to-budget halving search both trapezoid
modules used to hand-roll).  The autotuner (`igg.autotune`) sweeps the
cap through :func:`set_cap_override`, so a tuned budget reaches every
kernel that consults :func:`vmem_limit` without per-kernel plumbing."""

from __future__ import annotations

from typing import Callable, Optional

VMEM_FLOOR = 32 * 1024 * 1024
VMEM_CAP = 110 * 1024 * 1024

# Resident-working-set ceiling for the K-step chunk kernels (the v5e/v5p
# have 128 MB of VMEM; leave slack for Mosaic's own allocations).  One
# constant, one place — the trapezoid modules and the chunk engine all
# read it from here.
CHUNK_VMEM_BUDGET = 110 * 1024 * 1024

# The autotuner's cap override (igg.autotune applies a tuned vmem budget
# here; None = the hand-derived default).  Read at trace/build time only —
# never from a hot loop.
_CAP_OVERRIDE: Optional[int] = None


def set_cap_override(cap_bytes: Optional[int]) -> None:
    """Install (or clear, with None) the autotuned per-call VMEM cap.
    Affects :func:`vmem_limit` and :func:`chunk_budget`; callers re-trace
    on the next factory build, so flipping it never invalidates a live
    compiled program mid-run."""
    global _CAP_OVERRIDE
    _CAP_OVERRIDE = int(cap_bytes) if cap_bytes else None


def vmem_cap() -> int:
    return _CAP_OVERRIDE if _CAP_OVERRIDE is not None else VMEM_CAP


def chunk_budget() -> int:
    """The chunk tiers' resident-working-set budget (override-aware)."""
    return (_CAP_OVERRIDE if _CAP_OVERRIDE is not None
            else CHUNK_VMEM_BUDGET)


def vmem_limit(need: int) -> int:
    """The per-call scoped-vmem budget for a modeled footprint."""
    return max(VMEM_FLOOR, min(vmem_cap(), need))


def fit_chunk_K(admissible: Callable[[int], object], kmax: int, *,
                min_k: int = 2) -> int:
    """Largest admissible chunk depth K <= kmax by halving (>= `min_k`);
    0 when none applies.  `admissible(K)` is the family's full admission
    gate (an :class:`igg.degrade.Admission` or bool) — the search walks
    kmax, kmax/2, ... so an even kmax keeps even K (the property the
    extended-span band-divisibility gates rely on).  This is the shared
    fit-K-to-budget computation both trapezoid modules used to carry
    privately (`stokes_trapezoid.fit_stokes_K`, the diffusion dispatch's
    fixed bx fallbacks)."""
    K = int(kmax)
    while K >= min_k:
        if admissible(K):
            return K
        K //= 2
    return 0


def whole_block_vmem(shapes, itemsize: int = 4) -> int:
    """Modeled VMEM footprint of a whole-block/whole-window kernel
    holding `shapes` in and out (trailing dims tile-padded to the
    Mosaic (8, 128) tile, 2x margin for Mosaic scratch) — the one
    footprint model the wave2d per-step/chunk gates and the
    `igg.stencil` generated tiers share, kept next to the budget it is
    compared against."""
    from .chunk_engine import pad8, pad128

    total = 0
    for s in shapes:
        padded = list(s)
        padded[-1] = pad128(s[-1])
        if len(s) >= 2:
            padded[-2] = pad8(s[-2])
        n = 1
        for v in padded:
            n *= int(v)
        total += n
    return int(2 * 2 * total * itemsize)


def fit_bx(need_fn, bx: int, S0: int, S1: int, S2: int, *,
           min_bx: int, check_vmem: bool = True) -> int:
    """Largest slab height <= bx (halving, >= `min_bx`) that divides S0
    and — in compiled mode — whose modeled footprint fits the cap; 0 when
    none does.  `check_vmem=False` is the interpret-mode form: no Mosaic,
    no budget."""
    while bx >= min_bx:
        if S0 % bx == 0 and (not check_vmem
                             or need_fn(bx, S1, S2) <= vmem_cap()):
            return bx
        bx //= 2
    return 0
