"""2-D acoustic wave on a staggered grid (velocity-pressure leapfrog).

The 2-D/1-D-halo counterpart of BASELINE.json config 3 ("2-D shallow-water /
acoustic wave, 1-D periodic halo").  Exercises exactly the staggered-array
machinery the reference is built for: pressure `P (nx, ny)` plus face
velocities `Vx (nx+1, ny)` and `Vy (nx, ny+1)` — `Vx` has overlap
`ol_x = 3` so its halo planes sit one cell deeper, handled by the per-array
`ol(dim, A)` rule (`/root/reference/src/shared.jl:81`).  All three fields are
exchanged in ONE grouped `update_halo` (the multi-field pipelining the
reference recommends, `/root/reference/src/update_halo.jl:19-20`).

Round 16: the family dispatches through the degradation ladder like every
other model — `wave2d.chunk` (K-step temporal blocking over the exchanged
dims, periodic meshes; `igg.ops.wave2d_pallas.fused_wave2d_chunk_steps`)
→ `wave2d.mosaic` (the whole coupled update in ONE fused kernel + the
grouped exchange; `fused_wave2d_step`) → `wave2d.xla` (the composition
truth) — with structured Admission refusals, compile-failure capture,
quarantine, and verify-on-first-use (`igg.degrade`).  The fast tiers are
f32-only; the f64 test configurations ride the truth rung unchanged.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import numpy as np

import igg


@dataclasses.dataclass(frozen=True)
class Params:
    rho: float = 1.0      # density
    K: float = 1.0        # bulk modulus
    lx: float = 10.0
    ly: float = 10.0

    def spacing(self) -> Tuple[float, float]:
        return (self.lx / (igg.nx_g() - 1), self.ly / (igg.ny_g() - 1))

    def timestep(self) -> float:
        dx, dy = self.spacing()
        c = (self.K / self.rho) ** 0.5
        return min(dx, dy) / c / 4.1


def init_fields(params: Params = Params(), dtype=np.float32):
    """Gaussian pressure pulse; velocities at rest."""
    import jax.numpy as jnp

    grid = igg.get_global_grid()
    nx, ny = grid.nxyz[0], grid.nxyz[1]
    dx, dy = params.spacing()

    P0 = igg.zeros((nx, ny), dtype=dtype)
    X = igg.x_g_field(dx, P0)[:, None].astype(dtype)
    Y = igg.y_g_field(dy, P0)[None, :].astype(dtype)
    P = jnp.exp(-((X - params.lx / 2) ** 2 + (Y - params.ly / 2) ** 2)) + 0 * P0
    Vx = igg.zeros((nx + 1, ny), dtype=dtype)
    Vy = igg.zeros((nx, ny + 1), dtype=dtype)
    return P, Vx, Vy


def compute_step(P, Vx, Vy, *, dx, dy, dt, rho, K):
    """The pure coupled leapfrog update (no halo exchange): velocities on
    interior faces from the pressure gradient, then the pressure
    FULL-SHAPE from the fresh velocity divergence (Gauss-Seidel flavor —
    effective radius 2 per step through the chain).  The single source of
    arithmetic truth shared by the XLA composition, the fused Mosaic
    step, and the chunk tier's window core
    (`igg.ops.wave2d_pallas`)."""
    from igg.ops import interior_add

    Vx = interior_add(Vx, -dt / rho * (P[1:, :] - P[:-1, :]) / dx,
                      ((1, 1), (0, 0)))
    Vy = interior_add(Vy, -dt / rho * (P[:, 1:] - P[:, :-1]) / dy,
                      ((0, 0), (1, 1)))
    P = P - dt * K * ((Vx[1:, :] - Vx[:-1, :]) / dx
                      + (Vy[:, 1:] - Vy[:, :-1]) / dy)
    return P, Vx, Vy


def local_step(P, Vx, Vy, *, dx, dy, dt, rho, K, overlap: bool = False,
               assembly=None):
    """One leapfrog step over per-device local arrays.  With
    `overlap=True` the step is restructured by
    :func:`igg.hide_communication` (radius 2 — the velocity/pressure
    chain — so the grid needs overlap >= 3 along the exchanged dims)."""
    kw = dict(dx=dx, dy=dy, dt=dt, rho=rho, K=K)
    if overlap:
        return igg.hide_communication(
            (P, Vx, Vy),
            lambda Pb, Vxb, Vyb: compute_step(Pb, Vxb, Vyb, **kw),
            radius=2, assembly=assembly)
    P, Vx, Vy = compute_step(P, Vx, Vy, **kw)
    return igg.update_halo_local(P, Vx, Vy)


_PALLAS_REQ = (
    "the fused wave2d step requires TPU devices (or pallas_interpret="
    "True), a 2-D decomposition (dims[2] == 1) with an overlap-2 grid, "
    "f32 fields, and whole blocks small enough for VMEM "
    "(igg.ops.wave2d_pallas.wave2d_pallas_supported); use the XLA path "
    "otherwise.")

_CHUNK_REQ = (
    "the K-step wave2d chunk tier requires the fused per-step kernel's "
    "prerequisites plus: PERIODIC dims only, n_inner >= K+1 (one warm-up "
    "step + at least one full chunk), 2K-deep send slabs inside every "
    "split dimension's block, and an extended working set within the "
    "VMEM budget (igg.ops.wave2d_pallas.wave2d_chunk_supported); use "
    "chunk='auto' or the per-step tiers otherwise.")

def make_step(params: Params = Params(), *, donate: bool = True,
              overlap="auto", n_inner: int = 1, use_pallas="auto",
              pallas_interpret: bool = False, chunk="auto", K: int = None,
              verify=None, tune=None):
    """Compiled `(P, Vx, Vy) -> (P, Vx, Vy)` advancing `n_inner` steps in
    one SPMD program, dispatched through the family's degradation ladder
    (`wave2d.chunk` → `wave2d.mosaic` → `wave2d.xla`).

    `use_pallas`: "auto" (default) serves the fused Mosaic step when it
    applies (TPU devices or `pallas_interpret=True`, 2-D overlap-2 grid,
    f32 fields); False pins the XLA composition; True requires the kernel
    and raises `GridError` when inapplicable.  `chunk` admits the K-step
    temporal-blocking tier on top ("auto"/False/True, the
    `stokes3d.make_iteration` contract); `K` overrides the auto-fitted
    chunk depth.  `overlap` restructures the XLA composition with
    `igg.hide_communication` ("auto" follows the `IGG_OVERLAP` knob, then
    the autotuner's cached winner — the coupled leapfrog has radius 2, so
    admission needs overlap >= 3; the fused tiers have overlap semantics
    built in).  `verify="first_use"` (or `IGG_VERIFY_KERNELS=1`)
    numerically checks each fast tier against the truth before it serves
    traffic.  `tune` consults the autotuner's cached winner for this
    signature ("auto"/True/False; `igg.autotune` — True searches on a
    cache miss)."""
    from jax import lax

    from igg.overlap import resolve_overlap

    dx, dy = params.spacing()
    dt = params.timestep()
    rho, bulk = params.rho, params.K
    # NOTE: the step closures capture only hashable scalars so recreated
    # closures share one compiled program (`igg.parallel._fn_key`).

    from ._dispatch import apply_tuned

    K, K_from_cache, chunk, use_pallas, tuned = apply_tuned(
        "wave2d", tune, n_inner=n_inner, interpret=pallas_interpret, K=K,
        chunk_knob=chunk, use_pallas=use_pallas,
        tiers=("chunk", "mosaic", "xla"))
    overlap = resolve_overlap(overlap, family="wave2d", tuned=tuned,
                              radius=2, ndim=2, chunk_active=chunk is True)

    def step_kw():
        return dict(dx=dx, dy=dy, dt=dt, rho=rho, K=bulk)

    def xla_steps(P, Vx, Vy):
        return lax.fori_loop(
            0, n_inner,
            lambda _, S: local_step(*S, **step_kw(), overlap=overlap),
            (P, Vx, Vy))

    donate_argnums = (0, 1, 2) if donate else ()
    xla_path = igg.sharded(xla_steps, donate_argnums=donate_argnums)

    if chunk is True and use_pallas is False:
        raise igg.GridError(_CHUNK_REQ)
    if chunk is True:
        use_pallas = True    # the chunk tier rides the fused kernel

    def _fit_K(grid, lshape, dtype):
        from igg.ops.wave2d_pallas import (fit_wave2d_K,
                                           wave2d_chunk_supported)

        from ._dispatch import resolve_chunk_K

        if chunk is False or n_inner < 3:
            return 0
        return resolve_chunk_K(
            K, K_from_cache,
            lambda k: wave2d_chunk_supported(
                grid, tuple(lshape), k, n_inner - 1, dtype,
                interpret=pallas_interpret),
            lambda: fit_wave2d_K(grid, tuple(lshape), n_inner - 1, dtype,
                                 interpret=pallas_interpret))

    def admit_chunk(args):
        from igg.degrade import Admission
        from igg.ops.wave2d_pallas import wave2d_pallas_supported

        from ._dispatch import pallas_applicable

        if use_pallas is False:
            return Admission.no("use_pallas=False pins the XLA path")
        if chunk is False:
            return Admission.no("chunk=False pins the per-step tiers")
        base = pallas_applicable("auto", args[0],
                                 supported_fn=wave2d_pallas_supported,
                                 requirement=_PALLAS_REQ,
                                 interpret=pallas_interpret)
        if not base:
            return Admission.no(f"fused per-step kernel (the chunk "
                                f"tier's carrier) inadmissible: "
                                f"{getattr(base, 'reason', '')}")
        if n_inner < 3:
            return Admission.no(f"n_inner={n_inner} < 3: no warm-up plus "
                                f"full chunk fits")
        grid = igg.get_global_grid()
        P = args[0]
        if not _fit_K(grid, grid.local_shape_any(P), P.dtype):
            return Admission.no(
                "no chunk depth K admissible "
                "(igg.ops.wave2d_pallas.wave2d_chunk_supported)")
        return Admission.yes()

    def build_chunk():
        from igg.ops.wave2d_pallas import (fused_wave2d_chunk_steps,
                                           fused_wave2d_step)

        def chunk_steps(P, Vx, Vy):
            kw = step_kw()
            grid = igg.get_global_grid()
            Kf = _fit_K(grid, P.shape, P.dtype)
            if not Kf:    # admission gate and trace share _fit_K
                raise igg.GridError(_CHUNK_REQ)
            # Warm-up per-step kernel: consumes (and replaces) the entry
            # halos — the exchange-fresh window state the chunk's
            # validity argument requires, for ANY input.
            S = fused_wave2d_step(P, Vx, Vy, **kw,
                                  interpret=pallas_interpret)
            *S, done = fused_wave2d_chunk_steps(
                *S, n_inner=n_inner - 1, K=Kf, dx=dx, dy=dy, dt=dt,
                rho=rho, bulk=bulk, interpret=pallas_interpret)
            n = n_inner - 1 - done
            if n:    # remainder through the per-step kernel
                S = lax.fori_loop(
                    0, n,
                    lambda _, T: tuple(fused_wave2d_step(
                        *T, **step_kw(), interpret=pallas_interpret)),
                    tuple(S))
            return tuple(S)

        return igg.sharded(chunk_steps, donate_argnums=donate_argnums,
                           check_vma=not pallas_interpret)

    def build_pallas_steps():
        from igg.ops.wave2d_pallas import fused_wave2d_steps

        def pallas_steps(P, Vx, Vy):
            return fused_wave2d_steps(
                P, Vx, Vy, n_inner=n_inner, **step_kw(),
                interpret=pallas_interpret)

        return pallas_steps

    from igg.degrade import Tier
    from igg.ops.wave2d_pallas import wave2d_pallas_supported

    from ._dispatch import auto_dispatch

    chunk_tier = Tier(name="wave2d.chunk", rung=0, build=build_chunk,
                      admit=admit_chunk, required=chunk is True,
                      requirement=_CHUNK_REQ)
    return auto_dispatch(
        use_pallas=use_pallas, interpret=pallas_interpret,
        supported_fn=wave2d_pallas_supported, requirement=_PALLAS_REQ,
        xla_path=xla_path, build_pallas_steps=build_pallas_steps,
        donate_argnums=donate_argnums,
        family="wave2d", verify=verify,
        extra_tiers=(chunk_tier,))


def run(nt: int, params: Params = Params(), dtype=np.float32,
        warmup: int = 1, n_inner: int = 1, use_pallas="auto",
        pallas_interpret: bool = False, tune=None):
    """Slope-timed run (see :func:`igg.time_steps`)."""
    P, Vx, Vy = init_fields(params, dtype=dtype)
    step = make_step(params, n_inner=n_inner, use_pallas=use_pallas,
                     pallas_interpret=pallas_interpret, tune=tune)
    n1 = max(1, nt // 4)
    state, sec = igg.time_steps(step, (P, Vx, Vy), n1=n1,
                                n2=max(nt - n1, n1 + 1),
                                warmup=max(warmup, 1))
    return state, sec / n_inner


# Numeric-integrity declaration (igg.integrity, round 19): the leapfrog
# acoustic scheme's discrete energy (Σ P² + Σ V² over owned cells, a
# constant-factor stand-in for P²/2K + ρv²/2) oscillates within a few
# percent on a stable timestep and DECAYS when open boundaries radiate —
# it never grows.  A bounded invariant with a loose tolerance: its job
# is catching large finite corruption, not certifying the scheme.
from igg import integrity as _integrity

_integrity.register_invariants("wave2d", [
    _integrity.Invariant("wave_energy", ("P", "Vx", "Vy"), moment=2,
                         kind="bounded", tol=0.25,
                         requires_periodic=False),
])
