"""3-D heat diffusion — the flagship model.

TPU-native re-implementation of the reference's de-facto integration benchmark
(`/root/reference/docs/examples/diffusion3D_multigpu_CuArrays_novis.jl`):
Fourier-law fluxes on a staggered grid, conservative temperature update, halo
exchange each step.  The whole step (fluxes + update + halo ppermutes)
compiles to ONE XLA program per device via `igg.sharded`, with the temperature
buffer donated so the update is in-place in HBM; XLA's latency-hiding
scheduler overlaps the halo collectives with interior compute — the built-in
analog of ParallelStencil's `@hide_communication`
(`/root/reference/README.md:9`).
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import numpy as np

import igg


@dataclasses.dataclass(frozen=True)
class Params:
    lam: float = 1.0        # thermal conductivity
    cp_min: float = 1.0     # minimal heat capacity
    lx: float = 10.0        # domain length in x
    ly: float = 10.0
    lz: float = 10.0

    def spacing(self) -> Tuple[float, float, float]:
        return igg.tools.spacing(self.lx, self.ly, self.lz)

    def timestep(self) -> float:
        dx, dy, dz = self.spacing()
        # CFL-type bound of the reference example
        # (`/root/reference/docs/examples/diffusion3D_multigpu_CuArrays_novis.jl:40`).
        return min(dx * dx, dy * dy, dz * dz) * self.cp_min / self.lam / 8.1


def init_fields(params: Params = Params(), dtype=np.float32):
    """Heat capacity and temperature with Gaussian anomalies, built from
    global coordinates so every device holds globally-consistent data
    (`/root/reference/docs/examples/diffusion3D_multigpu_CuArrays_novis.jl:33-37`)."""
    import jax.numpy as jnp

    grid = igg.get_global_grid()
    nx, ny, nz = grid.nxyz
    dx, dy, dz = params.spacing()
    lx, ly, lz = params.lx, params.ly, params.lz

    T0 = igg.zeros((nx, ny, nz), dtype=dtype)
    X, Y, Z = igg.coord_fields(dx, dy, dz, T0)
    X, Y, Z = (a.astype(dtype) for a in (X, Y, Z))
    Cp = (params.cp_min
          + 5 * jnp.exp(-(X - lx / 1.5) ** 2 - (Y - ly / 2) ** 2 - (Z - lz / 1.5) ** 2)
          + 5 * jnp.exp(-(X - lx / 3.0) ** 2 - (Y - ly / 2) ** 2 - (Z - lz / 1.5) ** 2)
          + 0 * T0)
    T = (100 * jnp.exp(-((X - lx / 2) / 2) ** 2 - ((Y - ly / 2) / 2) ** 2
                       - ((Z - lz / 3.0) / 2) ** 2)
         + 50 * jnp.exp(-((X - lx / 2) / 2) ** 2 - ((Y - ly / 2) / 2) ** 2
                        - ((Z - lz / 1.5) / 2) ** 2)
         + 0 * T0)
    return T, Cp


def compute_step(T, Cp, *, dx, dy, dz, dt, lam):
    """The pure stencil update (no halo exchange): conservative interior
    temperature update; boundary planes keep their stale values (the
    reference's no-write semantics).

    Physics of the reference example — Fourier-law fluxes on staggered inner
    faces plus ∂T/∂t = 1/cp ∇·(λ∇T)
    (`/root/reference/docs/examples/diffusion3D_multigpu_CuArrays_novis.jl:41-48`)
    — algebraically re-associated for TPU: with constant λ the staggered flux
    divergence telescopes to the 7-point Laplacian, so the whole update is ONE
    fused XLA pass (read T, read Cp, write T).  The flux form as written in
    the reference materializes three face-flux temporaries (measured 2.2 GB of
    HBM traffic per step at 256³ instead of ~0.8 GB — the same reason the
    reference's own CuArray-broadcast version is ">10x" slower than its
    hand-fused kernels, `/root/reference/README.md:161`).

    Shift-invariant and radius-1, so it is usable both full-domain and on the
    boundary slabs of :func:`igg.hide_communication`.  The arithmetic lives in
    :func:`igg.ops.diffusion_compute`, shared with the fused Pallas step."""
    from igg.ops import diffusion_compute

    return diffusion_compute(
        T, float(dt * lam) / Cp, rdx2=1.0 / (dx * dx), rdy2=1.0 / (dy * dy),
        rdz2=1.0 / (dz * dz))


def local_step(T, Cp, *, dx, dy, dz, dt, lam, overlap: bool = False,
               assembly="xla"):
    """One diffusion step over per-device local arrays (the user-model of the
    reference: physics written for a single device's block).  With
    `overlap=True` the step is restructured by :func:`igg.hide_communication`
    so the halo collectives are data-independent of the full-domain stencil
    and XLA can overlap them (ParallelStencil's `@hide_communication`,
    `/root/reference/README.md:9`).

    `assembly` defaults to "xla" for standalone use (for this radius-1
    single-field step, XLA fuses the halo select chain into the stencil's
    output pass — measured 0.70 ms vs 1.12 ms with the Pallas writer at
    256^3); the compiled paths (:func:`make_multi_step`) override it with
    a per-signature measured choice instead of trusting this hint."""
    kw = dict(dx=dx, dy=dy, dz=dz, dt=dt, lam=lam)
    if overlap:
        return igg.hide_communication(
            T, lambda Tb, Cpb: compute_step(Tb, Cpb, **kw), Cp,
            assembly=assembly)
    return igg.update_halo_local(compute_step(T, Cp, **kw),
                                 assembly=assembly)


def make_member_step(params: Params = Params()):
    """Per-member LOCAL step over the `{"T", "Cp"}` state dict — the
    :func:`igg.run_ensemble` contract (the step is vmapped over the member
    axis inside one `shard_map` program, so it must be the local-arrays
    form, not an `igg.sharded`-wrapped whole-mesh program).  A member
    state may also carry a per-member scalar `"dt_scale"` field (a swept
    parameter): the timestep becomes `dt * dt_scale` for that member.

    The XLA assembly path is pinned: inside the vmapped ensemble program
    the halo select chain fuses into the stencil output pass exactly like
    the composed single-member step (the measured round-6 choice)."""
    dx, dy, dz = params.spacing()
    dt, lam = params.timestep(), params.lam
    rdx2, rdy2, rdz2 = 1.0 / (dx * dx), 1.0 / (dy * dy), 1.0 / (dz * dz)

    def member_step(st):
        from igg.ops import diffusion_compute

        # The coefficient is formed per member (dt_scale may be a traced
        # per-member scalar, so the composed-step float() shortcut of
        # `compute_step` does not apply here).
        coeff = dt * lam
        if "dt_scale" in st:
            coeff = coeff * st["dt_scale"]
        T = diffusion_compute(st["T"], coeff / st["Cp"], rdx2=rdx2,
                              rdy2=rdy2, rdz2=rdz2)
        out = dict(st)
        out["T"] = igg.update_halo_local(T, assembly="xla")
        return out

    return member_step


_PALLAS_REQ = (
    "the fused Pallas step requires TPU devices (or interpret=True), "
    "an overlap-2 grid, and an f32 unstaggered field with local "
    "shape divisible into x-slabs (x % 4 == 0, y >= 8, z >= 128).")


def _pallas_applicable(use_pallas, T, interpret: bool = False) -> bool:
    from igg.ops import pallas_supported

    from ._dispatch import pallas_applicable

    return pallas_applicable(use_pallas, T, supported_fn=pallas_supported,
                             requirement=_PALLAS_REQ, interpret=interpret)


def _best_bx(S0: int) -> int:
    # 8 measured fastest at 256^3 on v5e for the mega-kernel path (the
    # per-step kernel is flat across 8..32); see
    # benchmarks/results/pallas_sweep.jsonl.
    for b in (8, 16, 4, 2):
        if S0 % b == 0:
            return b
    return 1


def make_step(params: Params = Params(), *, donate: bool = True,
              use_pallas="auto", overlap="auto",
              pallas_interpret: bool = False, verify=None, tune=None):
    """Compiled whole-step function `(T, Cp) -> T` over the grid mesh.

    `use_pallas`: "auto" (default) uses the fused Pallas kernel
    (`igg.ops.fused_diffusion_step`) when it applies (TPU devices, overlap-2
    grid, f32 unstaggered field — any device count / periodicity); False
    forces the portable shard_map/XLA path; True requires the kernel and
    raises if inapplicable.
    `overlap`: restructure the XLA step with `igg.hide_communication` (the
    Pallas step has overlap semantics built in — its halo exchange is always
    data-independent of the main kernel).  "auto" (default) follows the
    `IGG_OVERLAP` knob, then the autotuner's cached winner, else off
    (`igg.overlap.resolve_overlap`).
    `pallas_interpret`: run the kernel in interpret mode (testing on CPU).
    `verify`: "first_use" numerically checks the fused tier against the
    XLA composition before it serves traffic (`igg.degrade`; defaults to
    the `IGG_VERIFY_KERNELS` environment knob).
    """
    return make_multi_step(1, params, donate=donate, use_pallas=use_pallas,
                           overlap=overlap, pallas_interpret=pallas_interpret,
                           verify=verify, tune=tune)


def make_multi_step(n_inner: int, params: Params = Params(), *,
                    donate: bool = True, use_pallas="auto",
                    overlap="auto", pallas_interpret: bool = False,
                    bx: int = None, verify=None, tune=None):
    """Compiled `(T, Cp) -> T` advancing `n_inner` steps in ONE XLA program
    (`lax.fori_loop` around the step, halo ppermutes included).  This is the
    TPU-idiomatic time loop: host dispatch overhead amortizes to zero, and
    XLA schedules collectives of step k+1 against compute of step k.  The
    reference instead re-dispatches kernels + MPI calls from the host every
    step (`/root/reference/docs/examples/diffusion3D_multigpu_CuArrays_novis.jl:41-48`).

    Both paths (fused Pallas kernel / portable XLA) compile through
    :func:`igg.sharded` into one SPMD program over the grid mesh.

    `tune` consults the autotuner's cached winner for this signature
    ("auto"/True/False, default the `IGG_TUNE` knob; `igg.autotune`):
    a hit supplies the slab/chunk depth `bx` and may pin the tier when
    the caller left the defaults — K is then searched, not fixed, and
    the winner's persisted overlap axis resolves `overlap="auto"`."""
    from jax import lax

    from igg.overlap import resolve_overlap

    from ._dispatch import apply_tuned

    _, _, _, use_pallas, tuned = apply_tuned(
        "diffusion3d", tune, n_inner=n_inner, interpret=pallas_interpret,
        K=None, chunk_knob="auto", use_pallas=use_pallas,
        tiers=("mosaic", "xla"))
    if bx is None and tuned and tuned.get("bx"):
        bx = int(tuned["bx"])
    overlap = resolve_overlap(overlap, family="diffusion3d", tuned=tuned,
                              radius=1)

    dx, dy, dz = params.spacing()
    dt = params.timestep()
    lam = params.lam
    # NOTE: the step closures capture only hashable scalars so recreated
    # closures share one compiled program (`igg.parallel._fn_key`).

    rdx2, rdy2, rdz2 = 1.0 / (dx * dx), 1.0 / (dy * dy), 1.0 / (dz * dz)
    dt_lam = float(dt * lam)

    def build_xla(assembly):
        def xla_steps(T, Cp):
            from igg.ops import diffusion_compute

            # Loop-invariant coefficient: hoists the per-element divide out
            # of the time loop (same trick as the Pallas path).
            A = dt_lam / Cp
            comp = lambda Tb, Ab: diffusion_compute(Tb, Ab, rdx2=rdx2,
                                                    rdy2=rdy2, rdz2=rdz2)

            def one(T):
                if overlap:
                    return igg.hide_communication(T, comp, A,
                                                  assembly=assembly)
                return igg.update_halo_local(comp(T, A), assembly=assembly)

            return lax.fori_loop(0, n_inner, lambda _, T: one(T), T)

        return igg.sharded(xla_steps, donate_argnums=(0,) if donate else ())

    from ._dispatch import measured_assembly_path

    # assembly strategy: measured once per signature ("xla" historically
    # wins this composed radius-1 single-field step; the writers win
    # standalone/multi-field — no more hard-coded hint).
    xla_path = measured_assembly_path(
        build_xla, tag=f"diffusion3d:{n_inner}:{overlap}:{donate}",
        wrap=lambda fn: lambda T, Cp: (fn(T, Cp), Cp))

    def build_pallas_steps():
        from igg.ops import fused_diffusion_steps
        bx_ = bx or _best_bx(igg.get_global_grid().nxyz[0])

        def pallas_steps(T, Cp):
            return fused_diffusion_steps(
                T, Cp, n_inner=n_inner, dx=dx, dy=dy, dz=dz, dt=dt,
                lam=lam, bx=bx_, interpret=pallas_interpret)

        return pallas_steps

    from igg.ops import pallas_supported

    from ._dispatch import auto_dispatch

    return auto_dispatch(
        use_pallas=use_pallas, interpret=pallas_interpret,
        supported_fn=pallas_supported, requirement=_PALLAS_REQ,
        xla_path=xla_path, build_pallas_steps=build_pallas_steps,
        donate_argnums=(0,) if donate else (),
        family="diffusion3d", verify=verify)


# Numeric-integrity declaration (igg.integrity, round 19): under fully
# periodic boundaries the conservative flux-divergence update preserves
# the total temperature sum exactly (up to accumulation roundoff) — the
# invariant the silent-data-corruption probes watch for state dicts
# carrying this family's canonical "T" field.
from igg import integrity as _integrity

_integrity.register_invariants("diffusion3d", [
    _integrity.Invariant("total_heat", ("T",), moment=1, kind="conserved",
                         requires_periodic=True),
])


def run(nt: int, params: Params = Params(), dtype=np.float32,
        warmup: int = 1, n_inner: int = 1, use_pallas="auto",
        overlap="auto", pallas_interpret: bool = False,
        bx: int = None):
    """Slope-timed run (see :func:`igg.time_steps`): the `nt` timed
    dispatches are split into slope batches of ~nt/4 and ~3nt/4, each
    dispatch advancing `n_inner` steps inside one compiled program, after
    `warmup` untimed dispatches.  Returns (T, seconds_per_step)."""
    T, Cp = init_fields(params, dtype=dtype)
    step = make_multi_step(n_inner, params, use_pallas=use_pallas,
                           overlap=overlap, pallas_interpret=pallas_interpret,
                           bx=bx)
    n1 = max(1, nt // 4)
    (T, Cp), sec = igg.time_steps(lambda T, Cp: (step(T, Cp), Cp), (T, Cp),
                                  n1=n1, n2=max(nt - n1, n1 + 1),
                                  warmup=max(warmup, 1))
    return T, sec / n_inner
