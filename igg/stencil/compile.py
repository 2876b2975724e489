"""`igg.stencil.compile` — the spec factory onto the degradation ladder.

Returns a compiled step function interchangeable with the hand-written
model factories: dispatched through a per-spec
:class:`igg.degrade.Ladder` (`{name}.chunk` → `{name}.mosaic` →
`{name}.xla` truth), every generated fast tier Admission-gated,
compile-failure-captured, verify-on-first-use-guarded, and
quarantinable — a miscompiled GENERATED kernel can never serve wrong
physics, which is what makes arbitrary user physics safe to compile.

Compiling a spec also registers its family with the observability and
tuning stack: `igg.perf` (analytic bytes/step from the analyzer's
read-set, plus a calibration step builder when the spec carries
`init=`), and `igg.autotune` (the (tier, K) candidate set + pinned-
config builders), so drift detection, re-calibration, and the tuning
cache treat spec-defined families exactly like built-ins.
"""

from __future__ import annotations

from typing import Dict, Optional

import igg

from ..shared import GridError
from .analyze import admissible, analyze
from .spec import StencilSpec

__all__ = ["compile"]


def _requirements(name):
    pallas_req = (
        f"the fused {name} spec step requires TPU devices (or "
        f"pallas_interpret=True), an overlap-2 grid whose decomposition "
        f"matches the spec rank, f32 fields, and whole blocks small "
        f"enough for VMEM (igg.stencil.lower.mosaic_supported_fn); use "
        f"the XLA path otherwise.")
    chunk_req = (
        f"the K-step {name} spec chunk tier requires the fused per-step "
        f"kernel's prerequisites plus: n_inner >= K+1, analyzer-admitted "
        f"boundary conditions, E-deep send slabs inside every split "
        f"dimension's block, and an extended working set within the VMEM "
        f"budget (igg.stencil.lower.chunk_supported_fn); use chunk='auto' "
        f"or the per-step tiers otherwise.")
    return pallas_req, chunk_req


def _register_family(spec: StencilSpec, analysis, cf: Dict) -> None:
    """Hook the spec family into igg.perf (roofline bytes model +
    calibration step builder) and igg.autotune (candidate set + pinned
    builders).  Re-registered on every compile with that compile's
    resolved coeffs (grid-derived values like dx are not spec
    defaults), and idempotent dict writes mean `igg.perf.reset`'s
    test-isolation clears never strand a spec family unregistered."""
    from .. import autotune, perf

    def steps(dtype):
        fields = spec.init(cf, dtype)
        step = compile(spec, coeffs=cf, donate=False)
        return (lambda *fs: step(*fs)), tuple(fields)

    perf.register_family(spec.name, accesses=analysis.accesses,
                         steps=steps if spec.init is not None else None)

    if spec.invariants:
        # The igg.integrity hook (round 19): spec-declared conserved/
        # bounded quantities join the silent-data-corruption probes —
        # same registry the built-in families use, keyed by the spec's
        # canonical field names.
        from .. import integrity

        integrity.register_invariants(spec.name, spec.invariants)

    if spec.init is not None:
        import numpy as np

        def candidates(grid, *, n_inner, interpret):
            from ..overlap import overlap_admission
            from .lower import chunk_supported_fn

            nd = spec.ndim
            shape = tuple(grid.nxyz[:nd])
            out = [{"tier": f"{spec.name}.xla", "K": None, "bx": None,
                    "vmem_mb": None},
                   {"tier": f"{spec.name}.mosaic", "K": None, "bx": None,
                    "vmem_mb": None}]
            # The overlapped XLA variant rides the analyzer's read-set
            # radius: any spec whose halo radius fits ol-1 is a search
            # candidate with no per-spec code.
            r = max(analysis.halo_radius) if analysis.halo_radius else 1
            if overlap_admission(r, grid=grid, ndim=nd):
                out.append({"tier": f"{spec.name}.xla", "K": None,
                            "bx": None, "vmem_mb": None, "overlap": True})
            sup = chunk_supported_fn(spec, analysis)
            for K in (4, 8):
                if sup(grid, shape, K, n_inner - 1, np.float32,
                       interpret=interpret):
                    out.append({"tier": f"{spec.name}.chunk", "K": K,
                                "bx": None, "vmem_mb": None})
            return out

        def build(cand, *, n_inner, params, interpret):
            tier = cand["tier"]
            fast = not tier.endswith(".xla")
            fields = spec.init(cf, np.float32)
            step = compile(
                spec, coeffs=cf, donate=False, n_inner=n_inner,
                use_pallas=(True if fast else False),
                overlap=bool(cand.get("overlap")),
                pallas_interpret=interpret,
                chunk=(tier == f"{spec.name}.chunk"), K=cand.get("K"),
                tune=False)
            return (lambda *fs: step(*fs)), tuple(fields)

        autotune.register_family(spec.name, candidates=candidates,
                                 build=build)


def compile(spec: StencilSpec, *, coeffs: Optional[Dict] = None,
            donate: bool = True, n_inner: int = 1, use_pallas="auto",
            overlap="auto", pallas_interpret: bool = False, chunk="auto",
            K: Optional[int] = None, verify=None, tune=None):
    """Compiled `(*fields) -> (*fields)` advancing `n_inner` steps in one
    SPMD program, dispatched through the spec's degradation ladder
    (`{name}.chunk` → `{name}.mosaic` → `{name}.xla`).

    `coeffs` binds the spec's scalar Params (declared defaults fill the
    rest); the remaining knobs carry the model-factory contract verbatim
    — `use_pallas` "auto"/True/False, `chunk`/`K` for the K-step tier,
    `overlap` "auto"/True/False to restructure the generated XLA
    composition with `igg.hide_communication` (the analyzer's read-set
    radius drives the admission for free: a spec whose
    `analysis.halo_radius` fits `ol-1` is overlap-admissible with no
    per-spec code — `igg.overlap.resolve_overlap`),
    `verify="first_use"` (or `IGG_VERIFY_KERNELS=1`) to numerically
    check each generated tier against the generated XLA truth before it
    serves traffic, `tune` to consult the autotuner's cached winner.
    Requires an initialized grid (the analyzer's truth-level gate —
    boundary conditions, read radius vs overlap — runs here and raises
    `GridError` carrying the structured refusal)."""
    from jax import lax

    from ..models._dispatch import (apply_tuned, auto_dispatch,
                                    pallas_applicable, resolve_chunk_K)
    from ..overlap import resolve_overlap
    from . import lower

    igg.get_global_grid()      # factories need the live grid
    adm = admissible(spec)
    if not adm:
        raise GridError(f"igg.stencil.compile({spec.name!r}): {adm.reason}")
    analysis = analyze(spec)
    cf = spec.coeffs(coeffs)
    pallas_req, chunk_req = _requirements(spec.name)

    _register_family(spec, analysis, cf)

    K, K_from_cache, chunk, use_pallas, tuned = apply_tuned(
        spec.name, tune, n_inner=n_inner, interpret=pallas_interpret, K=K,
        chunk_knob=chunk, use_pallas=use_pallas,
        tiers=("chunk", "mosaic", "xla"))
    radius = max(analysis.halo_radius) if analysis.halo_radius else 1
    overlap = resolve_overlap(overlap, family=spec.name, tuned=tuned,
                              radius=radius, ndim=spec.ndim,
                              chunk_active=chunk is True)

    local_step = lower.local_step_fn(spec, cf)

    def xla_steps(*fields):
        if overlap:
            def one(S):
                out = igg.hide_communication(
                    tuple(S),
                    lambda *fs: tuple(lower.apply_updates(spec, fs, cf)),
                    radius=radius)
                return out if isinstance(out, tuple) else (out,)

            return lax.fori_loop(0, n_inner, lambda _, S: one(S),
                                 tuple(fields))
        return lax.fori_loop(0, n_inner, lambda _, S: local_step(*S),
                             tuple(fields))

    nf = len(spec.fields)
    donate_argnums = tuple(range(nf)) if donate else ()
    xla_path = igg.sharded(xla_steps, donate_argnums=donate_argnums)

    if chunk is True and use_pallas is False:
        raise GridError(chunk_req)
    if chunk is True:
        use_pallas = True      # the chunk tier rides the fused kernel

    mosaic_supported = lower.mosaic_supported_fn(spec)
    chunk_supported = lower.chunk_supported_fn(spec, analysis)

    def _base_shape(lshape):
        return tuple(lshape[d] - spec.fields[0].stagger[d]
                     for d in range(spec.ndim))

    def _fit_K(grid, lshape, dtype):
        base = _base_shape(lshape)
        if chunk is False or n_inner < 3:
            return 0
        return resolve_chunk_K(
            K, K_from_cache,
            lambda k: chunk_supported(grid, base, k, n_inner - 1, dtype,
                                      interpret=pallas_interpret),
            lambda: lower.fit_spec_K(spec, analysis, grid, base,
                                     n_inner - 1, dtype,
                                     interpret=pallas_interpret))

    def admit_chunk(args):
        from ..degrade import Admission

        if use_pallas is False:
            return Admission.no("use_pallas=False pins the XLA path")
        if chunk is False:
            return Admission.no("chunk=False pins the per-step tiers")
        base = pallas_applicable("auto", args[0],
                                 supported_fn=mosaic_supported,
                                 requirement=pallas_req,
                                 interpret=pallas_interpret)
        if not base:
            return Admission.no(f"fused per-step kernel (the chunk "
                                f"tier's carrier) inadmissible: "
                                f"{getattr(base, 'reason', '')}")
        if n_inner < 3:
            return Admission.no(f"n_inner={n_inner} < 3: no warm-up plus "
                                f"full chunk fits")
        grid = igg.get_global_grid()
        A = args[0]
        if not _fit_K(grid, grid.local_shape_any(A), A.dtype):
            return Admission.no(
                "no chunk depth K admissible "
                "(igg.stencil.lower.chunk_supported_fn)")
        return Admission.yes()

    def build_chunk():
        def chunk_steps(*fields):
            grid = igg.get_global_grid()
            Kf = _fit_K(grid, fields[0].shape, fields[0].dtype)
            if not Kf:     # admission gate and trace share _fit_K
                raise GridError(chunk_req)
            # Warm-up per-step kernel: consumes (and replaces) the entry
            # halos — the exchange-fresh window state the chunk's
            # validity argument requires, for ANY input.
            S = lower.fused_spec_step(spec, cf, fields,
                                      interpret=pallas_interpret)
            *S, done = lower.spec_chunk_steps(
                spec, analysis, cf, S, n_inner=n_inner - 1, K=Kf,
                interpret=pallas_interpret)
            n = n_inner - 1 - done
            if n:          # remainder through the per-step kernel
                S = lax.fori_loop(
                    0, n,
                    lambda _, T: tuple(lower.fused_spec_step(
                        spec, cf, T, interpret=pallas_interpret)),
                    tuple(S))
            return tuple(S)

        return igg.sharded(chunk_steps, donate_argnums=donate_argnums,
                           check_vma=not pallas_interpret)

    def build_pallas_steps():
        def pallas_steps(*fields):
            return lower.fused_spec_steps(spec, cf, fields,
                                          n_inner=n_inner,
                                          interpret=pallas_interpret)

        return pallas_steps

    from ..degrade import Tier

    chunk_tier = Tier(name=f"{spec.name}.chunk", rung=0, build=build_chunk,
                      admit=admit_chunk, required=chunk is True,
                      requirement=chunk_req)
    return auto_dispatch(
        use_pallas=use_pallas, interpret=pallas_interpret,
        supported_fn=mosaic_supported, requirement=pallas_req,
        xla_path=xla_path, build_pallas_steps=build_pallas_steps,
        donate_argnums=donate_argnums,
        family=spec.name, verify=verify,
        extra_tiers=(chunk_tier,))
