"""Shared `use_pallas` dispatch scaffolding for the model families.

Each model family exposes the same three-valued contract on its compiled
step factory: `use_pallas="auto"` (default) routes to the fused Pallas
kernel when it applies and silently falls back to the portable
shard_map/XLA path otherwise; `False` forces the XLA path; `True` requires
the kernel and raises `GridError` with the family's requirement string.
This module is the single implementation of that contract, parameterized
by the family's `supported(grid, field)` gate, requirement message, and
fused-step builder.

Round 10: the contract is realized as an :class:`igg.degrade.Ladder` —
every dispatch walks the family's tier ladder (optional extra rungs like
the Stokes trapezoid chunk tier → the fused Mosaic rung → the pure-XLA
composition truth rung), so compile-failure capture, kernel quarantine,
and numeric verify-on-first-use apply uniformly to every family (see
`igg/degrade.py`)."""

from __future__ import annotations

import igg


def pallas_applicable(use_pallas, field, *, supported_fn, requirement,
                      interpret: bool = False):
    """The auto/True/False applicability probe: TPU devices (or interpret
    mode), f32 fields, and the family's `supported_fn` gate.  Returns an
    :class:`igg.degrade.Admission` (truthy/falsy, with the structured
    refusal reason); raises `GridError(requirement)` when `use_pallas is
    True` but the kernel is inapplicable."""
    import inspect

    import jax.numpy as jnp

    from igg.degrade import Admission

    def probe():
        if use_pallas is False:
            return Admission.no("use_pallas=False pins the XLA path")
        grid = igg.get_global_grid()
        if not (interpret
                or next(iter(grid.mesh.devices.flat)).platform == "tpu"):
            return Admission.no("devices are not TPU (and interpret mode "
                                "is off)")
        if field.dtype != jnp.float32:
            return Admission.no(f"dtype {field.dtype} is not float32")
        # Gates that distinguish interpret mode (no Mosaic, no VMEM budget
        # — stokes/hm3d) receive the flag; older two-arg gates are
        # unchanged.
        kw = ({"interpret": interpret}
              if "interpret" in inspect.signature(supported_fn).parameters
              else {})
        ok = supported_fn(grid, field, **kw)
        if isinstance(ok, Admission):
            return ok
        return Admission.yes() if ok else Admission.no(
            "the family's admission gate refused the field/grid")

    adm = probe()
    if use_pallas is True and not adm:
        raise igg.GridError(requirement)
    return adm


# Measured assembly choices, keyed by (model tag, grid epoch, arg
# signature): the right `assembly` mode for a composed step is
# signature-dependent (`"xla"` fuses the halo select chain into a radius-1
# single-field stencil's output pass; the Pallas writers win standalone and
# multi-field updates — `igg.halo.update_halo_local` docstring), so instead
# of hard-coding per-model hints the compiled paths measure both variants
# once per signature and cache the winner (VERDICT r3 item 7).
_ASSEMBLY_CHOICE: dict = {}


def _elect(measure, names=("xla", "writer"), *, close=0.15, max_rounds=3):
    """Noise-hardened winner election: one slope measurement per variant per
    round, compared by per-variant *median* (a single outlier round — high
    OR low — cannot pin the choice, unlike min-of-k).  Re-measures while
    the medians sit within `close` relative distance of each other, up to
    `max_rounds` rounds; variants separated by more than the noise margin
    are elected after one round, so the well-separated common case still
    pays exactly one measurement per variant."""
    import statistics

    samples = {n: [measure(n)] for n in names}
    for _ in range(max_rounds - 1):
        med = {n: statistics.median(s) for n, s in samples.items()}
        lo = min(med.values())
        if max(med.values()) - lo > close * lo:
            break
        for n in names:
            samples[n].append(measure(n))
    med = {n: statistics.median(s) for n, s in samples.items()}
    return min(med, key=med.get)


def _measurement_would_oom(args) -> bool:
    """The one-time measurement keeps a full scratch copy of every field
    live alongside the originals (plus both variants' executables); when
    the device reports less free memory than ~2x the argument bytes, skip
    it — jobs sized to the donation steady state would OOM at first
    dispatch before finding the `IGG_ASSEMBLY` escape hatch."""
    import jax

    try:
        stats = jax.local_devices()[0].memory_stats()
        free = stats["bytes_limit"] - stats["bytes_in_use"]
    except Exception:
        return False  # no stats on this runtime: keep the measured path

    def per_device_bytes(a):
        # Compare like with like: free memory is per device, so count the
        # bytes one device holds (a sharded field's `nbytes` is the global
        # size — N-chip meshes would over-count by N and silently disable
        # the election).
        shards = getattr(a, "addressable_shards", None)
        if shards:
            ndev = max(1, len({sh.device for sh in shards}))
            return sum(sh.data.nbytes for sh in shards) // ndev
        return getattr(a, "nbytes", 0)

    return free < 2 * sum(per_device_bytes(a) for a in args)


def measured_assembly_path(build_variant, *, tag: str, wrap):
    """Returns `dispatch(*args)` choosing between the compiled
    `assembly="xla"` and writer (`assembly=None`) variants of the same step
    by a one-time slope-timed measurement per argument signature.

    `build_variant(assembly)` -> compiled step callable (built lazily and
    at most once per variant).  `wrap(fn)` adapts the step to a
    state-preserving `state -> state` function for `igg.time_steps` (the
    measurement runs on scratch copies, so donation in the real path is
    unaffected — note the one-time cost: a full scratch copy of the
    fields is live alongside the originals during measurement, plus both
    variants' executables; jobs sized to the donation steady state can
    pin the choice with `IGG_ASSEMBLY=xla|writer` to skip it).

    The measurement is skipped — with a fixed "writer" default, the
    engine's standalone-optimal strategy — when it cannot run safely or
    meaningfully: non-TPU meshes (the writers never engage; "xla"),
    a quarantined writer tier (igg.degrade), multi-controller runs
    (per-process wall clocks could elect different variants and the
    processes would then execute divergent SPMD programs), or an
    `IGG_ASSEMBLY` override."""
    import igg
    from igg import shared
    from igg.halo import _is_tpu

    built = {}

    # Choices are NAMED ("xla" / "writer") rather than the engine's
    # None-means-writers convention: a None cache entry would be
    # indistinguishable from "not measured yet" and re-measure on every
    # dispatch.
    def variant(choice: str):
        if choice not in built:
            built[choice] = build_variant(
                None if choice == "writer" else choice)
        return built[choice]

    def dispatch(*args):
        import jax

        from igg import _env, degrade, halo

        grid = shared.global_grid()
        if not (_is_tpu(grid) or halo._FORCE_WRITER_INTERPRET):
            return variant("xla")(*args)
        if degrade.is_quarantined(degrade.HALO_WRITER_TIER):
            # The writer tier is quarantined (see igg/halo.py): skip the
            # election — a measured "writer" choice could no longer engage
            # the writers and would just mislead the cache.
            return variant("xla")(*args)
        forced = _env.text("IGG_ASSEMBLY")
        if forced in ("xla", "writer"):
            return variant(forced)(*args)
        if jax.process_count() > 1:
            # No cross-process agreement protocol for a measured choice;
            # a per-process pick could diverge and the SPMD programs with
            # it.  Fixed default instead.
            return variant("writer")(*args)
        key = (tag, shared.grid_epoch(),
               tuple((a.shape, str(a.dtype)) for a in args))
        choice = _ASSEMBLY_CHOICE.get(key)
        if choice is None:
            if _measurement_would_oom(args):
                _ASSEMBLY_CHOICE[key] = choice = "writer"
                return variant(choice)(*args)

            def measure(name):
                fn = variant(name)
                scratch = tuple(a + 0 for a in args)  # donation-safe copies
                _, sec = igg.time_steps(wrap(fn), scratch, n1=2, n2=6,
                                        warmup=1)
                return sec

            _ASSEMBLY_CHOICE[key] = choice = _elect(measure)
        return variant(choice)(*args)

    return dispatch


def auto_dispatch(*, use_pallas, interpret, supported_fn, requirement,
                  xla_path, build_pallas_steps, donate_argnums,
                  family: str = "model", verify=None, extra_tiers=()):
    """The compiled-entry dispatcher shared by the model factories: a
    per-family :class:`igg.degrade.Ladder` whose rungs are `extra_tiers`
    (family-specific fast tiers, e.g. the Stokes trapezoid chunk tier) →
    the fused Mosaic tier (`{family}.mosaic`, admission-probed per call on
    the first field argument, lazily compiled through `igg.sharded` on
    first use) → the pure-XLA composition truth tier (`{family}.xla`).
    Every dispatch gets the ladder's runtime guards: quarantine skip,
    compile-failure capture, and — with `verify="first_use"` (or
    `IGG_VERIFY_KERNELS=1`) — a one-time numeric check of each fast tier
    against the truth rung before it serves real traffic.

    `build_pallas_steps()` returns the local (per-device) fused step
    function; `check_vma=not interpret` works around interpret-mode
    pallas_call not propagating shard_map's varying-manual-axes metadata.
    `extra_tiers` is a sequence of `igg.degrade.Tier` placed above the
    Mosaic rung (their `rung` indices are assigned by position).  The
    returned callable exposes the ladder as `.ladder` for observability
    and benchmarks."""
    from igg.degrade import Ladder, Tier

    def admit_mosaic(args):
        return pallas_applicable(use_pallas, args[0],
                                 supported_fn=supported_fn,
                                 requirement=requirement, interpret=interpret)

    def build_mosaic():
        return igg.sharded(build_pallas_steps(),
                           donate_argnums=donate_argnums,
                           check_vma=not interpret)

    tiers = list(extra_tiers)
    tiers.append(Tier(name=f"{family}.mosaic", rung=0, build=build_mosaic,
                      admit=admit_mosaic, required=use_pallas is True,
                      requirement=requirement))
    tiers.append(Tier(name=f"{family}.xla", rung=0,
                      build=lambda: xla_path, truth=True))
    for i, t in enumerate(tiers):
        t.rung = i
    ladder = Ladder(family, tiers, verify=verify)

    def dispatch(*args):
        return ladder.dispatch(*args)

    dispatch.ladder = ladder
    return dispatch


def apply_tuned(family, tune, *, n_inner, interpret, K, chunk_knob,
                use_pallas, tiers):
    """The chunk-tier families' shared tuned-config application (one
    implementation of the precedence rules — hm3d/wave2d/stokes3d used
    to carry private copies):

    - a winner whose tier is not one of the family's ladder `tiers`
      (suffixes; a cache written before a tier was removed may still
      name it) is no winner: the factory serves its default ladder;
    - a cached winner's `K` fills an unset caller `K` — and is marked
      cache-sourced, so the family's `_fit_K` can FALL BACK to auto-fit
      when that K is inapplicable at this factory's `n_inner` (the cache
      key has no n_inner axis; only a CALLER-pinned K hard-refuses);
    - a `<family>.mosaic` winner turns `chunk_knob` "auto" off;
    - a `<family>.xla` winner pins `use_pallas` off ONLY when the caller
      left ALL the knobs on auto — an explicit chunk/trapezoid=True
      always outranks a cached winner.

    Returns `(K, K_from_cache, chunk_knob, use_pallas, tuned)` — `tuned`
    is the raw winner entry (or None), so the factory can resolve its
    remaining auto knobs (the overlap axis,
    `igg.overlap.resolve_overlap`) from the same lookup."""
    from igg import autotune

    tuned = autotune.applied(family, tune, n_inner=n_inner,
                             interpret=interpret)
    if tuned and tuned.get("tier") not in {f"{family}.{t}" for t in tiers}:
        tuned = None
    K_from_cache = False
    if K is None and tuned and tuned.get("K"):
        K, K_from_cache = int(tuned["K"]), True
    winner = tuned.get("tier") if tuned else None
    if chunk_knob == "auto" and winner == f"{family}.mosaic":
        chunk_knob = False
    if use_pallas == "auto" and chunk_knob == "auto" and \
            winner == f"{family}.xla":
        use_pallas = False
    return K, K_from_cache, chunk_knob, use_pallas, tuned


def resolve_chunk_K(K, K_from_cache, supported, fit):
    """The family `_fit_K` body shared by the chunk tiers: an explicit
    K serves iff admissible (a caller pin hard-refuses on mismatch, a
    cache-sourced K falls back to the auto-fit — see
    :func:`apply_tuned`); otherwise the largest admissible K is
    fitted."""
    if K is not None:
        if supported(K):
            return K
        if not K_from_cache:
            return 0
    return fit()

