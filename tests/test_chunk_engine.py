"""The shared K-step chunk engine and the two NEW speed rungs it
generates: the HM3D trapezoid tier and the wave2d Mosaic/chunk tiers.

The existing diffusion/Stokes matrices (tests/test_trapezoid.py,
tests/test_stokes_trapezoid.py) pin the engine refactor bit-exact; this
file covers what is new — the hm3d chunk tier against its pure-XLA
composition truth on 8-device periodic/open/mixed interpret meshes, the
wave2d per-step Mosaic tier (interpret-capable, so the REAL kernel body
runs here) and 2-D chunk tier against the XLA composition, the
structured Admission verdicts on both ladders, the `_vmem` budget
authority (fit_chunk_K + cap override) the engine dispatches through,
and every factory's ladder since the streaming banded tier was removed.
The compiled Mosaic realizations are TPU-only and pinned on hardware by
tests/test_mega_tpu.py.
"""

import numpy as np
import pytest

import igg
from igg.ops import _vmem


# ---------------------------------------------------------------------------
# _vmem: the single budget authority (satellite)
# ---------------------------------------------------------------------------

def test_fit_chunk_k_halving():
    # Walks kmax, kmax/2, ...; returns the first admissible; 0 when none.
    assert _vmem.fit_chunk_K(lambda K: K <= 5, 8) == 4
    assert _vmem.fit_chunk_K(lambda K: K == 8, 8) == 8
    assert _vmem.fit_chunk_K(lambda K: False, 8) == 0
    assert _vmem.fit_chunk_K(lambda K: True, 8, min_k=4) == 8
    assert _vmem.fit_chunk_K(lambda K: K < 4, 8, min_k=4) == 0
    # Admission objects work as predicates (truthy/falsy).
    from igg.degrade import Admission

    assert _vmem.fit_chunk_K(
        lambda K: Admission.yes() if K <= 4 else Admission.no("big"),
        16) == 4


def test_vmem_cap_override_round_trip():
    base_cap = _vmem.vmem_cap()
    base_budget = _vmem.chunk_budget()
    try:
        _vmem.set_cap_override(64 * 1024 * 1024)
        assert _vmem.vmem_cap() == 64 * 1024 * 1024
        assert _vmem.chunk_budget() == 64 * 1024 * 1024
        assert _vmem.vmem_limit(2 ** 30) == 64 * 1024 * 1024
    finally:
        _vmem.set_cap_override(None)
    assert _vmem.vmem_cap() == base_cap
    assert _vmem.chunk_budget() == base_budget


# ---------------------------------------------------------------------------
# HM3D trapezoid tier (generated from the engine)
# ---------------------------------------------------------------------------

def _hm3d_compare(mesh, periods, K, tol=2e-5):
    from igg.models import hm3d

    igg.init_global_grid(16, 16, 128, dimx=mesh[0], dimy=mesh[1],
                         dimz=mesh[2], periodx=periods[0],
                         periody=periods[1], periodz=periods[2],
                         quiet=True)
    p = hm3d.Params(lx=4.0, ly=4.0, lz=4.0)
    Pe, phi = hm3d.init_fields(p, dtype=np.float32)
    n_inner = K + 1          # warm-up + one full chunk
    ref = hm3d.make_step(p, donate=False, n_inner=n_inner,
                         use_pallas=False)
    trap = hm3d.make_step(p, donate=False, n_inner=n_inner,
                          use_pallas=True, pallas_interpret=True,
                          trapezoid=True, K=K)
    r = ref(Pe, phi)
    t = trap(Pe, phi)
    assert igg.degrade.active().get("hm3d") == "hm3d.trapezoid"
    for name, a, b in zip(("Pe", "phi"), r, t):
        a, b = (np.asarray(v, np.float64) for v in (a, b))
        rel = np.abs(a - b).max() / (np.abs(a).max() + 1e-30)
        assert rel < tol, (name, rel, mesh, periods)
    igg.finalize_global_grid()


def test_hm3d_chunk_ring_periodic():
    """(8,1,1) fully periodic: x extended by self/neighbor slabs, y/z
    in-window self-wrap."""
    _hm3d_compare((8, 1, 1), (1, 1, 1), K=4)


def test_hm3d_chunk_ring_open():
    """(8,1,1) all open — the reference-default boundary condition:
    'oext' x with BOTH fields' boundary planes frozen, frozen y/z."""
    _hm3d_compare((8, 1, 1), (0, 0, 0), K=4)


def test_hm3d_chunk_torus_mixed():
    """(2,2,2) mixed: open x/z around periodic extended y (K=8 — the
    y-extension sublane-tile gate)."""
    _hm3d_compare((2, 2, 2), (0, 1, 0), K=8)


def test_hm3d_chunk_single_device_frozen():
    """(1,1,1) all open: every dim 'frozen' — both fields' boundary
    planes re-frozen every step."""
    _hm3d_compare((1, 1, 1), (0, 0, 0), K=4)


def test_hm3d_chunk_with_remainder():
    """n_inner = warm-up + one K=4 chunk + 2 per-step remainder steps."""
    from igg.models import hm3d

    igg.init_global_grid(16, 16, 128, dimx=8, dimy=1, dimz=1,
                         periodx=1, periody=1, periodz=1, quiet=True)
    p = hm3d.Params(lx=4.0, ly=4.0, lz=4.0)
    Pe, phi = hm3d.init_fields(p, dtype=np.float32)
    ref = hm3d.make_step(p, donate=False, n_inner=7, use_pallas=False)
    trap = hm3d.make_step(p, donate=False, n_inner=7, use_pallas=True,
                          pallas_interpret=True, trapezoid=True, K=4)
    r = ref(Pe, phi)
    t = trap(Pe, phi)
    for name, a, b in zip(("Pe", "phi"), r, t):
        a, b = (np.asarray(v, np.float64) for v in (a, b))
        rel = np.abs(a - b).max() / (np.abs(a).max() + 1e-30)
        assert rel < 2e-5, (name, rel)


def test_hm3d_chunk_admission_matrix():
    """Structured Admission verdicts of the hm3d chunk gate."""
    from igg.ops.hm3d_trapezoid import (fit_hm3d_K,
                                        hm3d_trapezoid_supported)

    igg.init_global_grid(16, 16, 128, dimx=8, dimy=1, dimz=1,
                         periodx=1, periody=1, periodz=1, quiet=True)
    grid = igg.get_global_grid()
    s = (16, 16, 128)
    ok = hm3d_trapezoid_supported
    assert ok(grid, s, 4, 4, np.float32)
    adm = ok(grid, s, 4, 3, np.float32)          # no full chunk
    assert not adm and "chunk" in adm.reason
    adm = ok(grid, s, 1, 8, np.float32)          # K < 2
    assert not adm
    adm = ok(grid, s, 4, 4, np.float64)          # f32 only
    assert not adm and "float32" in adm.reason
    adm = ok(grid, s, 16, 16, np.float32)        # send slabs too deep
    assert not adm
    assert fit_hm3d_K(grid, s, 8, np.float32) == 8
    assert fit_hm3d_K(grid, s, 3, np.float32) == 0
    igg.finalize_global_grid()
    # overlap-3 grid: the per-step kernel's overlap-2 prerequisite fails
    igg.init_global_grid(16, 16, 128, dimx=8, dimy=1, dimz=1,
                         periodx=1, periody=1, periodz=1,
                         overlapx=3, overlapy=3, overlapz=3, quiet=True)
    grid = igg.get_global_grid()
    adm = ok(grid, s, 4, 4, np.float32)
    assert not adm and "overlaps" in adm.reason


def test_hm3d_trapezoid_true_raises_when_unsupported():
    """trapezoid=True is a real contract: requirement-string GridError
    when no K is admissible."""
    from igg.models import hm3d

    igg.init_global_grid(16, 16, 128, dimx=8, dimy=1, dimz=1,
                         periodx=1, periody=1, periodz=1, quiet=True)
    p = hm3d.Params(lx=4.0, ly=4.0, lz=4.0)
    Pe, phi = hm3d.init_fields(p, dtype=np.float32)
    step = hm3d.make_step(p, donate=False, n_inner=2, use_pallas=True,
                          pallas_interpret=True, trapezoid=True)
    with pytest.raises(igg.GridError, match="chunk tier"):
        step(Pe, phi)


# ---------------------------------------------------------------------------
# wave2d Mosaic per-step tier
# ---------------------------------------------------------------------------

def _wave_fields(p, dtype=np.float32, pre_steps=0):
    from igg.models import wave2d

    fields = wave2d.init_fields(p, dtype=dtype)
    if pre_steps:
        pre = wave2d.make_step(p, donate=False, n_inner=pre_steps,
                               use_pallas=False)
        fields = pre(*fields)
    return fields


@pytest.mark.parametrize("periods", [(1, 1), (0, 0)],
                         ids=["periodic", "open"])
def test_wave2d_mosaic_matches_xla(periods):
    """The fused per-step kernel (real kernel body, interpret mode) on
    the (4,2,1) 8-device mesh — periodic AND open (the tier's halo half
    is the exchange engine, so every boundary condition is served)."""
    from igg.models import wave2d

    igg.init_global_grid(8, 8, 1, periodx=periods[0], periody=periods[1],
                         quiet=True)
    p = wave2d.Params()
    fields = _wave_fields(p)
    ref = wave2d.make_step(p, donate=False, n_inner=5, use_pallas=False)
    pal = wave2d.make_step(p, donate=False, n_inner=5, use_pallas=True,
                           pallas_interpret=True, chunk=False)
    r = ref(*fields)
    o = pal(*fields)
    assert igg.degrade.active().get("wave2d") == "wave2d.mosaic"
    for name, a, b in zip(("P", "Vx", "Vy"), r, o):
        a, b = (np.asarray(v, np.float64) for v in (a, b))
        rel = np.abs(a - b).max() / (np.abs(a).max() + 1e-30)
        assert rel < 1e-5, (name, rel, periods)


def test_wave2d_xla_rung_serves_f64():
    """The fast tiers are f32-only: the f64 configuration (the historical
    test setup) rides the truth rung."""
    from igg.models import wave2d

    igg.init_global_grid(8, 8, 1, periodx=1, periody=1, quiet=True)
    p = wave2d.Params()
    fields = _wave_fields(p, dtype=np.float64)
    step = wave2d.make_step(p, donate=False, use_pallas=True,
                            pallas_interpret=True)
    with pytest.raises(igg.GridError):
        step(*fields)     # use_pallas=True on f64 is a real refusal
    auto = wave2d.make_step(p, donate=False, use_pallas="auto",
                            pallas_interpret=True)
    auto(*fields)
    assert igg.degrade.active().get("wave2d") == "wave2d.xla"


# ---------------------------------------------------------------------------
# wave2d 2-D chunk tier
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mesh,local", [((4, 2, 1), (16, 16)),
                                        ((1, 1, 1), (16, 16))],
                         ids=["mesh42", "selfwrap"])
def test_wave2d_chunk_matches_xla(mesh, local):
    """One warm-up + one K=4 chunk on periodic meshes, from an
    overlap-consistent model-evolved state, against the composition."""
    from igg.models import wave2d

    igg.init_global_grid(local[0], local[1], 1, dimx=mesh[0],
                         dimy=mesh[1], dimz=mesh[2],
                         periodx=1, periody=1, quiet=True)
    p = wave2d.Params()
    fields = _wave_fields(p, pre_steps=3)
    ref = wave2d.make_step(p, donate=False, n_inner=5, use_pallas=False)
    chk = wave2d.make_step(p, donate=False, n_inner=5, use_pallas=True,
                           pallas_interpret=True, chunk=True, K=4)
    r = ref(*fields)
    c = chk(*fields)
    assert igg.degrade.active().get("wave2d") == "wave2d.chunk"
    for name, a, b in zip(("P", "Vx", "Vy"), r, c):
        a, b = (np.asarray(v, np.float64) for v in (a, b))
        rel = np.abs(a - b).max() / (np.abs(a).max() + 1e-30)
        assert rel < 2e-5, (name, rel, mesh)


def test_wave2d_chunk_refuses_open_with_structured_reason():
    """Open meshes are refused with a structured Admission naming the
    periodic-only contract (the per-step tiers serve them) — and the
    auto ladder falls to the mosaic rung there instead."""
    from igg.models import wave2d
    from igg.ops.wave2d_pallas import wave2d_chunk_supported

    igg.init_global_grid(16, 16, 1, quiet=True)   # all open
    grid = igg.get_global_grid()
    adm = wave2d_chunk_supported(grid, (16, 16), 4, 8, np.float32)
    assert not adm and "periodic" in adm.reason
    p = wave2d.Params()
    fields = _wave_fields(p)
    step = wave2d.make_step(p, donate=False, n_inner=5, use_pallas=True,
                            pallas_interpret=True, chunk="auto")
    step(*fields)
    assert igg.degrade.active().get("wave2d") == "wave2d.mosaic"


def test_wave2d_chunk_admission_matrix():
    from igg.ops.wave2d_pallas import fit_wave2d_K, wave2d_chunk_supported

    igg.init_global_grid(16, 16, 1, periodx=1, periody=1, quiet=True)
    grid = igg.get_global_grid()
    s = (16, 16)
    ok = wave2d_chunk_supported
    assert ok(grid, s, 4, 4, np.float32)
    assert not ok(grid, s, 4, 3, np.float32)      # no full chunk
    assert not ok(grid, s, 1, 8, np.float32)      # K < 2
    assert not ok(grid, s, 8, 8, np.float32)      # 2K slabs too deep
    assert not ok(grid, s, 4, 4, np.float64)      # f32 only
    assert fit_wave2d_K(grid, s, 8, np.float32) == 4
    igg.finalize_global_grid()


def test_wave2d_chunk_true_raises_when_unsupported():
    from igg.models import wave2d

    igg.init_global_grid(16, 16, 1, periodx=1, periody=1, quiet=True)
    p = wave2d.Params()
    fields = _wave_fields(p)
    step = wave2d.make_step(p, donate=False, n_inner=2, use_pallas=True,
                            pallas_interpret=True, chunk=True)
    with pytest.raises(igg.GridError, match="chunk tier"):
        step(*fields)


# ---------------------------------------------------------------------------
# Verify-on-first-use guards the generated tiers (the miscompile story)
# ---------------------------------------------------------------------------

def test_corrupt_hm3d_chunk_tier_never_serves():
    """A chaos-corrupted hm3d.trapezoid output must be caught by
    verify-on-first-use and quarantined — the generated-tier safety
    contract: a miscompiled generated tier can never serve wrong
    physics."""
    from igg.models import hm3d

    igg.init_global_grid(16, 16, 128, dimx=8, dimy=1, dimz=1,
                         periodx=1, periody=1, periodz=1, quiet=True)
    p = hm3d.Params(lx=4.0, ly=4.0, lz=4.0)
    Pe, phi = hm3d.init_fields(p, dtype=np.float32)
    igg.degrade.reset()
    try:
        with igg.chaos.kernel_corrupt("hm3d.trapezoid", magnitude=1e3):
            step = hm3d.make_step(p, donate=False, n_inner=5,
                                  use_pallas=True, pallas_interpret=True,
                                  trapezoid="auto", verify="first_use")
            step(Pe, phi)
        q = igg.degrade.status()
        assert "hm3d.trapezoid" in q
        assert q["hm3d.trapezoid"].reason == "verify_mismatch"
        # Dispatch fell to the next healthy rung.
        assert igg.degrade.active().get("hm3d") in ("hm3d.mosaic",
                                                    "hm3d.xla")
    finally:
        igg.degrade.reset()


def test_use_pallas_false_pins_xla_past_the_chunk_tiers():
    """use_pallas=False must reach the truth rung even where the chunk
    tier would be admissible — the chunk tiers ride the fused kernels,
    so an explicit XLA pin outranks them (hm3d, wave2d, and stokes all
    share the gate)."""
    from igg.models import hm3d

    igg.init_global_grid(16, 16, 128, dimx=8, dimy=1, dimz=1,
                         periodx=1, periody=1, periodz=1, quiet=True)
    p = hm3d.Params(lx=4.0, ly=4.0, lz=4.0)
    Pe, phi = hm3d.init_fields(p, dtype=np.float32)
    step = hm3d.make_step(p, donate=False, n_inner=5, use_pallas=False,
                          pallas_interpret=True, trapezoid="auto")
    step(Pe, phi)
    assert igg.degrade.active().get("hm3d") == "hm3d.xla"
    igg.finalize_global_grid()

    from igg.models import wave2d

    igg.init_global_grid(16, 16, 1, periodx=1, periody=1, quiet=True)
    wp = wave2d.Params()
    fields = wave2d.init_fields(wp, dtype=np.float32)
    wstep = wave2d.make_step(wp, donate=False, n_inner=5,
                             use_pallas=False, pallas_interpret=True,
                             chunk="auto")
    wstep(*fields)
    assert igg.degrade.active().get("wave2d") == "wave2d.xla"


# ---------------------------------------------------------------------------
# The ladders after the streaming banded tier's removal: every factory's
# rungs, stale tune-cache winners, and the shapes that tier used to serve
# ---------------------------------------------------------------------------

_REMOVED = "banded"     # the removed tier's suffix and factory option

def _ladder_case(family):
    """(factory kwargs -> step, fields) for one family on a small
    8-device interpret mesh where its chunk tier admits."""
    from igg.models import diffusion3d, hm3d, stokes3d, wave2d

    if family == "diffusion3d":
        igg.init_global_grid(16, 16, 128, dimx=8, periodx=1, periody=1,
                             periodz=1, quiet=True)
        p = diffusion3d.Params(lx=4.0, ly=4.0, lz=4.0)
        return (lambda **kw: diffusion3d.make_multi_step(5, p, **kw),
                diffusion3d.init_fields(p, dtype=np.float32))
    if family == "stokes3d":
        igg.init_global_grid(16, 16, 128, dimx=8, periodx=1, periody=1,
                             periodz=1, overlapx=3, overlapy=3, overlapz=3,
                             quiet=True)
        p = stokes3d.Params(lx=4.0, ly=4.0, lz=4.0)
        fields = stokes3d.init_fields(p, dtype=np.float32)
        return (lambda **kw: stokes3d.make_iteration(p, n_inner=5, **kw),
                tuple(fields))
    if family == "hm3d":
        igg.init_global_grid(16, 16, 128, dimx=8, periodx=1, periody=1,
                             periodz=1, quiet=True)
        p = hm3d.Params(lx=4.0, ly=4.0, lz=4.0)
        return (lambda **kw: hm3d.make_step(p, n_inner=5, **kw),
                hm3d.init_fields(p, dtype=np.float32))
    igg.init_global_grid(16, 16, 1, dimx=4, dimy=2, periodx=1, periody=1,
                         quiet=True)
    p = wave2d.Params()
    fields = _wave_fields(p, pre_steps=3)
    if family == "wave2d":
        return (lambda **kw: wave2d.make_step(p, n_inner=5, **kw), fields)
    from igg import stencil

    spec, cf = stencil.wave2d_spec(), stencil.wave2d_coeffs(p)
    return (lambda **kw: stencil.compile(spec, coeffs=cf, n_inner=5, **kw),
            fields)


@pytest.mark.parametrize("family,rungs", [
    ("diffusion3d", ["mosaic", "xla"]),
    ("stokes3d", ["trapezoid", "mosaic", "xla"]),
    ("hm3d", ["trapezoid", "mosaic", "xla"]),
    ("wave2d", ["chunk", "mosaic", "xla"]),
    ("wave2d_spec", ["chunk", "mosaic", "xla"]),
])
def test_factory_ladder_rungs(family, rungs):
    """Each factory's ladder is exactly its resident chunk tier (where
    the family has one), the per-step Mosaic tier and the XLA truth —
    and the removed tier's option is no longer accepted."""
    make, _ = _ladder_case(family)
    step = make(donate=False, pallas_interpret=True, tune=False)
    assert [t.name for t in step.ladder.tiers] == [f"{family}.{r}"
                                                   for r in rungs]
    with pytest.raises(TypeError):
        make(donate=False, pallas_interpret=True, **{_REMOVED: True})


@pytest.mark.parametrize("family,default", [("diffusion3d", "mosaic"),
                                            ("stokes3d", "trapezoid"),
                                            ("hm3d", "trapezoid"),
                                            ("wave2d", "chunk")])
def test_stale_banded_winner_serves_default_ladder(family, default, tmp_path,
                                                   monkeypatch):
    """A tune cache written while the streaming banded tier existed (its
    winner tier plus a `band` field) still loads under tune="auto", and the
    factory serves the same tier as an untuned one: a winner whose tier
    is not on the ladder is no winner."""
    import json

    from igg import autotune

    path = tmp_path / "tune.json"
    monkeypatch.setenv("IGG_TUNE_CACHE", str(path))
    autotune.reset()
    try:
        make, fields = _ladder_case(family)
        plain = make(donate=False, pallas_interpret=True, tune=False)
        plain(*fields)
        want = igg.degrade.active().get(family)
        assert want == f"{family}.{default}"
        autotune.record_winner(family, {"tier": f"{family}.{_REMOVED}",
                                        "K": 4, "bx": None, "vmem_mb": None,
                                        "ms": 1.0})
        doc = json.loads(path.read_text())
        for e in doc["entries"].values():
            e["band"] = 8
        path.write_text(json.dumps(doc))
        autotune.reset()
        tuned = make(donate=False, pallas_interpret=True, tune="auto")
        assert autotune.get(family)["band"] == 8    # the file loaded
        tuned(*fields)
        assert igg.degrade.active().get(family) == want
    finally:
        autotune.reset()


@pytest.mark.parametrize("family", ["hm3d", "wave2d", "wave2d_spec"])
def test_mosaic_serves_where_resident_chunk_refuses(family):
    """Under a 2 MiB VMEM cap the resident chunk tier refuses these
    8-device blocks; the per-step Mosaic tier serves them and matches
    the XLA composition."""
    from igg.models import hm3d, wave2d

    if family == "hm3d":
        igg.init_global_grid(16, 16, 128, dimx=8, periodx=1, periody=1,
                             periodz=1, quiet=True)
        p = hm3d.Params(lx=4.0, ly=4.0, lz=4.0)
        fields = hm3d.init_fields(p, dtype=np.float32)
        make = lambda **kw: hm3d.make_step(p, donate=False, n_inner=5,
                                           **kw)
        names = ("Pe", "phi")
    else:
        # 128 x 256 blocks: the 2K-extended whole-window working set
        # exceeds 2 MiB at every K.
        igg.init_global_grid(128, 256, 1, dimx=4, dimy=2, periodx=1,
                             periody=1, quiet=True)
        p = wave2d.Params()
        fields = _wave_fields(p, pre_steps=3)
        names = ("P", "Vx", "Vy")
        if family == "wave2d":
            make = lambda **kw: wave2d.make_step(p, donate=False,
                                                 n_inner=5, **kw)
        else:
            from igg import stencil

            spec, cf = stencil.wave2d_spec(), stencil.wave2d_coeffs(p)
            make = lambda **kw: stencil.compile(spec, coeffs=cf,
                                                donate=False, n_inner=5,
                                                **kw)
    ref = make(use_pallas=False)
    step = make(pallas_interpret=True, tune=False)
    r = ref(*fields)
    try:
        _vmem.set_cap_override(2 * 1024 * 1024)
        o = step(*fields)
        assert igg.degrade.active().get(family) == f"{family}.mosaic"
    finally:
        _vmem.set_cap_override(None)
    for name, a, c in zip(names, r, o):
        a, c = (np.asarray(v, np.float64) for v in (a, c))
        rel = np.abs(a - c).max() / (np.abs(a).max() + 1e-30)
        assert rel < 2e-5, (name, rel)


def test_explicit_chunk_true_outranks_cached_xla_winner(tmp_path,
                                                        monkeypatch):
    """A cached '<family>.xla' winner must not turn an explicit
    trapezoid=True request into a spurious GridError."""
    from igg import autotune
    from igg.models import hm3d

    monkeypatch.setenv("IGG_TUNE_CACHE", str(tmp_path / "tune.json"))
    autotune.reset()
    try:
        igg.init_global_grid(16, 16, 128, dimx=8, dimy=1, dimz=1,
                             periodx=1, periody=1, periodz=1, quiet=True)
        autotune.record_winner("hm3d", {"tier": "hm3d.xla", "K": None,
                                        "bx": None, "vmem_mb": None,
                                        "ms": 1.0})
        p = hm3d.Params(lx=4.0, ly=4.0, lz=4.0)
        Pe, phi = hm3d.init_fields(p, dtype=np.float32)
        step = hm3d.make_step(p, donate=False, n_inner=5,
                              pallas_interpret=True, trapezoid=True,
                              tune="auto")
        step(Pe, phi)
        assert igg.degrade.active().get("hm3d") == "hm3d.trapezoid"
    finally:
        autotune.reset()
