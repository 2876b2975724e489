"""Chip smoke: diffusion3d's main path, end to end on the TPU.

Drives the public API (`igg.init_global_grid` -> `diffusion3d.init_fields`
-> `make_multi_step` -> `igg.gather_interior` -> `igg.finalize_global_grid`)
with the Mosaic tiers required (`use_pallas=True`, so a kernel the chip's
compiler refuses raises instead of falling to the XLA rung) and checks each
result against the pure-XLA truth rung run from the same initial fields.

    python chip_smoke.py             one chip: phase A, the reference's
                                     published configuration (512^3, open
                                     boundaries), and phase B, bench.py's
                                     (256^3, fully periodic) plus one
                                     standalone `igg.update_halo`
    python chip_smoke.py --chips 4   the (2,2,1) periodic mesh at 256^3 per
                                     chip against the same global problem
                                     on one chip; no other phase

After every phase nothing may have been degraded: `igg.degrade.status()`
is empty and the tier that served is not the XLA rung.  Served tier,
compile seconds and a rough ms/step are printed as information, not as
benchmark metrics.  The last stdout line is the contract line
`{"ok": true, "device": {...}}`; any failure exits non-zero before it, and
without a TPU the script exits non-zero at once (there is no CPU branch).
"""

import argparse
import json
import time

import numpy as np

N_INNER = 100      # steps per dispatch (one compiled program)
DISPATCHES = 2     # 200 steps per run
REL_TOL = 1e-5     # max|fast - truth| <= REL_TOL * max|truth|


def note(msg):
    print(f"[smoke] {msg}", flush=True)


def check(cond, msg):
    if not cond:
        raise SystemExit(f"[smoke] FAIL: {msg}")


def assert_not_degraded(phase, family="diffusion3d"):
    """Nothing quarantined (halo writer tier included) and, given a
    family, that it was served by a fast tier."""
    import igg.degrade

    q = igg.degrade.status()
    check(not q, f"{phase}: tiers quarantined: "
          f"{ {k: (v.reason, v.error) for k, v in q.items()} }")
    if family is None:
        return
    tier = igg.degrade.active().get(family)
    check(tier is not None and not tier.endswith(".xla"),
          f"{phase}: served by {tier!r}, not a Mosaic tier")


def run_steps(use_pallas):
    """200 diffusion3d steps from fresh initial fields on the live grid.
    Returns (final T on device, served tier, per-dispatch seconds)."""
    import igg
    import igg.degrade
    from igg.models import diffusion3d as d3

    T, Cp = d3.init_fields(d3.Params(), dtype=np.float32)
    step = d3.make_multi_step(N_INNER, d3.Params(), use_pallas=use_pallas)
    secs = []
    for _ in range(DISPATCHES):
        t0 = time.perf_counter()
        T = step(T, Cp)
        T.block_until_ready()
        secs.append(time.perf_counter() - t0)
    return T, igg.degrade.active()["diffusion3d"], secs


def report(phase, tier, secs):
    note(f"{phase}: served by {tier}; first dispatch {secs[0]:.1f} s "
         f"(compile included), then ~{secs[-1] / N_INNER * 1e3:.4f} "
         f"ms/step (rough, host clock)")


def compare(phase, got, want):
    check(got.shape == want.shape, f"{phase}: shapes {got.shape} vs "
          f"{want.shape}")
    check(np.isfinite(got).all(), f"{phase}: non-finite values")
    scale = float(np.max(np.abs(want)))
    err = float(np.max(np.abs(got.astype(np.float64) - want)))
    note(f"{phase}: max|fast - truth| = {err:.3e}, "
         f"{err / scale:.3e} of max|T| = {scale:.6g} (bound {REL_TOL:g})")
    check(err <= REL_TOL * scale, f"{phase}: fast tier disagrees with the "
          f"XLA truth rung beyond {REL_TOL:g} of max|T|")


def phase_single(phase, n, periodic):
    """One chip: the required Mosaic tier vs the XLA truth rung."""
    import jax

    import igg

    p = int(periodic)
    igg.init_global_grid(n, n, n, periodx=p, periody=p, periodz=p,
                         devices=jax.devices()[:1], quiet=True)
    try:
        T, tier, secs = run_steps(use_pallas=True)
        assert_not_degraded(phase)
        report(phase, tier, secs)
        got = igg.gather_interior(T)
        del T
        T, xtier, xsecs = run_steps(use_pallas=False)
        report(f"{phase} truth", xtier, xsecs)
        compare(phase, got, igg.gather_interior(T))
    finally:
        igg.finalize_global_grid()


def phase_halo(n, seed=0):
    """One standalone periodic `igg.update_halo` (the in-place Pallas
    writers on the chip) against the XLA halo plans, bit for bit."""
    import jax

    import igg

    igg.init_global_grid(n, n, n, periodx=1, periody=1, periodz=1,
                         devices=jax.devices()[:1], quiet=True)
    try:
        host = np.random.default_rng(seed).standard_normal(
            (n, n, n), dtype=np.float32)
        sharding = igg.sharding_for(3)
        got = igg.update_halo(jax.device_put(host, sharding))
        want = igg.update_halo(jax.device_put(host, sharding),
                               assembly="xla")
        assert_not_degraded("phase B halo", family=None)
        check(np.array_equal(np.asarray(got), np.asarray(want)),
              "phase B halo: writer update_halo differs from the XLA plans")
        note(f"phase B halo: update_halo {n}^3 periodic matches "
             f"assembly='xla' bit for bit")
    finally:
        igg.finalize_global_grid()


def phase_mesh(n):
    """The (2,2,1) periodic mesh over four chips vs one chip."""
    import jax

    import igg

    devs = jax.devices()[:4]
    check(len(devs) == 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    igg.init_global_grid(n, n, n, dimx=2, dimy=2, dimz=1, periodx=1,
                         periody=1, periodz=1, devices=devs, quiet=True)
    try:
        mesh = igg.get_global_grid().mesh
        note("mesh device order (gx, gy, gz) -> device: " + ", ".join(
            f"{idx}->{d.id}@{getattr(d, 'coords', None)}"
            for idx, d in np.ndenumerate(mesh.devices)))
        T, tier, secs = run_steps(use_pallas=True)
        held = {s.device for s in T.addressable_shards}
        check(len(held) == 4, f"mesh: shards sit on {len(held)} distinct "
              f"devices, not 4")
        assert_not_degraded("mesh")
        report("mesh 2x2x1", tier, secs)
        got = igg.gather_interior(T)
        del T
    finally:
        igg.finalize_global_grid()

    # The same global problem (2(n-2) x 2(n-2) x (n-2) periodic cells) on
    # one chip through the XLA truth rung.
    m = 2 * (n - 2) + 2
    igg.init_global_grid(m, m, n, periodx=1, periody=1, periodz=1,
                         devices=devs[:1], quiet=True)
    try:
        T, xtier, xsecs = run_steps(use_pallas=False)
        report("one chip truth", xtier, xsecs)
        compare("mesh vs one chip", got, igg.gather_interior(T))
    finally:
        igg.finalize_global_grid()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    from igg import _env  # fails outside a checkout of this repo

    note(f"compile cache: {_env.place_compile_cache()}")
    import jax

    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"needs a TPU; JAX found {devs[0].platform}")
    note(f"jax {jax.__version__}, {len(devs)} x {devs[0].device_kind}")
    from igg import native

    note("native re-tile library: "
         + ("built" if native.available() else "unavailable (numpy path)"))

    if args.chips == 4:
        phase_mesh(256)
    else:
        phase_single("phase A (512^3 open)", 512, periodic=False)
        phase_single("phase B (256^3 periodic)", 256, periodic=True)
        phase_halo(256)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
