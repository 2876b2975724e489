"""Autotuned dispatch end to end: cold search → tuning-cache write →
a SECOND process reads the cache and serves the winner with ZERO search
dispatches — the `ci.sh` acceptance proof for `igg.autotune`.

Phase "cold" (first process):
  1. The perf ledger starts empty (no prior) and the tuning cache is a
     miss for the diffusion signature.
  2. `make_multi_step(..., tune=True)` runs the (tier, K, bx) search on
     warm scratch-copy dispatches — the ledger gains autotune-sourced
     samples for every candidate, and the winner persists to
     `IGG_TUNE_CACHE` (format igg-tune-cache-v1, atomic merge-on-write).
  3. The winner's measured step time is asserted <= the hand-picked
     bx=8 candidate's (the pre-autotuner default).

Phase "warm" (second process, same cache path):
  4. `make_multi_step(..., tune=True)` finds the cached winner: ZERO
     search dispatches (`igg.autotune.search_dispatches()` asserted 0),
     and the served configuration equals the cached winner (ladder
     active tier + applied bx asserted).
  5. `python -m igg.perf tune` renders the cache next to its ledger
     prior.

Run (ci.sh does exactly this):
    TMP=$(mktemp -d)
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        IGG_TUNE_CACHE=$TMP/tune.json IGG_PERF_LEDGER=$TMP/ledger.json \
        python examples/tuned_run.py cold
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        IGG_TUNE_CACHE=$TMP/tune.json IGG_PERF_LEDGER=$TMP/ledger.json \
        python examples/tuned_run.py warm
"""

import json
import os
import pathlib
import subprocess
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

phase = sys.argv[1] if len(sys.argv) > 1 else "cold"
assert phase in ("cold", "warm"), f"usage: tuned_run.py cold|warm, got {phase}"
assert os.environ.get("IGG_TUNE_CACHE"), "set IGG_TUNE_CACHE (shared file)"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import igg  # noqa: E402
from igg import autotune, perf  # noqa: E402
from igg import telemetry as tel  # noqa: E402
from igg.models import diffusion3d as d3  # noqa: E402

igg.init_global_grid(16, 16, 128, dimx=8, dimy=1, dimz=1,
                     periodx=1, periody=1, periodz=1, quiet=True)
params = d3.Params(lx=8.0, ly=8.0, lz=60.0)
N_INNER = 9
cache = pathlib.Path(os.environ["IGG_TUNE_CACHE"])

if phase == "cold":
    assert perf.best("diffusion3d") is None, \
        "cold phase expects an empty ledger seed"
    assert autotune.get("diffusion3d") is None, \
        "cold phase expects a tuning-cache miss"

    # tune=True on a miss runs the search inside the factory build.
    step = d3.make_multi_step(N_INNER, params, donate=False, tune=True,
                              pallas_interpret=True)
    n_search = autotune.search_dispatches()
    assert n_search > 0, "cold phase must have searched"
    w = autotune.get("diffusion3d")
    assert w is not None, "the winner must be cached"
    # Round 16: the overlap axis is part of every persisted winner — the
    # warm process must be able to serve the full
    # (tier, K, bx, vmem, overlap) configuration from the cache alone.
    assert isinstance(w.get("overlap"), bool), w
    print(f"cold: searched with {n_search} timed dispatches -> winner "
          f"tier={w['tier']} K={w['K']} bx={w['bx']} "
          f"overlap={w['overlap']} ms={w['ms']:.4f}")

    # The winner beats-or-equals the hand-picked bx=8 config (searched
    # samples carry per-candidate labels on the bus).
    hand = [r.payload["ms_per_step"] for r in tel.flight_recorder()
            if r.kind == "autotune_sample"
            and "bx=8" in r.payload["candidate"]]
    assert hand, "the search must have measured the hand-picked config"
    assert w["ms"] <= min(hand) * (1 + 1e-9), (w["ms"], min(hand))
    print(f"cold: winner {w['ms']:.4f} ms <= hand-picked bx=8 "
          f"{min(hand):.4f} ms")

    # The ledger was enriched by the search (the prior for next time).
    entries = perf.query("diffusion3d")
    assert entries and any("autotune" in e["sources"] for e in entries)

    # Durable: the versioned cache file round-trips.
    doc = json.loads(cache.read_text())
    assert doc["format"] == "igg-tune-cache-v1"
    assert any(e["family"] == "diffusion3d"
               for e in doc["entries"].values())
    perf.save()
    print(f"cold: cache written to {cache}")
else:
    assert cache.exists(), "warm phase needs the cold phase's cache"
    # The factory consults the cache: ZERO search dispatches in this
    # process, even with tune=True (search-on-miss, and this is a hit).
    step = d3.make_multi_step(N_INNER, params, donate=False, tune=True,
                              pallas_interpret=True)
    assert autotune.search_dispatches() == 0, \
        "warm phase must not search"
    w = autotune.get("diffusion3d")
    assert w is not None

    # Serve one dispatch and assert the served config IS the winner.
    T, Cp = d3.init_fields(params, dtype=np.float32)
    step(T, Cp)
    served = igg.degrade.active().get("diffusion3d")
    assert served == w["tier"], (served, w["tier"])
    assert autotune.search_dispatches() == 0
    # The overlap axis round-trips the cache and resolves to the served
    # schedule: overlap="auto" (the factory default) must follow the
    # cached winner exactly (admission permitting — this 8-device
    # radius-1 grid admits).
    assert isinstance(w.get("overlap"), bool), w
    from igg.overlap import resolve_overlap
    assert resolve_overlap("auto", family="diffusion3d",
                           tuned=w) == w["overlap"], w
    print(f"warm: served {served} with cached config "
          f"K={w['K']} bx={w['bx']} overlap={w['overlap']} "
          f"after 0 search dispatches")

    # The CLI renders the cache next to its ledger prior.
    out = subprocess.run(
        [sys.executable, "-m", "igg.perf", "tune", str(cache),
         "--family", "diffusion3d"],
        capture_output=True, text=True, env=os.environ)
    assert out.returncode == 0, out.stderr
    assert "diffusion3d" in out.stdout
    print(out.stdout.rstrip())

print(f"tuned_run {phase}: OK")
