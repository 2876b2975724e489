"""Comm/compute overlap study (BASELINE.json configs 4-5: the
`@hide_communication` workloads).

Times each model's step on the same grid:
  - plain    — compute then grouped `update_halo_local` (XLA may still
               overlap what the data flow allows);
  - hidden   — `igg.hide_communication`: send planes from thin slab
               recomputations, so the full-domain stencil is
               data-independent of every collective;
  - pallas   — the fused kernel (diffusion, Stokes, and HM3D), where
               applicable.

Models: `diffusion3d` (flagship, radius 1) and `stokes3d` (BASELINE config
5's Stokes solver, radius 2 — run on an overlap-3 grid).  On a 1-device
grid there is NO communication to hide (the exchange is HBM-local), so
hidden-vs-plain measures pure restructuring overhead: ~0 for diffusion
(radius-1, single-field slabs), substantial for Stokes (radius-2 slabs of
five arrays, including minor-dim z-slabs).

Honest reading of the committed artifacts (see results/*.jsonl): as of this
round, `hidden` does NOT beat `plain` in ANY measured configuration — not
on the single chip (no communication to hide, pure overhead) and not on the
8-device virtual CPU mesh (in-process "collectives" are memcpys with
nothing to overlap, and the slab recomputation contends for the same
cores).  Neither environment exercises real ICI links, where XLA's
latency-hiding scheduler can actually run the interior stencil while planes
are in flight — the configuration `hide_communication` exists for — but no
measurement demonstrating a win exists in this repo, and model defaults are
therefore `overlap=False` everywhere.  Treat `hide_communication` as a
correctness-complete mechanism whose performance case is unproven until a
multi-chip TPU measurement lands.

Methodology note (round 3): cross-PROCESS compile variance dominates the
noise on these model steps — XLA's layout/fusion choices differ run to run
(diffusion plain observed 0.46-0.52 ms, Stokes hidden 0.26-0.42 ms across
five fresh processes at the same commit), while within-process medians are
tight.  The committed artifact is the run closest (per-metric) to the
cross-process medians of five runs; single outlier draws (one run showed
Stokes hidden at 1.11x plain) must not be read as wins.  Halo assembly in
the models is pinned per measurement via `update_halo_local(...,
assembly=)`: "xla" for the radius-1 single-field diffusion step (the select
chain fuses into the stencil pass), the default Pallas writers for the
multi-field Stokes/hm3d steps.

Usage: `python benchmarks/overlap_study.py [local_n] [nt] [n_inner]`.
"""

from __future__ import annotations

import sys

import numpy as np

from common import emit, median_of, note


def _study(model_run, metric_prefix, supported_fn, grid_kwargs,
           extra_config, n, nt, n_inner, platform):
    """Shared study body: time plain / hidden / (pallas where supported)
    variants of one model's step on a fresh grid and emit the JSON lines."""
    import igg
    import jax

    igg.init_global_grid(n, n, n, periodx=1, periody=1, periodz=1,
                         quiet=True, **grid_kwargs)
    grid = igg.get_global_grid()
    note(f"{metric_prefix} platform={platform} devices={grid.nprocs} "
         f"dims={grid.dims} local={n}^3")

    variants = [("plain", dict(overlap=False)), ("hidden", dict(overlap=True))]
    F0 = jax.ShapeDtypeStruct((n, n, n), np.float32)
    if platform == "tpu" and supported_fn(grid, F0):
        variants.append(("pallas", dict(use_pallas=True)))

    times = {}
    for name, kv in variants:
        sec = median_of(lambda: model_run(nt, dtype=np.float32,
                                          n_inner=n_inner, **kv)[1])
        times[name] = sec
        # Comm ledger (igg.comm, round 14): the measured variant times
        # are ledger samples too (family "comm", tier
        # "<metric_prefix>.<variant>"), so the overlap story and the
        # autotuner prior live in one queryable store.
        from igg import perf as iperf

        iperf.record("comm", f"{metric_prefix}.{name}", sec * 1e3,
                     source="bench", local_shape=(n, n, n),
                     dtype="float32", dims=tuple(grid.dims),
                     **iperf.device_context())
        emit({
            "metric": f"{metric_prefix}_{name}",
            "value": round(sec * 1e3, 4),
            "unit": "ms",
            "config": {"local": n, "devices": grid.nprocs,
                       "dims": list(grid.dims), "platform": platform,
                       **extra_config},
            "speedup_vs_plain": round(times["plain"] / sec, 3),
        })
    igg.finalize_global_grid()


def study_diffusion(n, nt, n_inner, platform):
    from igg.models import diffusion3d as d3
    from igg.ops import pallas_supported

    # d3.run defaults use_pallas="auto"; the plain/hidden variants must
    # pin the XLA path explicitly.
    def run(nt, *, use_pallas=False, **kw):
        return d3.run(nt, use_pallas=use_pallas, **kw)

    _study(run, "diffusion3d_step", pallas_supported, {}, {},
           n, nt, n_inner, platform)


def study_stokes(n, nt, n_inner, platform):
    from igg.models import stokes3d
    from igg.ops import stokes_pallas_supported

    # Radius-2 update chain: overlap-3 grid (reference supports overlap>=3,
    # `/root/reference/test/test_update_halo.jl:188-217`).
    # stokes3d.run defaults use_pallas="auto"; the plain/hidden variants
    # must pin the XLA path explicitly (same as study_diffusion).
    def run(nt, *, use_pallas=False, **kw):
        return stokes3d.run(nt, use_pallas=use_pallas, **kw)

    _study(run, "stokes3d_iteration", stokes_pallas_supported,
           dict(overlapx=3, overlapy=3, overlapz=3),
           {"overlap_cells": 3}, n, nt, n_inner, platform)


def study_hm3d(n, nt, n_inner, platform):
    from igg.models import hm3d
    from igg.ops import hm3d_pallas_supported

    # hm3d.run defaults use_pallas="auto"; the plain/hidden variants must
    # pin the XLA path explicitly (same as study_diffusion).
    def run(nt, *, use_pallas=False, **kw):
        return hm3d.run(nt, use_pallas=use_pallas, **kw)

    _study(run, "hm3d_step", hm3d_pallas_supported, {}, {},
           n, nt, n_inner, platform)


def study_wave2d(n, nt, n_inner, platform):
    """BASELINE config 3: 2-D acoustic wave, 1-D periodic halo, three
    staggered fields in one grouped exchange (plain step only — the 2-D
    model has no fused-kernel tier; its step is bandwidth-trivial)."""
    import igg
    from igg.models import wave2d

    igg.init_global_grid(n, n, 1, periodx=1, quiet=True)
    grid = igg.get_global_grid()
    note(f"wave2d platform={platform} devices={grid.nprocs} "
         f"dims={grid.dims} local={n}^2")
    sec = median_of(lambda: wave2d.run(nt, dtype=np.float32,
                                       n_inner=n_inner)[1])
    cells = float(n) * n * grid.nprocs   # global cells advanced per step
    emit({
        "metric": "wave2d_step_plain",
        "value": round(sec * 1e3, 4),
        "unit": "ms",
        "config": {"local": n, "devices": grid.nprocs,
                   "dims": list(grid.dims), "platform": platform},
        "mcells_per_s": round(cells / sec / 1e6, 1),
    })
    igg.finalize_global_grid()


def study_decomposition_smoke(platform):
    """Round 14: the always-present CPU-smoke step-time decomposition
    row (golden-gated) — `igg.comm.decompose` on a small radius-1
    stencil, the production data path the per-variant model rows above
    are a bench-side view of.  The contract is structural (the
    decomposition is well-formed and emitted as a `comm_stats` record),
    not a performance claim — on a single chip or a shared-core CPU mesh
    there is no communication to hide (module docstring)."""
    import igg
    from igg.comm import model_step_variants

    igg.init_global_grid(16, 16, 16, periodx=1, periody=1, periodz=1,
                         quiet=True)
    grid = igg.get_global_grid()

    # The shared step-variant recipe (igg.comm.model_step_variants):
    # the same compute closure the autotuner's exposed-comm confirmation
    # and weak_scaling.py's columns decompose.
    mv = model_step_variants("diffusion3d")
    fields = mv["init"](np.float32)
    d = igg.comm.decompose(mv["compute"], fields[:mv["nf"]],
                           aux=fields[mv["nf"]:], radius=mv["radius"],
                           nt=3, n_inner=5)
    ok = (d["compute_ms"] > 0 and d["exchange_ms"] > 0
          and d["hidden_ms"] > 0
          and 0.0 <= d["exposed_comm_fraction"] <= 1.0)
    emit({
        "metric": "overlap_decomposition",
        "value": round(d["exposed_comm_fraction"], 4),
        "unit": "exposed-comm fraction",
        "config": {"local": 16, "devices": grid.nprocs,
                   "dims": list(grid.dims), "platform": platform},
        "compute_ms": round(d["compute_ms"], 4),
        "exchange_ms": round(d["exchange_ms"], 4),
        "hidden_ms": round(d["hidden_ms"], 4),
        "overlap_efficiency": round(d["overlap_efficiency"], 4)
        if "overlap_efficiency" in d else None,
        "pass": bool(ok),
        "contract": "igg.comm.decompose yields a well-formed step-time "
                    "decomposition (three positive variant times, "
                    "exposed-comm fraction in [0, 1]) and emits it as a "
                    "comm_stats record",
    })
    igg.finalize_global_grid()


def main():
    import jax

    platform = jax.devices()[0].platform
    n = int(sys.argv[1]) if len(sys.argv) > 1 else (256 if platform != "cpu" else 32)
    nt = int(sys.argv[2]) if len(sys.argv) > 2 else (12 if platform != "cpu" else 3)
    # 100-step dispatches keep the per-dispatch host cost small next to
    # the steps (see common.time_dispatches).
    n_inner = int(sys.argv[3]) if len(sys.argv) > 3 else (100 if platform != "cpu" else 5)

    study_diffusion(n, nt, n_inner, platform)
    # Stokes at 128^3+ per chip (VERDICT item 7's measurement); halve the
    # grid on CPU smoke runs.  Full n_inner: the iteration is FASTER than
    # the diffusion step, so halved batches would leave the per-dispatch
    # host cost a larger share of each sample.
    ns = max(128, n // 2) if platform != "cpu" else n
    study_stokes(ns, nt, n_inner if platform != "cpu" else 2, platform)
    # HM3D (BASELINE config 4's model family) at the diffusion size.
    study_hm3d(n, nt, n_inner, platform)
    # 2-D wave (BASELINE config 3) at the 2-D local size with the same
    # cell count as the 3-D grids (n^1.5 squared = n^3).
    study_wave2d(max(int(n ** 1.5), 16), nt, n_inner, platform)
    # Round 14: the always-emitted decomposition smoke/contract row.
    study_decomposition_smoke(platform)


if __name__ == "__main__":
    main()
