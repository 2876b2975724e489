"""Headline benchmark: 3-D heat diffusion, 256^3 per chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.

Baseline derivation (see BASELINE.md): the reference reports 29 min wall-clock
for 100k steps of 3-D heat diffusion on a 510^3 global grid over 8x NVIDIA
P100 (255^3 per GPU, CuArray-broadcast version) on Piz Daint
(`/root/reference/README.md:158-162`) — i.e. 17.4 ms/step/GPU.  We run the
same physics at 256^3 per chip and report ms/step; `vs_baseline` is the
speedup over 17.4 ms (>1 = faster than the reference's published number).

Both execution paths are measured and emitted:
  - `pallas_ms`: the fused Pallas step (the flagship path);
  - `xla_ms`:    the portable shard_map/XLA path (identical program shape to
                 a multi-chip run — periodic self-wrap moves the same planes
                 as an interior rank).
`value` is the flagship (best) path.  Timing uses the slope method
(`igg.time_steps`): two batches of dispatches, each ending in one readback,
so the constant per-batch dispatch and readback cost cancels.

Needs a TPU: without one it exits non-zero and measures nothing.

The grid is fully periodic so the halo path executes even on one chip (the
self-wrap branch, the same planes moved per step as an interior rank).
"""

import json
import sys

import numpy as np


def main():
    import jax

    import igg
    from igg import _env
    from igg.models import diffusion3d as d3

    _env.place_compile_cache()
    platform = jax.devices()[0].platform
    if platform != "tpu":
        sys.exit(f"bench.py needs a TPU; JAX found {platform}")
    n = 256
    # 100 steps per compiled program, a slope window of 20 dispatches,
    # median of 3 runs per path.
    nt, n_inner, reps = 20, 100, 3

    igg.init_global_grid(n, n, n, periodx=1, periody=1, periodz=1, quiet=True)
    params = d3.Params()

    def measure(**kw):
        secs = []
        for _ in range(reps):
            _, sec = d3.run(nt, params, dtype=np.float32, n_inner=n_inner,
                            **kw)
            secs.append(sec)
        return sorted(secs)[len(secs) // 2]

    grid = igg.get_global_grid()
    xla_sec = measure(use_pallas=False)
    pallas_sec = measure(use_pallas=True)

    best = min(xla_sec, pallas_sec)
    ms = best * 1e3

    cells = float(n) ** 3
    # Equivalent ideal-fusion throughput (bytes a kernel touching only
    # `read T + Cp, write T` would need): a speedup proxy, NOT a physical
    # bandwidth — the mega-kernel exceeds "peak" here because it keeps Cp
    # resident in VMEM.  The physical number is pct_hbm_peak, computed
    # against the flagship path's actual per-step traffic
    # T*(1+2/bx) + T_out (+ Cp/K, negligible), bx=8.
    gbps_ideal = 3 * cells * 4 / best / 1e9
    actual_bytes = cells * 4 * (1 + 2 / 8) + cells * 4
    # The one peak table (igg.perf); an unknown device kind leaves the
    # share unmeasured rather than guessed.
    from igg.perf import hbm_peak_gbps

    kind = jax.devices()[0].device_kind
    peak = hbm_peak_gbps(kind)
    pct_peak = ((actual_bytes / best / 1e9) / peak * 100
                if peak is not None and best == pallas_sec else None)

    baseline_ms = 17.4  # ms/step/GPU, reference 510^3 on 8x P100
    result = {
        "metric": f"diffusion3d_{n}cubed_ms_per_step",
        "value": round(ms, 4),
        "unit": "ms",
        "vs_baseline": round(baseline_ms / ms, 3),
        "xla_ms": round(xla_sec * 1e3, 4),
        "pallas_ms": round(pallas_sec * 1e3, 4),
        "gbps_equivalent_ideal_fusion": round(gbps_ideal, 1),
        "pct_hbm_peak_actual_traffic": (round(pct_peak, 1)
                                        if pct_peak is not None
                                        else "not measured"),
        "assumed_hbm_peak_gbps": peak,
        "device_kind": kind,
    }
    print(f"[bench] platform={platform} devices={grid.nprocs} "
          f"dims={grid.dims} local={n}^3 "
          f"xla={xla_sec * 1e3:.3f}ms pallas={pallas_sec * 1e3:.3f}ms "
          f"~{gbps_ideal:.1f} GB/s ideal-fusion equiv", file=sys.stderr)
    igg.finalize_global_grid()

    if len(jax.devices()) == 1:
        # The reference's published headline configuration: 512^3 OPEN
        # boundaries on ONE chip (nx is a LOCAL size, so a multi-chip run
        # would measure an exchanged 512^3-per-chip grid instead — hence
        # the 1-device guard).  Compute-only ms/step.  A failure here
        # fails the bench.
        igg.init_global_grid(512, 512, 512, quiet=True)
        try:
            sec512 = sorted(
                d3.run(nt, params, dtype=np.float32, n_inner=n_inner,
                       use_pallas=True)[1] for _ in range(3))[1]
        finally:
            igg.finalize_global_grid()
        result["ms_per_step_512cubed_open"] = round(sec512 * 1e3, 4)
        print(f"[bench] 512^3 open (headline config): "
              f"{sec512 * 1e3:.3f} ms/step", file=sys.stderr)

    print(json.dumps(result))


if __name__ == "__main__":
    main()
