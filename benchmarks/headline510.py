"""The reference's published headline workload, reproduced end-to-end.

`/root/reference/README.md:158-162`: 3-D heat diffusion on a **510^3 global
grid, 100,000 steps, with in-situ visualization every 1,000 steps** took
**29 min wall-clock on 8x NVIDIA Tesla P100** (CuArray broadcast version;
the reference's native-kernel variant is stated ">10x faster" but carries
no published wall-clock).

This script runs the example's physics (open boundaries, f32) for 100k
steps with a rendered PNG frame every 1,000 steps on whatever devices are
attached (one v5e chip here), at **512^3 global** — a tile-aligned
SUPERSET of the reference's 510^3 (1.2% more cells; 510 is not
slab-divisible for the fused kernel, and the comparison only gains from
solving the slightly larger problem).  Both execution tiers are measured:

  - `use_pallas=True` (the committed wall-clock): the K-step mega-kernel
    in streamed-coefficient frozen-edge mode (round 5), 2.79 ms/step of
    compute — the framework's recommended path, the analog of the
    reference's native-kernel tier;
  - the XLA broadcast-style path (~9.2 ms/step), the abstraction-level
    match for the reference's measured CuArray-broadcast version, emitted
    as `xla_ms_per_step` for the apples-to-apples reading.

In-situ visualization fetches ONLY what each frame renders — the mid-z
slice (~1 MB) — rather than the full 512 MB volume, so a frame moves
little over the device->host link.  The fetch + PNG
rendering run on a BACKGROUND worker thread (round 5): frames are
captured on device at sim time and handed off, so the host-side pipeline
(matplotlib ~2 s/frame — ~3 ms/step of serial stall at the 1,000-step
cadence, which round 4's runs paid in full) overlaps the next 1,000-step
dispatch instead of serializing with it — in-situ vis must not stall the
simulation.  One full-volume `gather_interior` runs at the end (final
state export) and is included in the wall-clock, as is the final drain
of the render queue.

Usage: `python benchmarks/headline510.py [--steps N] [--outdir DIR]`.
The committed artifact is a full 100k-step run.
"""

from __future__ import annotations

import pathlib
import sys
import time

import numpy as np

from common import emit, note


def main():
    steps = 100_000
    outdir = None
    args = sys.argv[1:]
    while args:
        a = args.pop(0)
        if a == "--steps":
            steps = int(args.pop(0))
        elif a == "--outdir":
            outdir = pathlib.Path(args.pop(0))
        else:
            raise SystemExit(f"unknown arg {a}")

    import jax

    import igg
    from igg.models import diffusion3d as d3

    platform = jax.devices()[0].platform
    n = 512 if platform == "tpu" else 64
    vis_every = 1_000 if platform == "tpu" else max(steps // 4, 1)

    igg.init_global_grid(n, n, n, quiet=True)
    grid = igg.get_global_grid()
    note(f"platform={platform} devices={grid.nprocs} dims={grid.dims} "
         f"global={igg.nx_g()}^3 steps={steps} vis_every={vis_every}")

    params = d3.Params()

    # Reference-tier comparator: the XLA broadcast-style step (slope-timed).
    _, xla_sec = d3.run(6, params, dtype=np.float32, n_inner=50,
                        use_pallas=False)

    T, Cp = d3.init_fields(params, dtype=np.float32)
    use_pallas = platform == "tpu"
    step = d3.make_multi_step(vis_every, params, use_pallas=use_pallas)

    try:
        import matplotlib
        matplotlib.use("Agg")
        import matplotlib.pyplot as plt
    except Exception:
        plt = None
    if outdir:
        outdir.mkdir(parents=True, exist_ok=True)

    # Background render worker (igg.vis.BackgroundRenderer — the round-5
    # pattern, now the library's shared in-situ helper): receives batches
    # of (step, device-resident mid-z slice), fetches them (one batched
    # ~10 MB transfer) and renders PNGs, all off the simulation thread.  maxsize bounds the outstanding dispatch depth (~30 x
    # 1,000-step programs): natural backpressure instead of a per-dispatch
    # sync.
    from igg.vis import BackgroundRenderer

    def render_batch(batch):
        import jax.numpy as jnp

        ks = [k for k, _ in batch]
        stack = np.asarray(jnp.stack([s for _, s in batch]))
        if plt is not None and outdir:
            for k, sl in zip(ks, stack):
                plt.imshow(sl.T, origin="lower", cmap="inferno")
                plt.title(f"T @ step {k}")
                plt.savefig(outdir / f"T_{k:06d}.png", dpi=60)
                plt.clf()

    renderer = BackgroundRenderer(render_batch, maxsize=3)

    t0 = time.monotonic()
    done = 0
    pending = []   # (step, device-resident mid-z slice)
    while done < steps:
        T = step(T, Cp)
        done += vis_every
        pending.append((done, T[:, :, T.shape[2] // 2]))
        if len(pending) >= 10:
            renderer.submit(pending)
            pending = []
    if pending:
        renderer.submit(pending)
    jax.block_until_ready(T)
    render_errors = renderer.close()   # the render drain is part of the wall-clock
    if render_errors:
        note(f"render worker errors: {render_errors[:3]}")
    # Final state export: one full-volume gather.
    G = igg.gather_interior(T)
    if G is not None and outdir:
        np.save(outdir / "T_final.npy", np.asarray(G[::4, ::4, ::4]))
    wall = time.monotonic() - t0

    emit({
        "metric": "headline_512cubed_100ksteps_wall_min",
        "value": round(wall / 60, 2),
        "unit": "min",
        "config": {"global": igg.nx_g(), "steps": done,
                   "vis_every": vis_every, "devices": grid.nprocs,
                   "dims": list(grid.dims), "platform": platform,
                   "use_pallas": use_pallas,
                   "vis_rendered": bool(plt is not None and outdir)},
        "reference_min": 29.0,
        "reference_grid": 510,
        "reference_hw": "8x NVIDIA Tesla P100",
        "vs_reference": round(29.0 * (done / 100_000) / (wall / 60), 2),
        "ms_per_step": round(wall / done * 1e3, 4),
        "xla_ms_per_step": round(xla_sec * 1e3, 4),
    })
    igg.finalize_global_grid()


if __name__ == "__main__":
    main()
