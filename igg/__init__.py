"""igg — Implicit Global Grid, TPU-native.

A brand-new JAX/XLA framework with the capabilities of
`ImplicitGlobalGrid.jl` (reference at `/root/reference`): distributed
stencil-based simulations on regular staggered grids, where a solver written
for a single device's local `(nx, ny, nz)` array runs unchanged on an
implicitly-assembled global grid over a whole TPU slice.

Instead of a Cartesian MPI topology with CUDA-aware point-to-point halo
messages, the grid is a `jax.sharding.Mesh` whose axes are the grid
dimensions; halo updates are XLA collective-permutes over ICI fused with their
pack/unpack slices, and whole time steps compile to single SPMD programs whose
communication XLA overlaps with interior compute.

Public API (reference: the 13 exported symbols at
`/root/reference/src/ImplicitGlobalGrid.jl:10-22`):

    init_global_grid, finalize_global_grid, update_halo, gather,
    select_device, nx_g, ny_g, nz_g, x_g, y_g, z_g, tic, toc

plus TPU-native extensions: field constructors (`zeros`, `ones`, `full`),
coordinate fields (`x_g_field`, ..., `coord_fields`), whole-step SPMD
compilation (`sharded`, `update_halo_local`, `local_coords`),
`gather_interior`, checkpointing (`save_checkpoint`, `load_checkpoint`,
`latest_checkpoint`, `verify_checkpoint`), the resilient run loop
(`run_resilient` — device-side NaN watchdog, checkpoint ring with
rollback-and-retry, preemption handling; fault injectors in `igg.chaos`),
the verified tier-degradation ladder (`igg.degrade` — kernel
quarantine with compile-failure capture, numeric verify-on-first-use
against the pure-XLA composition truth, observable/resettable status),
the ensemble/fleet tier (`igg.run_ensemble` — M independent members
in one compiled program with per-member fault isolation and quarantine;
`igg.run_fleet` — a job queue drained onto whatever devices exist, with
retry/backoff, a persistent journal, and elastic resume), and the unified
observability subsystem (`igg.telemetry` — one timestamped, rank-tagged
event bus with a flight-recorder ring, a metrics registry with
Prometheus exposition, zero-sync device-side step stats, and Chrome-trace
spans; docs/observability.md), and the performance-observability layer
(`igg.perf` — a persistent per-(family, tier, shape, dtype, topology)
perf ledger feeding the future autotuner, live roofline and
cost-model-drift gauges, and the `python -m igg.perf compare` benchmark
regression gate), and the communication-observability layer (`igg.comm`
— the comm ledger + ICI roofline gauges, per-window step-time
decomposition with exposed-comm fraction and overlap efficiency,
per-rank skew, and the collective-stall heartbeat that turns hung
collectives into structured artifacts), and the self-healing control
plane (`igg.heal` — a policy engine subscribed to the event bus that
closes the detection→action loops: stall/straggler → elastic re-tile,
cost-model drift → re-calibration, lagging fleet job → repack, all
budget/hysteresis-governed and chaos-proven), and the live ops plane
(`igg.statusd` — an always-on HTTP endpoint serving `/metrics`,
`/healthz`, `/status`, and `/events` with live HBM gauges and
multi-rank aggregation, wired via the `serve=` knob on the run loops;
`python -m igg.top` renders it as a terminal dashboard), and the
numeric-integrity layer (`igg.integrity` — silent-data-corruption
defense: family-declared invariant probes and shadow re-execution
checks fused into the watchdog's single async fetch, per-rank device
attribution, deep-verified checkpoint rollback via
`verify_checkpoint(deep=True)`, and the heal loop's
fence-the-suspect-device re-tile — all chaos-provable with
`igg.chaos.silent_corruption`/`poison_checkpoint`).
"""

from .shared import (
    AXIS_NAMES,
    NDIMS,
    NNEIGHBORS_PER_DIM,
    PROC_NULL,
    GlobalGrid,
    GridError,
    get_global_grid,
    grid_is_initialized,
)
from .init import init_global_grid
from .finalize import finalize_global_grid
from .halo import update_halo, update_halo_local
from .gather import gather, gather_interior
from .device import select_device
from .tools import (
    barrier,
    coord_fields,
    nx_g,
    ny_g,
    nz_g,
    tic,
    toc,
    x_g,
    x_g_field,
    y_g,
    y_g_field,
    z_g,
    z_g_field,
)
from .fields import (
    from_local_blocks,
    full,
    local_block,
    local_blocks,
    ones,
    spec_for,
    sharding_for,
    stacked_shape,
    zeros,
)
from .overlap import hide_communication
from .parallel import local_coords, sharded
from .checkpoint import (
    latest_checkpoint,
    load_checkpoint,
    save_checkpoint,
    save_checkpoint_sharded,
    verify_checkpoint,
    verify_checkpoint_distributed,
)
from .resilience import ResilienceError, RunResult, run_resilient
from .ensemble import EnsembleResult, run_ensemble
from .fleet import FleetResult, Job, JobOutcome, run_fleet
from .serve import ServeControl, ServeResult, serve_fleet
from .timing import time_steps
from . import autotune
from . import chaos
from . import comm
from . import degrade
from . import device
from . import ensemble
from . import fleet
from . import heal
from . import integrity
from . import perf
from . import profiling
from . import resilience
from . import serve
from . import statusd
from . import stencil
from . import telemetry
from . import tools
from . import vis
from .telemetry import Telemetry

__version__ = "0.1.0"

__all__ = [
    "AXIS_NAMES", "NDIMS", "NNEIGHBORS_PER_DIM", "PROC_NULL",
    "GlobalGrid", "GridError", "get_global_grid", "grid_is_initialized",
    "init_global_grid", "finalize_global_grid",
    "update_halo", "update_halo_local",
    "gather", "gather_interior",
    "select_device",
    "nx_g", "ny_g", "nz_g", "x_g", "y_g", "z_g",
    "x_g_field", "y_g_field", "z_g_field", "coord_fields",
    "tic", "toc", "barrier",
    "zeros", "ones", "full", "from_local_blocks", "local_blocks",
    "local_block", "spec_for", "sharding_for", "stacked_shape",
    "hide_communication", "local_coords", "sharded", "profiling",
    "save_checkpoint", "save_checkpoint_sharded", "load_checkpoint",
    "latest_checkpoint", "verify_checkpoint", "verify_checkpoint_distributed",
    "run_resilient", "RunResult", "ResilienceError", "resilience", "chaos",
    "degrade", "vis",
    "run_ensemble", "EnsembleResult", "ensemble",
    "run_fleet", "Job", "JobOutcome", "FleetResult", "fleet",
    "serve_fleet", "ServeControl", "ServeResult", "serve",
    "telemetry", "Telemetry", "perf", "comm", "heal", "integrity",
    "autotune",
    "statusd", "stencil", "time_steps", "__version__",
]
