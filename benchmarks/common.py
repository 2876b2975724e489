"""Shared benchmark machinery.

Timing convention: the unit of dispatch is ONE jitted program that advances
`n_inner` iterations via `lax.fori_loop` — per-call host dispatch cost
amortizes to zero, which is the TPU-idiomatic way to run a time loop (cf. `igg.models.diffusion3d.make_multi_step`).
Timings use the grid's barrier-synchronized chronometer (`igg.tic`/`igg.toc`),
the counterpart of the reference's MPI-barrier timers
(`/root/reference/src/tools.jl:228-234`).
"""

from __future__ import annotations

import json
import os
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

# Virtual host devices (`--xla_force_host_platform_device_count`) only exist
# on the CPU backend, and a TPU, where there is one, would win backend
# selection — pin CPU before any backend initializes (same reasoning as
# `__graft_entry__.py`).
if "xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", ""):
    import jax

    jax.config.update("jax_platforms", "cpu")

from igg import _env  # noqa: E402

_env.place_compile_cache()


import functools


@functools.lru_cache(maxsize=1)
def _commit() -> "str | None":
    import subprocess

    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], capture_output=True,
            text=True, cwd=str(pathlib.Path(__file__).resolve().parent),
        ).stdout.strip() or None
    except OSError:
        return None


@functools.lru_cache(maxsize=1)
def _toolchain() -> dict:
    """The environment half of the provenance header: jax/jaxlib versions,
    backend, device kind, process count.  Cached — the backend is queried
    once per benchmark process.  backend/device_kind come from
    `igg.perf.device_context` — the SAME source the perf-ledger keys and
    the `igg.perf compare` provenance matching use, so bench rows and
    ledger entries stay joinable by construction."""
    import jax

    from igg.perf import device_context

    try:
        import jaxlib

        jaxlib_version = getattr(jaxlib, "__version__", None)
    except ImportError:   # jaxlib folded into jax on some builds
        jaxlib_version = None
    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib_version,
        **device_context(),
        "processes": int(jax.process_count()),
    }


def provenance() -> dict:
    """Commit + timestamp + smoke flag + toolchain header stamped on every
    result line, so checked-in artifacts are traceable to the code AND the
    environment that produced them (rows become attributable:
    which jax/jaxlib, which backend, which device kind, how many
    processes, which git SHA).  `smoke: true` (the default on a virtual
    CPU mesh) marks a quick structural-validation run; a benchmark may
    override it for a full-quality measured run — the `platform` field
    inside each record's config still says where it ran, so CPU-mesh
    lines can never be mistaken for accelerator evidence.  Readers must
    stay backfill-tolerant: rows written before this header lack the
    `provenance` key (benchmarks/README.md, "Reading the provenance
    header")."""
    import jax

    return {
        "commit": _commit(),
        "ts": int(__import__("time").time()),
        "smoke": jax.devices()[0].platform == "cpu",
        "provenance": _toolchain(),
    }


def emit(record: dict, stream=sys.stdout) -> None:
    """One JSON line per result (the contract of the repo's `bench.py`),
    stamped with provenance (record-level keys win, see `provenance`).
    Multi-controller launches (one process per pod host): only process 0
    emits, so per-host stdout collection yields one row per measurement."""
    import jax

    if jax.process_index() != 0:
        return
    print(json.dumps({**provenance(), **record}), file=stream)
    stream.flush()


def median_of(fn, reps: int = 3):
    """Median of `reps` calls — min of a noisy estimator biases low, and a
    single host-clock measurement carries the host's own noise."""
    vals = sorted(fn() for _ in range(reps))
    return vals[len(vals) // 2]


def note(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr)
    sys.stderr.flush()


def time_dispatches(fn, args, *, nt: int, warmup: int = 1):
    """Seconds per dispatch of `fn(*args)`, slope-measured via
    `igg.time_steps` (two batch sizes; the constant dispatch/readback
    cost cancels in the slope; a plain tic/toc over `nt` dispatches would
    be inflated by that cost/nt).

    `fn` must map `args` to same-structured outputs (a time-steppable
    program); `nt` scales the batch sizes."""
    import igg

    n1 = max(1, nt)
    _, sec = igg.time_steps(fn, args, n1=n1, n2=3 * n1, warmup=warmup)
    return sec
