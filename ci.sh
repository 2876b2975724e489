#!/usr/bin/env bash
# Local CI runner — the same checks .github/workflows/ci.yml runs, executable
# anywhere (the driver, a dev box) without GitHub.  Mirrors the reference's
# CPU-only CI intent (`/root/reference/.github/workflows/ci.yml:1-42`) on the
# virtual 8-device CPU mesh, which exercises the real shard_map/ppermute
# multi-device programs.
set -euo pipefail
cd "$(dirname "$0")"

echo "=== test suite (virtual 8-device CPU mesh, incl. multihost subprocess"
echo "    test and interpret-mode Pallas tests) ==="
python -m pytest tests/ -x -q

echo "=== driver entry points (compile + 8-device dryrun) ==="
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python __graft_entry__.py

echo "=== benchmark harness smoke (--quick, CPU mesh; artifacts stamped"
echo "    smoke=true) + golden-baseline regression gate (round 13:"
echo "    python -m igg.perf compare vs benchmarks/goldens/ — presence +"
echo "    'pass' contract flags gate strictly, values within the"
echo "    CPU-noise tolerance) ==="
python benchmarks/run_all.py --quick --compare

# The smoke artifacts must carry one open-boundary chunk row (round 6 —
# the reference-default boundary condition on the K-step tier runs its
# window realization on CPU; pallas_sweep emits it unconditionally there).
if grep -q "trapezoid_open" benchmarks/results_smoke/pallas_sweep.jsonl; then
    echo "    open-boundary chunk smoke row PRESENT (pallas_sweep.jsonl)"
else
    echo "    open-boundary chunk smoke row MISSING from"
    echo "    benchmarks/results_smoke/pallas_sweep.jsonl"
    exit 1
fi

# Ditto for the Stokes K-iteration chunk tier (round 7): pallas_sweep
# emits its window-realization smoke row unconditionally on every
# platform (tests: tests/test_stokes_trapezoid.py — interpret-mode mesh
# equivalence, dispatch admission, banded-kernel-scheme simulation —
# plus tests/test_models.py::test_stokes_trapezoid_dispatch_admission).
if grep -q "stokes_trapezoid" benchmarks/results_smoke/pallas_sweep.jsonl; then
    echo "    Stokes chunk-tier smoke row PRESENT (pallas_sweep.jsonl)"
else
    echo "    Stokes chunk-tier smoke row MISSING from"
    echo "    benchmarks/results_smoke/pallas_sweep.jsonl"
    exit 1
fi

# Round 16: the two NEW chunk-engine rungs must emit their CPU-smoke
# CONTRACT rows ("pass" = tier output matches the XLA composition) on
# every platform — golden-gated via the pallas_sweep goldens in the
# run_all --compare above (GOLDEN_CONTRACT_ONLY keeps exactly these).
# Round 17 adds the SPEC-GENERATED rungs (igg.stencil): the spec-wave2d
# chunk tier gated against the HAND-WRITTEN module's composition (the
# frontend's bit-exactness contract) and the shallow-water family —
# zero hand-written kernel code — against its own generated XLA truth.
# Round 18 unpins the hm3d row to automatic dims (K=8 — the depth the
# (2,2,2) mesh's sublane-tile gate admits, now a structured Admission
# refusal at K=4 instead of a Mosaic crash).
for cfg in hm3d_trapezoid_open_interpret_K8 wave2d_mosaic_interpret \
        wave2d_chunk_interpret_K4 stencil_wave2d_chunk_interpret_K4 \
        shallow_water_mosaic_interpret shallow_water_chunk_interpret_K4; do
    if grep "\"config\": \"$cfg\"" \
            benchmarks/results_smoke/pallas_sweep.jsonl \
            | grep -q '"pass": true'; then
        echo "    $cfg smoke contract row PRESENT and passing"
    else
        echo "    $cfg smoke contract row MISSING or failing"
        echo "    (benchmarks/results_smoke/pallas_sweep.jsonl)"
        exit 1
    fi
done

# Round 8: the resilience tier.  The chaos suite (tests/test_resilience.py:
# NaN watchdog detection, rollback/retry bit-exactness, checkpoint ring
# fallback past truncated/bit-flipped generations, preemption + resume,
# halo-corruption seam, dist-init retry) ran inside the main pytest run
# above; here the watchdog-overhead contract row is asserted (< 2% vs the
# bare step loop at 128^3 with watch_every=50 — the row is emitted on every
# platform, CPU included).
if grep '"metric": "resilience_overhead"' \
        benchmarks/results_smoke/resilience_overhead.jsonl \
        | grep -q '"pass": true'; then
    echo "    resilience_overhead smoke row PRESENT and within the <2%"
    echo "    contract (resilience_overhead.jsonl)"
else
    echo "    resilience_overhead smoke row MISSING or overhead >= 2%"
    echo "    (benchmarks/results_smoke/resilience_overhead.jsonl)"
    exit 1
fi

# Round 9: the sharded-checkpoint tier.  The async writer must keep the
# hot-loop stall per ring generation under 10% of a sync sharded write
# (component row emitted by resilience_overhead.py on every platform).
if grep '"metric": "checkpoint_stall"' \
        benchmarks/results_smoke/resilience_overhead.jsonl \
        | grep -q '"pass": true'; then
    echo "    checkpoint_stall smoke row PRESENT and within the <10%"
    echo "    contract (resilience_overhead.jsonl)"
else
    echo "    checkpoint_stall smoke row MISSING or stall >= 10% of the"
    echo "    sync write (benchmarks/results_smoke/resilience_overhead.jsonl)"
    exit 1
fi

# Round 11: the ensemble tier.  The per-member watchdog (counts reduced
# over grid axes only — an (n_fields, M) probe attributing a blowup to
# its member on device) must keep the PR-3 overhead contract: < 2% over
# the bare vmapped member loop at watch_every=50 (fourth row of
# resilience_overhead.py, emitted on every platform).
if grep '"metric": "ensemble_overhead"' \
        benchmarks/results_smoke/resilience_overhead.jsonl \
        | grep -q '"pass": true'; then
    echo "    ensemble_overhead smoke row PRESENT and within the <2%"
    echo "    per-member watchdog contract (resilience_overhead.jsonl)"
else
    echo "    ensemble_overhead smoke row MISSING or overhead >= 2%"
    echo "    (benchmarks/results_smoke/resilience_overhead.jsonl)"
    exit 1
fi

# Round 11: the fleet tier's jobs/hour headline — the smoke contract is
# every submitted job done with zero quarantined members on the
# chaos-free queue (scheduler-owned costs included end to end).
if grep '"metric": "fleet_throughput"' \
        benchmarks/results_smoke/fleet_throughput.jsonl \
        | grep -q '"pass": true'; then
    echo "    fleet_throughput smoke row PRESENT (all jobs done, zero"
    echo "    quarantines; fleet_throughput.jsonl)"
else
    echo "    fleet_throughput smoke row MISSING or failed"
    echo "    (benchmarks/results_smoke/fleet_throughput.jsonl)"
    exit 1
fi

# Round 20: the fleet-as-a-service tier.  The chaos-churn contract row
# (serve_fleet under Poisson arrivals + a priority preempt + a member
# NaN + a fenced device + an arrival storm: every admitted job done,
# zero quarantined members, the storm shed, jobs/hour + p99 turnaround
# journal-derived and finite) runs on the virtual 8-device mesh and is
# golden-gated via benchmarks/goldens/fleet_churn.jsonl in the run_all
# --compare above.
if grep '"metric": "fleet_churn"' \
        benchmarks/results_smoke/fleet_churn.jsonl \
        | grep -q '"pass": true'; then
    echo "    fleet_churn smoke contract row PRESENT and passing"
    echo "    (fleet_churn.jsonl)"
else
    echo "    fleet_churn smoke contract row MISSING or failed"
    echo "    (benchmarks/results_smoke/fleet_churn.jsonl)"
    exit 1
fi

# Round 20: the churn golden must BITE — a flipped fleet_churn contract
# pass flag against the committed golden has to fail the gate (the
# run_all --compare above proves the green path; this proves the red
# one, same pattern as the round-14 comm golden proof).
echo "=== fleet-churn golden-gate proof (flipped contract pass flag must"
echo "    fail igg.perf compare) ==="
IGG_CHURN_GATE_TMP=$(mktemp -d)
sed 's/"pass": true/"pass": false/' benchmarks/goldens/fleet_churn.jsonl \
    > "$IGG_CHURN_GATE_TMP/new.jsonl"
if python -m igg.perf compare benchmarks/goldens/fleet_churn.jsonl \
        "$IGG_CHURN_GATE_TMP/new.jsonl" --tol 3.0; then
    echo "    fleet-churn golden gate FAILED to flag the flipped"
    echo "    contract row"
    rm -rf "$IGG_CHURN_GATE_TMP"
    exit 1
else
    echo "    fleet-churn golden gate correctly rejected the flipped"
    echo "    contract row"
fi
rm -rf "$IGG_CHURN_GATE_TMP"

# Round 12: the unified observability subsystem.  With an igg.telemetry
# session attached, run_resilient's hot loop pays one step_stats record +
# JSONL line per watch window and one counter increment per step — the
# contract is < 1% over the bare watchdog loop at 128^3 watch_every=50,
# with ZERO additional device->host syncs (the step stats ride the
# watchdog's existing async probe fetches; sentinel-asserted in
# tests/test_telemetry.py).  Fifth row of resilience_overhead.py,
# emitted on every platform.
if grep '"metric": "telemetry_overhead"' \
        benchmarks/results_smoke/resilience_overhead.jsonl \
        | grep -q '"pass": true'; then
    echo "    telemetry_overhead smoke row PRESENT and within the <1%"
    echo "    contract (resilience_overhead.jsonl)"
else
    echo "    telemetry_overhead smoke row MISSING or overhead >= 1%"
    echo "    (benchmarks/results_smoke/resilience_overhead.jsonl)"
    exit 1
fi

# Round 14: communication observability.  With comm observability
# enabled, run_resilient's hot loop pays one stall-heartbeat
# registration/retirement + one comm_stats record + two gauge sets per
# watch window — the contract is < 1% over the bare watchdog loop at
# 128^3 watch_every=50 with ZERO additional device->host syncs (the
# decomposition probes ride the loop's existing is_ready channel;
# sentinel-asserted in tests/test_telemetry.py).  Sixth row of
# resilience_overhead.py, emitted on every platform.
if grep '"metric": "comm_overhead"' \
        benchmarks/results_smoke/resilience_overhead.jsonl \
        | grep -q '"pass": true'; then
    echo "    comm_overhead smoke row PRESENT and within the <1%"
    echo "    contract (resilience_overhead.jsonl)"
else
    echo "    comm_overhead smoke row MISSING or overhead >= 1%"
    echo "    (benchmarks/results_smoke/resilience_overhead.jsonl)"
    exit 1
fi

# Round 15: the self-healing control plane.  With the heal engine
# attached and no fault present, run_resilient's hot loop pays one
# bus-subscriber detector call per watch window plus one pending-deque
# check per step — the contract is < 1% over the bare watchdog loop at
# 128^3 watch_every=50 with ZERO additional device->host syncs (actions
# are planned only on detections; sentinel-asserted in
# tests/test_telemetry.py with the engine enabled).  Seventh row of
# resilience_overhead.py, emitted on every platform and golden-gated
# like the other six (benchmarks/goldens/resilience_overhead.jsonl).
if grep '"metric": "heal_overhead"' \
        benchmarks/results_smoke/resilience_overhead.jsonl \
        | grep -q '"pass": true'; then
    echo "    heal_overhead smoke row PRESENT and within the <1%"
    echo "    contract (resilience_overhead.jsonl)"
else
    echo "    heal_overhead smoke row MISSING or overhead >= 1%"
    echo "    (benchmarks/results_smoke/resilience_overhead.jsonl)"
    exit 1
fi

# Round 18: the live ops plane.  With the statusd endpoint serving and a
# scraper attached, run_resilient's hot loop pays one health-tracker
# bus-subscriber callback per emitted record — the HTTP server, the HBM
# poller, and the multi-rank merge all run on statusd's own threads —
# the contract is < 1% over the bare watchdog loop at 128^3
# watch_every=50 with ZERO additional device->host syncs
# (sentinel-asserted in tests/test_telemetry.py with statusd enabled and
# a live scraper).  Eighth row of resilience_overhead.py, emitted on
# every platform and golden-gated like the other seven.
if grep '"metric": "statusd_overhead"' \
        benchmarks/results_smoke/resilience_overhead.jsonl \
        | grep -q '"pass": true'; then
    echo "    statusd_overhead smoke row PRESENT and within the <1%"
    echo "    contract (resilience_overhead.jsonl)"
else
    echo "    statusd_overhead smoke row MISSING or overhead >= 1%"
    echo "    (benchmarks/results_smoke/resilience_overhead.jsonl)"
    exit 1
fi

# Round 19: the numeric-integrity layer.  Invariant probes (owned-cell
# moment sums + per-rank partials) are FUSED into the watchdog probe —
# one vector, the same single async fetch — so the always-on layer must
# add < 1% over the bare watchdog loop at 128^3 watch_every=50 with
# ZERO additional device->host syncs (sentinel-asserted in
# tests/test_telemetry.py with integrity AND shadow checks enabled).
# Ninth row of resilience_overhead.py, emitted on every platform and
# golden-gated like the other eight.
if grep '"metric": "integrity_overhead"' \
        benchmarks/results_smoke/resilience_overhead.jsonl \
        | grep -q '"pass": true'; then
    echo "    integrity_overhead smoke row PRESENT and within the <1%"
    echo "    contract (resilience_overhead.jsonl)"
else
    echo "    integrity_overhead smoke row MISSING or overhead >= 1%"
    echo "    (benchmarks/results_smoke/resilience_overhead.jsonl)"
    exit 1
fi
if grep '"metric": "integrity_overhead"' \
        benchmarks/results_smoke/resilience_overhead.jsonl \
        | grep -q '"host_syncs_added": 0'; then
    echo "    integrity_overhead row carries host_syncs_added: 0"
else
    echo "    integrity_overhead row is MISSING host_syncs_added: 0"
    exit 1
fi

# Round 14: the halo-bandwidth byte-accounting golden must BITE — a
# flipped contract flag against the committed golden has to fail the
# gate (the goldens comparison in run_all --compare above proves the
# green path for the new comm goldens; this proves the red one).
echo "=== comm golden-gate proof (flipped halo_bytes_model_check pass"
echo "    flag must fail igg.perf compare) ==="
IGG_COMM_GATE_TMP=$(mktemp -d)
sed 's/"pass": true/"pass": false/' benchmarks/goldens/halo_bandwidth.jsonl \
    > "$IGG_COMM_GATE_TMP/new.jsonl"
if python -m igg.perf compare benchmarks/goldens/halo_bandwidth.jsonl \
        "$IGG_COMM_GATE_TMP/new.jsonl" --tol 3.0; then
    echo "    halo-bandwidth golden gate FAILED to flag the flipped"
    echo "    contract row"
    rm -rf "$IGG_COMM_GATE_TMP"
    exit 1
else
    echo "    halo-bandwidth golden gate correctly rejected the flipped"
    echo "    contract row"
fi
rm -rf "$IGG_COMM_GATE_TMP"

# The chunk-engine and spec-generated contract goldens must BITE too —
# flip every pass flag in the committed pallas_sweep contract-only
# goldens and the gate has to go red (the run_all --compare above proves
# the green path for the hm3d/wave2d/stencil/shallow-water rows; this
# proves a silently-failing chunk or generated tier cannot slip through).
echo "=== pallas_sweep golden-gate proof (flipped contract pass flags"
echo "    must fail igg.perf compare) ==="
IGG_SWEEP_GATE_TMP=$(mktemp -d)
sed 's/"pass": true/"pass": false/' benchmarks/goldens/pallas_sweep.jsonl \
    > "$IGG_SWEEP_GATE_TMP/new.jsonl"
if python -m igg.perf compare benchmarks/goldens/pallas_sweep.jsonl \
        "$IGG_SWEEP_GATE_TMP/new.jsonl" --tol 3.0; then
    echo "    pallas_sweep golden gate FAILED to flag the flipped"
    echo "    contract rows"
    rm -rf "$IGG_SWEEP_GATE_TMP"
    exit 1
else
    echo "    pallas_sweep golden gate correctly rejected the flipped"
    echo "    contract rows"
fi
rm -rf "$IGG_SWEEP_GATE_TMP"

# Round 10: the degradation ladder.  verify="first_use" is a one-time
# numeric check of each kernel tier against the pure-XLA truth; its cost
# must amortize to < 1% of a 1000-step run on the serving tier (third
# row of resilience_overhead.py, emitted on every platform).
if grep '"metric": "verify_first_use"' \
        benchmarks/results_smoke/resilience_overhead.jsonl \
        | grep -q '"pass": true'; then
    echo "    verify_first_use smoke row PRESENT and within the <1%"
    echo "    contract (resilience_overhead.jsonl)"
else
    echo "    verify_first_use smoke row MISSING or one-time check >= 1%"
    echo "    of a 1000-step run"
    echo "    (benchmarks/results_smoke/resilience_overhead.jsonl)"
    exit 1
fi

# Round 17: the stencil frontend end to end.  The shallow-water family
# is pure spec input (zero hand-written kernel code); the example runs
# the analyzer, serves a clean run from the GENERATED chunk tier, then
# chaos-miscompiles the generated Mosaic kernel under verify="first_use"
# inside run_resilient — the numeric check refuses the tier before it
# serves traffic, quarantines it, and the run completes BIT-EXACT on the
# generated XLA truth — and asserts the family is registered with
# igg.perf (analyzer-derived roofline bytes) and igg.autotune
# (candidate set) like any built-in.
echo "=== stencil frontend end to end (spec -> tiered dispatch ->"
echo "    chaos-corrupt generated kernel -> verify refusal -> bit-exact"
echo "    XLA fallback; 8-device CPU mesh) ==="
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/shallow_water.py

echo "=== resilient run loop end-to-end (watchdog -> rollback -> retry,"
echo "    preemption -> checkpoint -> resume; 8-device CPU mesh) ==="
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/resilient_run.py

echo "=== elastic checkpoints end-to-end (sharded save on the (2,2,2)"
echo "    8-device mesh -> bit-exact restore on (1,2,4) and on a 4-device"
echo "    mesh; run_resilient resume across topologies) ==="
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/elastic_resume.py

echo "=== degradation chaos smoke (compile-fail -> quarantine -> bit-exact"
echo "    fallback; corrupt kernel -> verify refusal; corrupt kernel ->"
echo "    run_resilient tier demotion; 8-device CPU mesh) ==="
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/degraded_run.py

echo "=== ensemble/fleet end to end (member NaN -> isolated per-member"
echo "    recovery -> job preempt -> journal -> elastic resume on 4 of 8"
echo "    devices, bit-identical to the uninterrupted fleet) ==="
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/fleet_run.py

# Round 20: fleet as a service end to end.  The scheduler loop owns the
# main thread while a driver thread plays two tenants over the REAL
# POST /jobs intake: online admission while a job runs, a priority-5
# arrival preempting the running job, an arrival storm + malformed body
# shed/rejected at the bounded queues (a late POST observes HTTP 429
# queue_saturated and /healthz pins the 503 readiness reason), a REAL
# SIGTERM drains to sealed generations + a sealed journal, and a
# resume=True relaunch re-admits everything from the journaled specs
# and finishes BIT-EXACT to an uninterrupted fleet — with the whole
# timeline order-asserted from the journal + events JSONL alone.
echo "=== fleet service end to end (POST /jobs two tenants -> priority"
echo "    preempt -> storm shed 429 -> SIGTERM drain -> resume bit-exact;"
echo "    timeline from journal + events JSONL; 8-device CPU mesh) ==="
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/fleet_service.py

echo "=== observability end to end (chaos NaN-corrupt kernel -> watchdog ->"
echo "    rollback -> tier demotion, full timeline reconstructed from the"
echo "    telemetry artifacts alone: ordered JSONL events + metrics"
echo "    snapshot + Prometheus file + span trace; ResilienceError ->"
echo "    flight-recorder auto-dump; python -m igg.telemetry merge) ==="
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/observed_run.py

# Round 18: the live ops plane end to end.  A run served by igg.statusd
# is scraped MID-RUN (/metrics with # HELP lines, /healthz ready,
# /status progress + serving tier), then a chaos collective stall flips
# /healthz to 503 naming collective_stall while the loop is wedged,
# readiness RECOVERS to 200 once the episode drains (same process, no
# restart), python -m igg.top renders the endpoint, and a clean
# shutdown releases the port — all asserted inside the example.
echo "=== live ops plane end to end (serve= -> mid-run scrape -> chaos"
echo "    stall -> readiness flips 503 -> recovers -> igg.top -> clean"
echo "    shutdown releases the port; 8-device CPU mesh) ==="
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/observed_service.py

echo "=== communication observability end to end (comm ledger calibration"
echo "    -> per-window step-time decomposition riding run_resilient ->"
echo "    chaos-injected collective stall: event + stall_r0.json report +"
echo "    flight dump; python -m igg.comm report; 8-device CPU mesh) ==="
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/comm_observed_run.py

# Round 15: the self-healing control plane end to end.  A chaos
# collective stall tied to one chip -> stall heartbeat -> heal engine
# seals a final generation, fences the chip, re-plans dims over the
# survivors, resumes elastically, and finishes BIT-EXACT to an
# uninterrupted run with zero operator recovery code; then a stale
# cost-model calibration -> cost_model_drift -> ledger invalidation ->
# recalibrated, the whole loop read back from the events JSONL alone —
# all asserted inside the example.
echo "=== self-healing control plane end to end (stall -> elastic re-tile"
echo "    bit-exact; drift -> recalibration from artifacts alone;"
echo "    8-device CPU mesh) ==="
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/self_healing_run.py

# Round 19: silent-data-corruption defense end to end.  A FINITE
# perturbation (the NaN watchdog provably silent — zero nan_detected
# events asserted) -> the fused invariant probe detects within one
# watch window with per-rank device attribution -> rollback prefers a
# DEEP-verified generation (a poisoned-but-finite generation is proven
# refused by verify_checkpoint(deep=True) while the structural scan
# serves it) -> the heal loop fences the attributed chip and re-tiles
# -> the run finishes BIT-EXACT to an uninterrupted reference, the
# whole timeline reconstructed from the events JSONL alone — all
# asserted inside the example.
echo "=== silent-data-corruption defense end to end (finite corruption ->"
echo "    invariant probe -> deep-verified rollback -> fence/re-tile ->"
echo "    bit-exact finish, from artifacts alone; 8-device CPU mesh) ==="
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/integrity_run.py

# Round 13: performance observability end to end.  A model-backed run on
# the 8-device mesh fills the perf ledger (watchdog windows attributed
# to the serving tier via igg.degrade.active(), a verify-first-use
# sample, an explicit igg.perf.calibrate), the ledger persists as
# versioned JSON, round-trips through the `python -m igg.perf
# show|merge` CLI, and igg.perf.best() answers for the served
# (family, tier, shape) — all asserted inside the example.  The PR-7
# zero-host-syncs sentinel ran with the ledger enabled in the pytest
# suite above.
echo "=== perf observability end to end (run -> ledger -> show/merge"
echo "    round-trip -> igg.perf.best; 8-device CPU mesh) ==="
IGG_PERF_TMP=$(mktemp -d)
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    IGG_PERF_LEDGER="$IGG_PERF_TMP/ledger.json" python examples/perf_run.py
rm -rf "$IGG_PERF_TMP"

# Round 13: prove the regression gate actually gates — a synthetic row
# 20% slower than its baseline twin must flip `igg.perf compare` to a
# nonzero exit at --tol 0.1 (the goldens comparison above proves the
# green path; this proves the red one).
echo "=== regression-gate proof (injected 20% slowdown row must fail"
echo "    igg.perf compare at --tol 0.1) ==="
IGG_GATE_TMP=$(mktemp -d)
cat > "$IGG_GATE_TMP/base.jsonl" <<'EOF'
{"metric": "gate_proof_ms", "value": 100.0, "unit": "ms", "smoke": true, "provenance": {"backend": "cpu", "device_kind": "cpu"}, "config": {"n": 64}}
EOF
cat > "$IGG_GATE_TMP/new.jsonl" <<'EOF'
{"metric": "gate_proof_ms", "value": 120.0, "unit": "ms", "smoke": true, "provenance": {"backend": "cpu", "device_kind": "cpu"}, "config": {"n": 64}}
EOF
if python -m igg.perf compare "$IGG_GATE_TMP/base.jsonl" \
        "$IGG_GATE_TMP/new.jsonl" --tol 0.1; then
    echo "    regression gate FAILED to flag the injected 20% slowdown"
    rm -rf "$IGG_GATE_TMP"
    exit 1
else
    echo "    regression gate correctly rejected the injected slowdown"
fi
rm -rf "$IGG_GATE_TMP"

# Round 16: autotuned dispatch end to end — cold search in one process
# (empty ledger seed -> (tier, K, bx) search -> winner <= the hand-picked
# bx=8 config -> tuning-cache write), then a SECOND process reads the
# cache and serves the winner with ZERO search dispatches, served config
# asserted (examples/tuned_run.py asserts all of it internally; the
# drift->invalidate->eviction leg is test-asserted in
# tests/test_autotune.py, which ran in the pytest suite above).
echo "=== autotuned dispatch end to end (cold search -> cache -> second"
echo "    process serves the winner with zero search dispatches) ==="
IGG_TUNE_TMP=$(mktemp -d)
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    IGG_TUNE_CACHE="$IGG_TUNE_TMP/tune.json" \
    IGG_PERF_LEDGER="$IGG_TUNE_TMP/ledger.json" \
    python examples/tuned_run.py cold
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    IGG_TUNE_CACHE="$IGG_TUNE_TMP/tune.json" \
    IGG_PERF_LEDGER="$IGG_TUNE_TMP/ledger.json" \
    python examples/tuned_run.py warm
rm -rf "$IGG_TUNE_TMP"

# Round 16 (overlap serving): the weak-scaling artifact must carry the
# always-on overlap correctness contract row — the
# hide_communication-restructured diffusion step bitwise-equal to the
# sequential compute+exchange composition on the full 8-device mesh
# (emitted by benchmarks/weak_scaling.py on every platform, CPU
# included; golden-gated via benchmarks/goldens/weak_scaling_mesh8.jsonl
# in the run_all --compare above).
if grep '"metric": "overlap_contract"' \
        benchmarks/results_smoke/weak_scaling_mesh8.jsonl \
        | grep -q '"pass": true'; then
    echo "    overlap_contract smoke row PRESENT and bitwise-equal"
    echo "    (weak_scaling_mesh8.jsonl)"
else
    echo "    overlap_contract smoke row MISSING or overlapped step"
    echo "    diverged from the sequential composition"
    echo "    (benchmarks/results_smoke/weak_scaling_mesh8.jsonl)"
    exit 1
fi

# Round 16: the overlap golden must BITE — a flipped overlap_contract
# pass flag against the committed weak-scaling golden has to fail the
# gate (the run_all --compare above proves the green path; this proves
# the red one, same pattern as the round-14 comm golden proof).
echo "=== overlap golden-gate proof (flipped overlap_contract pass flag"
echo "    must fail igg.perf compare) ==="
IGG_OVERLAP_GATE_TMP=$(mktemp -d)
sed 's/"pass": true/"pass": false/' \
    benchmarks/goldens/weak_scaling_mesh8.jsonl \
    > "$IGG_OVERLAP_GATE_TMP/new.jsonl"
if python -m igg.perf compare benchmarks/goldens/weak_scaling_mesh8.jsonl \
        "$IGG_OVERLAP_GATE_TMP/new.jsonl" --tol 3.0; then
    echo "    overlap golden gate FAILED to flag the flipped contract row"
    rm -rf "$IGG_OVERLAP_GATE_TMP"
    exit 1
else
    echo "    overlap golden gate correctly rejected the flipped"
    echo "    contract row"
fi
rm -rf "$IGG_OVERLAP_GATE_TMP"

# Round 16: the multi-process scaling harness.  The launcher spawns two
# REAL single-device CPU processes that form one logical grid via
# jax.distributed.initialize — a genuine cross-process halo exchange plus
# the seq-vs-overlapped bitwise contract — and prints MULTIPROC-OK, or
# "SKIP: ..." (exit 0) where the installed jaxlib's CPU backend has no
# cross-process collectives ("Multiprocess computations aren't
# implemented").  Either line is a pass; a crash or silence is not —
# a wedged worker cannot read as a green harness.
echo "=== multi-process scaling harness smoke (2 real processes, or a"
echo "    clean SKIP where the CPU backend lacks cross-process"
echo "    collectives) ==="
python tests/multiproc/launcher.py 2 | tee /tmp/igg_multiproc.log
if grep -qE "MULTIPROC-OK|SKIP: " /tmp/igg_multiproc.log; then
    echo "    multiproc harness smoke PASSED (ran or skipped cleanly)"
else
    echo "    multiproc harness smoke produced neither MULTIPROC-OK nor"
    echo "    a clean SKIP (/tmp/igg_multiproc.log)"
    exit 1
fi

# Compiled-mode TPU kernel tests (VERDICT r3 weak item 4): run
# unconditionally — the tests' own per-test gate (the single source of
# TPU detection) skips them cleanly on chipless hosts, and the summary
# line below states plainly whether they RAN or SKIPPED, so a silently
# skipping chip cannot read as a green kernel suite.  The file includes
# the round-6 open-boundary chunk tests
# (test_trapezoid_open_modes_match_per_step_kernel,
# test_trapezoid_oext_kernel_matches_window) and the round-7 Stokes
# chunk-tier test (test_stokes_trapezoid_matches_per_iteration —
# compiled VMEM-resident banded kernel vs the per-iteration fused
# kernel, periodic and open).
echo "=== compiled-mode TPU kernel tests incl. open-boundary chunks"
echo "    (skip cleanly without a chip) ==="
IGG_TPU_TESTS=1 python -m pytest tests/test_mega_tpu.py -q -rs \
    | tee /tmp/igg_tpu_tests.log
if grep -qE "[0-9]+ passed" /tmp/igg_tpu_tests.log; then
    echo "    TPU kernel tests RAN (see above for counts)"
else
    echo "    TPU kernel tests SKIPPED (no usable chip; run on the driver"
    echo "    via bench.py / IGG_TPU_TESTS=1 on TPU hardware)"
fi

echo "CI PASS"
