"""The unified observability subsystem (igg/telemetry.py) and its
round-12 satellites: the event bus + flight recorder, the metrics
registry + Prometheus exposition, session JSONL/trace artifacts, the
multihost merge tool, the chaos-proven post-mortem timeline (the
acceptance contract: one failure reconstructed from the artifacts
ALONE), the zero-additional-host-syncs sentinel, `igg.profiling.trace`
hardening, and the `igg.timing.time_steps` slope-method math."""

import json
import pathlib
import time
import warnings

import numpy as np
import pytest

import igg
from igg import telemetry as tel


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Metrics, the flight-recorder ring, and sessions are process-global
    (by design — they outlive grids); isolate every test.  The ring clear
    matters in the full suite: by the time this file runs, hundreds of
    earlier tests have filled the ring to its maxlen, where an append
    evicts instead of growing."""
    tel.reset_metrics()
    tel._ring().clear()
    yield
    for s in list(tel._SESSIONS):
        s.detach()
    tel.reset_metrics()


# ---------------------------------------------------------------------------
# Harness (the test_resilience mini-step: deterministic, 6^3 on the
# (2,2,2) mesh)
# ---------------------------------------------------------------------------

def _grid(**kw):
    args = dict(periodx=1, periody=1, periodz=1, quiet=True)
    args.update(kw)
    igg.init_global_grid(6, 6, 6, **args)


def _make_step():
    from igg.ops import interior_add

    @igg.sharded
    def step(T):
        lap = (T[:-2, 1:-1, 1:-1] + T[2:, 1:-1, 1:-1]
               + T[1:-1, :-2, 1:-1] + T[1:-1, 2:, 1:-1]
               + T[1:-1, 1:-1, :-2] + T[1:-1, 1:-1, 2:]
               - 6.0 * T[1:-1, 1:-1, 1:-1])
        return igg.update_halo_local(interior_add(T, 0.1 * lap))

    return lambda st: {"T": step(st["T"])}


def _init_state(seed=3):
    rng = np.random.default_rng(seed)
    T = igg.from_local_blocks(lambda c, ls: rng.standard_normal(ls),
                              (6, 6, 6))
    return {"T": igg.update_halo(T)}


# ---------------------------------------------------------------------------
# (i) metrics registry
# ---------------------------------------------------------------------------

def test_metrics_registry_types_and_snapshot():
    c = tel.counter("igg_t_total")
    c.inc()
    c.inc(2.5)
    tel.gauge("igg_t_gauge").set(-4.0)
    h = tel.histogram("igg_t_hist")
    for v in (0.5, 1.5, 1.0):
        h.observe(v)
    snap = tel.snapshot()
    assert snap["igg_t_total"] == {"type": "counter", "value": 3.5}
    assert snap["igg_t_gauge"]["value"] == -4.0
    assert snap["igg_t_hist"] == {"type": "histogram", "count": 3,
                                  "sum": 3.0, "min": 0.5, "max": 1.5}
    # Same (name, labels) -> the same instance; labels key distinct series.
    assert tel.counter("igg_t_total") is c
    tel.counter("igg_t_total", tier="a").inc()
    assert tel.snapshot()['igg_t_total{tier="a"}']["value"] == 1.0
    # One name, one type.
    with pytest.raises(igg.GridError, match="one name, one type"):
        tel.gauge("igg_t_total")
    # Counters refuse to go backwards.
    with pytest.raises(igg.GridError, match="negative"):
        c.inc(-1)


def test_prometheus_exposition_format():
    tel.counter("igg_p_total", job="x").inc(2)
    tel.gauge("igg_p_depth").set(7)
    tel.histogram("igg_p_lat").observe(0.25)
    text = tel.prometheus_text()
    assert "# TYPE igg_p_total counter" in text
    assert 'igg_p_total{job="x"} 2.0' in text
    assert "# TYPE igg_p_depth gauge" in text and "igg_p_depth 7.0" in text
    assert "# TYPE igg_p_lat summary" in text
    assert "igg_p_lat_count 1" in text and "igg_p_lat_sum 0.25" in text
    # Every non-comment line is "name{...} value" — parseable exposition.
    for line in text.splitlines():
        if line.startswith("#") or not line:
            continue
        name, value = line.rsplit(" ", 1)
        float(value)
        assert name[0].isalpha()


# ---------------------------------------------------------------------------
# (ii) event bus, flight recorder, sessions
# ---------------------------------------------------------------------------

def test_emit_lands_in_flight_recorder_ring():
    n0 = len(tel.flight_recorder())
    rec = tel.emit("unit_test_event", step=12, foo="bar")
    ring = tel.flight_recorder()
    assert len(ring) == n0 + 1 and ring[-1] is rec
    assert rec.kind == "unit_test_event" and rec.step == 12
    assert rec.payload == {"foo": "bar"}
    assert rec.wall > 0 and rec.t > 0 and rec.process == 0


def test_flight_recorder_dump_and_ring_bound(tmp_path):
    ring_max = tel._ring().maxlen
    for i in range(ring_max + 10):
        tel.emit("flood", step=i)
    assert len(tel.flight_recorder()) == ring_max   # bounded
    out = tel.dump_flight_recorder("unit test", tmp_path / "f.json")
    assert out == [tmp_path / "f.json"]
    doc = json.loads((tmp_path / "f.json").read_text())
    assert doc["reason"] == "unit test"
    assert len(doc["events"]) == ring_max
    assert doc["events"][-1]["kind"] == "flood"


def test_session_writes_jsonl_metrics_and_valid_chrome_trace(tmp_path):
    with tel.Telemetry(tmp_path) as t:
        tel.emit("alpha", step=1, a=1)
        with tel.span("region", step=2, tag="x"):
            time.sleep(0.001)
        tel.counter("igg_s_total").inc()
    lines = [json.loads(l) for l in
             (tmp_path / "events_r0.jsonl").read_text().splitlines()]
    assert [l["kind"] for l in lines] == ["alpha", "span"]
    assert lines[1]["payload"]["name"] == "region"
    assert lines[1]["payload"]["dur_s"] >= 0.001
    snap = json.loads((tmp_path / "metrics_r0.jsonl").read_text()
                      .splitlines()[-1])
    assert snap["metrics"]["igg_s_total"]["value"] == 1.0
    assert "igg_s_total 1.0" in (tmp_path / "metrics_r0.prom").read_text()
    # The span export is VALID Chrome-trace JSON: an object with a
    # traceEvents list of complete ("ph": "X") events carrying numeric
    # ts/dur — what Perfetto/chrome://tracing requires.
    doc = json.loads((tmp_path / "trace_r0.json").read_text())
    assert isinstance(doc["traceEvents"], list) and doc["traceEvents"]
    ev = doc["traceEvents"][0]
    assert ev["ph"] == "X" and ev["name"] == "region"
    assert isinstance(ev["ts"], float) and isinstance(ev["dur"], float)
    assert ev["args"]["tag"] == "x"
    assert not t.attached


def test_span_capture_disabled_by_env_knob(monkeypatch):
    monkeypatch.setenv("IGG_TELEMETRY_SPANS", "0")
    n0 = len(tel.flight_recorder())
    with tel.span("invisible"):
        pass
    assert len(tel.flight_recorder()) == n0


def test_as_session_coercions(tmp_path, monkeypatch):
    assert tel.as_session(None) is None
    assert tel.as_session(False) is None
    s = tel.as_session(tmp_path / "x")
    assert isinstance(s, tel.Telemetry) and not s.attached
    assert tel.as_session(s) is s
    with pytest.raises(igg.GridError, match="IGG_TELEMETRY_DIR"):
        tel.as_session(True)
    monkeypatch.setenv("IGG_TELEMETRY_DIR", str(tmp_path / "env"))
    auto = tel.as_session(None)
    assert isinstance(auto, tel.Telemetry)
    assert auto.dir == tmp_path / "env"
    assert tel.as_session(False) is None     # explicit off beats the env
    with pytest.raises(igg.GridError, match="telemetry="):
        tel.as_session(123)


def test_telemetry_env_knobs_registered():
    from igg import _env

    for name in ("IGG_TELEMETRY_DIR", "IGG_TELEMETRY_FLIGHT_RECORDER",
                 "IGG_TELEMETRY_METRICS_EVERY", "IGG_TELEMETRY_SPANS"):
        assert name in _env._KNOWN, name


# ---------------------------------------------------------------------------
# (iii) the merge tool
# ---------------------------------------------------------------------------

def _fake_stream(path, process, walls, kinds):
    with open(path, "w") as fh:
        for w, k in zip(walls, kinds):
            fh.write(json.dumps({"t": w, "wall": w, "process": process,
                                 "kind": k, "step": None,
                                 "payload": {}}) + "\n")


def test_merge_orders_rank_streams_by_wall(tmp_path):
    _fake_stream(tmp_path / "events_r0.jsonl", 0, [1.0, 3.0, 5.0],
                 ["a0", "b0", "c0"])
    _fake_stream(tmp_path / "events_r1.jsonl", 1, [2.0, 4.0],
                 ["a1", "b1"])
    merged = tel.merge_streams([tmp_path], tmp_path / "merged.jsonl")
    assert [r["kind"] for r in merged] == ["a0", "a1", "b0", "b1", "c0"]
    on_disk = [json.loads(l) for l in
               (tmp_path / "merged.jsonl").read_text().splitlines()]
    assert on_disk == merged
    # A half-written line (killed process) is skipped, not fatal, and
    # accounted in the trailing summary record.
    (tmp_path / "events_r1.jsonl").open("a").write('{"wall": 9')
    merged2 = tel.merge_streams([tmp_path])
    assert merged2[-1]["kind"] == "merge_summary"
    assert merged2[-1]["payload"]["skipped_lines"] == 1
    (tmp_path / "empty").mkdir()
    with pytest.raises(igg.GridError, match="no event files"):
        tel.merge_streams([tmp_path / "empty"])


def test_merge_cli_entry_point(tmp_path):
    """The `python -m igg.telemetry merge` entry point, driven through
    `_main` in-process (the subprocess form of the same invocation is
    exercised end to end by examples/observed_run.py in ci.sh — spawning
    two fresh interpreters here would re-import jax for nothing)."""
    _fake_stream(tmp_path / "events_r0.jsonl", 0, [1.0, 2.0], ["x", "y"])
    rc = tel._main(["merge", str(tmp_path / "m.jsonl"), str(tmp_path)])
    assert rc == 0
    assert len((tmp_path / "m.jsonl").read_text().splitlines()) == 2
    assert tel._main([]) == 2                       # usage
    assert tel._main(["merge", "out"]) == 2         # missing inputs


# ---------------------------------------------------------------------------
# (iv) the acceptance contract: one chaos-injected failure, the full
# timeline from the telemetry artifacts ALONE
# ---------------------------------------------------------------------------

def test_failure_timeline_from_artifacts_alone(tmp_path):
    """NaN-corrupt kernel under run_resilient: the artifacts (events
    JSONL + metrics snapshot + flight dump) alone yield the NaN detection
    step, the rollback target generation, the retry count, and the
    serving-tier change — with `RunResult.events` / `igg.degrade.events()`
    preserved as compatible views."""
    from igg.models import diffusion3d as d3

    igg.init_global_grid(8, 8, 128, periodx=1, periody=1, periodz=1,
                         quiet=True)
    igg.degrade.reset()   # BEFORE the factory: reset clears ladder state
    params = d3.Params()
    T0, Cp = d3.init_fields(params, dtype=np.float32)
    step = d3.make_step(params, donate=False, pallas_interpret=True)
    tdir = tmp_path / "telemetry"
    ckdir = tmp_path / "ring"
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with igg.chaos.kernel_corrupt("diffusion3d.mosaic"):
            res = igg.run_resilient(
                lambda s: {"T": step(s["T"], Cp)}, {"T": T0 + 0}, 30,
                watch_every=10, checkpoint_dir=ckdir, checkpoint_every=10,
                async_checkpoint=False, telemetry=tdir)
    assert res.steps_done == 30

    # -- the timeline, from the JSONL stream alone --
    recs = [json.loads(l) for l in
            (tdir / "events_r0.jsonl").read_text().splitlines()]
    kinds = [r["kind"] for r in recs]
    i_nan = kinds.index("nan_detected")
    i_rb = kinds.index("rollback")
    i_deg = kinds.index("tier_degraded")
    assert i_nan < i_rb < i_deg                      # the story, in order
    nan_step = recs[i_nan]["step"]
    assert nan_step == 10                            # first watch window
    assert recs[i_nan]["payload"]["counts"]["T"] > 0
    rb = recs[i_rb]
    assert rb["payload"]["path"] == str(ckdir / "ckpt_000000000")
    assert rb["payload"]["attempt"] == 1             # the retry count
    deg = recs[i_deg]["payload"]
    assert deg["tier"] == "diffusion3d.mosaic"
    assert deg["reason"] == "nan_recurrence"
    # Timestamps are monotone within the stream and rank-tagged.
    ts = [r["t"] for r in recs]
    assert ts == sorted(ts)
    assert all(r["process"] == 0 for r in recs)
    # run bracket events frame the stream.
    assert kinds[0] == "run_started" and kinds[-1] == "run_finished"

    # -- the metrics snapshot corroborates the counts --
    snap = json.loads((tdir / "metrics_r0.jsonl").read_text()
                      .splitlines()[-1])["metrics"]
    # Two rollbacks: the first burns retry 1; the recurrence takes the
    # demotion rung (no retry burned) and replays from the same target.
    assert snap['igg_rollbacks_total{run="resilient"}']["value"] \
        == float(kinds.count("rollback")) == 2.0
    assert snap['igg_tier_quarantined_total'
                '{tier="diffusion3d.mosaic"}']["value"] == 1.0
    assert snap["igg_checkpoint_bytes_total"]["value"] > 0
    hist = snap['igg_checkpoint_write_seconds{format="sharded"}']
    assert hist["count"] >= 3 and hist["sum"] > 0
    dispatch = [k for k in snap if k.startswith("igg_tier_dispatch_total")]
    assert any('tier="diffusion3d.xla"' in k for k in dispatch)

    # -- compat views preserved: the per-run list still carries the same
    # incidents (without the bus-only step_stats/span/run-bracket noise) --
    run_kinds = [e.kind for e in res.events]
    assert {"nan_detected", "rollback", "tier_degraded"} <= set(run_kinds)
    assert run_kinds.index("nan_detected") \
        < run_kinds.index("tier_degraded")
    assert not {"step_stats", "span", "run_started"} & set(run_kinds)
    assert any(e["kind"] == "tier_degraded"
               for e in igg.degrade.events())
    igg.degrade.reset()


def test_resilience_error_auto_dumps_flight_recorder(tmp_path):
    _grid()
    step_fn = _make_step()
    plan = igg.chaos.ChaosPlan(nan_at=[(3, "T")])
    with pytest.raises(igg.ResilienceError):
        igg.run_resilient(step_fn, _init_state(), 10, watch_every=5,
                          telemetry=tmp_path, chaos=plan)
    dumps = tel.flight_dumps(tmp_path, rank=0)
    assert dumps, list(tmp_path.iterdir())
    dump = json.loads(dumps[0].read_text())
    assert "ResilienceError" in dump["reason"]
    assert any(r["kind"] == "nan_detected" for r in dump["events"])


# ---------------------------------------------------------------------------
# (v) the zero-additional-host-syncs sentinel
# ---------------------------------------------------------------------------

def test_telemetry_adds_zero_host_syncs(tmp_path, monkeypatch):
    """The dispatch-count/sentinel pattern: count every device-array
    materialization the loop performs (`np.asarray` on jax arrays — the
    only fetch primitive `run_resilient` uses) with telemetry OFF and
    with a session attached.  The counts must be IDENTICAL: step stats
    ride the watchdog's existing probe fetches."""
    from igg import resilience as res_mod

    _grid()
    step_fn = _make_step()
    real_asarray = np.asarray
    fetches = []

    def counting_asarray(x, *a, **kw):
        if hasattr(x, "is_ready"):           # a jax.Array — a device fetch
            fetches.append(type(x).__name__)
        return real_asarray(x, *a, **kw)

    def run(telemetry, comm=None, heal=None, serve=False, integrity=False):
        fetches.clear()
        igg.run_resilient(step_fn, _init_state(), 20, watch_every=5,
                          telemetry=telemetry, comm=comm, heal=heal,
                          serve=serve, integrity=integrity,
                          install_sigterm=False)
        return len(fetches)

    monkeypatch.setattr(res_mod, "np", type(np)("np_proxy"))
    for attr in dir(np):
        try:
            setattr(res_mod.np, attr, getattr(np, attr))
        except (AttributeError, TypeError):
            pass
    res_mod.np.asarray = counting_asarray

    bare = run(telemetry=False)
    observed = run(telemetry=tmp_path)
    assert bare > 0                      # the probes ARE being fetched
    assert observed == bare              # ...and telemetry added none
    # Round 13: with the perf ledger enabled too (IGG_PERF_LEDGER set, a
    # session attached), the watchdog-window attribution is host-side
    # ladder bookkeeping riding the SAME fetches — still zero
    # additional device-array materializations.
    monkeypatch.setenv("IGG_PERF_LEDGER",
                       str(tmp_path / "perf" / "ledger.json"))
    with_perf = run(telemetry=tmp_path / "session2")
    assert with_perf == bare
    # Round 14: with COMM observability enabled too — the stall
    # heartbeat watching every probe and a StepDecomposition monitor
    # dispatching its variant probes at the watch cadence — the
    # decomposition is observed entirely through is_ready polling
    # (never materialized), so the device-array fetch counts are STILL
    # identical.
    from igg import comm as icomm

    def compute(T):
        from igg.ops import interior_add

        lap = (T[:-2, 1:-1, 1:-1] + T[2:, 1:-1, 1:-1]
               + T[1:-1, :-2, 1:-1] + T[1:-1, 2:, 1:-1]
               + T[1:-1, 1:-1, :-2] + T[1:-1, 1:-1, 2:]
               - 6.0 * T[1:-1, 1:-1, 1:-1])
        return interior_add(T, 0.1 * lap)

    monkeypatch.setenv("IGG_COMM_STALL_TIMEOUT", "60")
    monitor = icomm.StepDecomposition(compute, (_init_state()["T"],),
                                      reps=2)
    with_comm = run(telemetry=tmp_path / "session3", comm=monitor)
    assert with_comm == bare
    # Round 15: with the HEAL ENGINE enabled too — the detection half is
    # a bus-subscriber callback, the action half a pending-deque check
    # per iteration; with no fault present neither touches a device, so
    # the fetch counts are STILL identical.
    from igg import heal as iheal

    engine = iheal.HealEngine(iheal.HealPolicy(), run="resilient")
    with_heal = run(telemetry=tmp_path / "session4", heal=engine)
    assert with_heal == bare
    assert engine.actions == [] and not engine.has_pending()
    # Round 18: with the STATUSD live endpoint enabled too — the health
    # tracker is a bus-subscriber callback, the HTTP server and the HBM
    # poller (device.memory_stats is a host-side allocator lookup) live
    # entirely on statusd's own threads — the fetch counts are STILL
    # identical, with a scraper hitting the endpoint mid-run.
    import json as _json
    import threading as _threading
    import urllib.request

    from igg import statusd as istatusd

    srv = istatusd.StatusServer(port=0, hbm_every=0.0).start()
    stop_scrape = _threading.Event()

    def scrape():
        while not stop_scrape.wait(0.02):
            try:
                urllib.request.urlopen(srv.url + "/metrics", timeout=2)
                urllib.request.urlopen(srv.url + "/healthz", timeout=2)
            except Exception:
                continue

    scraper = _threading.Thread(target=scrape, daemon=True)
    scraper.start()
    try:
        with_statusd = run(telemetry=tmp_path / "session5", serve=srv)
    finally:
        stop_scrape.set()
        scraper.join(timeout=5)
    assert with_statusd == bare
    body = urllib.request.urlopen(srv.url + "/status", timeout=2).read()
    assert _json.loads(body)["runs"]["resilient"]["finished"] is True
    srv.stop()
    # Round 19: with the INTEGRITY layer enabled too — invariant probes
    # AND shadow re-execution checks at every window (check_every=1).
    # The invariant moment sums and per-rank partials are FUSED into the
    # watchdog probe (one concatenated vector per window), and the
    # shadow truth replay is pure extra dispatch work whose comparison
    # rides the same vector — the device-array fetch counts are STILL
    # identical.
    from igg import integrity as iintegrity

    cfg = iintegrity.IntegrityConfig(
        invariants=[iintegrity.Invariant("probe_sum", ("T",), moment=1,
                                         kind="conserved", tol=1.0)],
        check_every=1)
    with_integrity = run(telemetry=tmp_path / "session6", integrity=cfg)
    assert with_integrity == bare


# ---------------------------------------------------------------------------
# (vi) ensemble + fleet wiring
# ---------------------------------------------------------------------------

def test_ensemble_emits_member_rates_and_unified_events(tmp_path):
    from helpers import ensemble_member_step, ensemble_states

    _grid()
    states = ensemble_states(4)
    res = igg.run_ensemble(ensemble_member_step(), states, 20,
                           watch_every=5, telemetry=tmp_path / "t",
                           install_sigterm=False)
    assert res.steps_done == 20
    recs = [json.loads(l) for l in
            (tmp_path / "t" / "events_r0.jsonl").read_text().splitlines()]
    started = [r for r in recs if r["kind"] == "run_started"]
    assert started and started[0]["payload"]["run"] == "ensemble"
    assert started[0]["payload"]["members"] == 4
    stats = [r for r in recs if r["kind"] == "step_stats"]
    assert stats, [r["kind"] for r in recs]
    assert stats[-1]["payload"]["members_active"] == 4
    assert stats[-1]["payload"]["member_steps_per_s"] == pytest.approx(
        4 * stats[-1]["payload"]["steps_per_s"])
    snap = tel.snapshot()
    assert snap["igg_member_steps_total"]["value"] == 4 * 20


def test_fleet_emits_job_lifecycle_and_queue_depth(tmp_path):
    from helpers import ensemble_member_step, ensemble_states

    jobs = [igg.Job(name="ja", global_interior=(8, 8, 8), members=2,
                    n_steps=4, watch_every=2, checkpoint_every=2,
                    make_states=lambda grid: ensemble_states(2),
                    step_fn=ensemble_member_step())]
    res = igg.run_fleet(jobs, tmp_path / "w", telemetry=tmp_path / "t",
                        install_sigterm=False)
    assert all(o.status == "done" for o in res.jobs.values())
    recs = [json.loads(l) for l in
            (tmp_path / "t" / "events_r0.jsonl").read_text().splitlines()]
    kinds = [r["kind"] for r in recs]
    assert "job_started" in kinds and "job_done" in kinds
    spans = [r for r in recs if r["kind"] == "span"
             and r["payload"]["name"] == "fleet.job"]
    assert len(spans) == len(jobs)
    snap = tel.snapshot()
    assert snap['igg_fleet_jobs_total{status="done"}']["value"] == len(jobs)
    assert snap["igg_fleet_queue_depth"]["value"] == 0.0


# ---------------------------------------------------------------------------
# (vii) satellites: profiling hardening
# ---------------------------------------------------------------------------

def test_profiling_trace_creates_missing_parents_and_rejects_nesting(
        tmp_path):
    _grid()
    deep = tmp_path / "a" / "b" / "c"              # parents do not exist
    T = igg.zeros((6, 6, 6))
    with igg.profiling.trace(str(deep)) as logdir:
        with pytest.raises(igg.GridError, match="do not nest"):
            with igg.profiling.trace(str(tmp_path / "other")):
                pass
        T = igg.update_halo(T)
        assert pathlib.Path(logdir).is_dir()
    # Re-entrancy state cleared: a new trace works after the first closed.
    with igg.profiling.trace(str(tmp_path / "second")):
        pass
    kinds = [r.kind for r in tel.flight_recorder()]
    assert kinds.count("trace_started") >= 2
    assert kinds.count("trace_stopped") >= 2


def test_profiling_trace_cleans_up_on_start_failure(tmp_path, monkeypatch):
    import jax

    def boom(logdir):
        raise RuntimeError("profiler unavailable")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    with pytest.raises(RuntimeError, match="profiler unavailable"):
        with igg.profiling.trace(str(tmp_path / "x")):
            pass
    # The guard is released: the failure did not wedge future traces.
    monkeypatch.undo()
    with igg.profiling.trace(str(tmp_path / "y")):
        pass


# ---------------------------------------------------------------------------
# (viii) satellites: igg.timing.time_steps
# ---------------------------------------------------------------------------

def test_time_steps_slope_cancels_constant_latency():
    """Synthetic constant-latency step: each call costs `c` seconds; the
    slope (T2-T1)/(n2-n1) must recover `c` even though every batch also
    pays the constant sync/readback the slope is designed to cancel."""
    c = 0.003
    calls = []

    def step(x):
        calls.append(1)
        time.sleep(c)
        return x

    state, sec = igg.time_steps(step, (np.float32(1.0),), n1=3, n2=9,
                                warmup=1)
    assert len(calls) == 1 + 3 + 9                  # deterministic count
    # The slope can only overshoot by sleep()'s scheduler overshoot (a
    # loaded CI host), never undershoot below the programmed latency.
    assert 0.8 * c <= sec <= 5 * c
    assert isinstance(state, tuple)


def test_time_steps_validates_batch_sizes():
    step = lambda x: x
    with pytest.raises(ValueError, match="n2 > n1"):
        igg.time_steps(step, (np.float32(0),), n1=5, n2=5)
    with pytest.raises(ValueError, match="n2 > n1"):
        igg.time_steps(step, (np.float32(0),), n1=8, n2=3)


def test_time_steps_single_element_state_normalization():
    """A bare (non-tuple) state is wrapped, and a step returning a single
    array (not a 1-tuple) keeps working — the documented 1-element
    convenience forms."""
    seen = []

    def step(x):
        seen.append(type(x))
        return x + 1

    state, sec = igg.time_steps(step, np.float64(0.0), n1=2, n2=4,
                                warmup=0)
    assert isinstance(state, tuple) and len(state) == 1
    assert state[0] == 2 + 4                        # every call applied
    assert all(t is not tuple for t in seen)        # elements, not tuples
    assert sec >= 0.0
