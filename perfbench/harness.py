"""The generic harness: one run of one cell, driven by `BENCHMARK.json`.

Everything specific to a configuration, a traffic mix or a per-layer
metric is found by name:

- `perfbench/configs/<config>.json` (named by the config's `file`) holds
  the deployment, its `family` and the limits of its correctness check;
- `perfbench/traffic/<traffic>.json` holds the traffic parameters;
- `perfbench/families/<family>.py` builds the cell (`build`), makes the
  seeded fields, and holds the plain reference (`Checker`) and the
  faults that show the check catches a broken path (`faults`);
- `perfbench/metrics/<metric>.py` reads one per-layer metric (`read`).
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "perfbench")
TRACE_SECONDS = 2.0     # length of the traced window (--trace 1)
SAMPLES = ((0.0, 0.5), (0.5, 0.95))   # checked dispatches: window shares


def log(msg):
    print(f"[perfbench] {msg}", file=sys.stderr, flush=True)


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """A workload of BENCHMARK.json with its configuration, traffic,
    family module and metric entries resolved from files."""

    def __init__(self, name, bench=None):
        bench = bench or json.load(open(os.path.join(ROOT,
                                                     "BENCHMARK.json")))
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                             f"(have {sorted(cells)})")
        self.name, self.entry = name, cells[name]
        cfg = {c["name"]: c for c in bench["configs"]}[self.entry["config"]]
        self.config = json.load(open(os.path.join(ROOT, cfg["file"])))
        self.traffic = json.load(open(os.path.join(
            HERE, "traffic", self.entry["traffic"] + ".json")))
        self.chips = int(self.entry["chips"])
        self.family = load_module(
            os.path.join(HERE, "families", self.config["family"] + ".py"),
            "perfbench_family_" + self.config["family"])

        def mine(m):
            return name in m.get("workloads", [name])

        self.end_to_end = [m for m in bench["end_to_end"] if mine(m)]
        self.per_layer = [m for m in bench["per_layer"] if mine(m)]

    def reader(self, metric):
        return load_module(os.path.join(HERE, "metrics",
                                        metric["name"] + ".py"),
                           "perfbench_metric_" + metric["name"]).read


def require_accelerator(chips):
    """The chip the cell asks for, or exit without a result."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"needs a TPU; JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise SystemExit(f"needs {chips} chips; JAX found {len(devs)}")
    return devs


def peaks(kind):
    table = json.load(open(os.path.join(HERE, "peaks.json")))["devices"]
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r} in "
                         f"perfbench/peaks.json")
    return table[kind]


def annotate(on, name):
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation("perfbench." + name)


def window(case, T, Cp, seconds, traffic, seed, tiers, traced=False):
    """Dispatch until `seconds` have passed, at most `in_flight`
    dispatches in flight, then wait for the last.  A dispatch fails if it
    raises, if a tier outside `tiers` or other than the first serves it,
    or if any tier is quarantined.  Snapshots the input and output of the
    checked dispatches (device copies, taken at window shares drawn from
    the seed).  Returns a dict of counts, times, the final T and the
    samples."""
    n_inner = int(traffic["n_inner"])
    in_flight = int(traffic.get("in_flight", 2))
    frame_every = int(traffic.get("frame_every", 0))
    rng = np.random.default_rng(seed)
    due = sorted(rng.uniform(a, b) * seconds for a, b in SAMPLES)
    tier0 = case.served()[0]
    samples, pending, probes = [], None, []
    attempted = failed = steps = frames = 0
    error = None
    t_start = time.perf_counter()
    with annotate(traced, "window"):
        while True:
            now = time.perf_counter() - t_start
            if pending is not None:
                samples.append((pending, case.copy(T)))
                pending = None
            if due and now >= due[0]:
                due.pop(0)
                pending = case.copy(T)
            attempted += 1
            try:
                with annotate(traced, "dispatch"):
                    T = case.step(T, Cp)
            except Exception as e:   # counted, and the window ends
                failed += 1
                error = f"{type(e).__name__}: {e}"
                break
            steps += n_inner
            tier, quarantined = case.served()
            if tier != tier0 or tier not in tiers or quarantined:
                failed += 1
            probes.append(case.probe(T))
            if len(probes) >= in_flight:
                with annotate(traced, "sync"):
                    probes.pop(0).block_until_ready()
            if frame_every and steps % frame_every == 0:
                with annotate(traced, "frame"):
                    case.frame(T, traffic["frame_kind"])
                frames += 1
            if time.perf_counter() - t_start >= seconds:
                break
        if error is None:
            with annotate(traced, "sync"):
                T.block_until_ready()
    t_end = time.perf_counter()
    if pending is not None:
        samples.append((pending, T))
    return dict(T=T, samples=samples, attempted=attempted, failed=failed,
                steps=steps, dispatches=steps // n_inner, frames=frames,
                window_s=t_end - t_start, error=error, tier=tier0)


class CompileCounter:
    """Counts backend compiles while it is open (a compile inside the
    measured window is a fault of the warm-up)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __enter__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def _on(self, name, *args, **kw):
        if name == self.EVENT:
            self.n += 1

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)


def memory_peak(devices):
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use")
              for d in devices]
    peaks_ = [p for p in peaks_ if p is not None]
    return max(peaks_) if peaks_ else None


def setup(cell, config, traffic, devs, seed, *, interpret=False,
          step_wrapper=None):
    """Build the cell, make the seeded fields and warm every program the
    window runs: two dispatches of the step, the snapshot copy, the
    in-flight probe and an output frame.  Returns (case, T, Cp,
    first_dispatch_s)."""
    import jax

    marks = [("start", time.perf_counter())]
    case = cell.family.build(config, traffic, devs[:cell.chips],
                             interpret=interpret, log=log)
    if step_wrapper is not None:
        case.step = step_wrapper(case.step)
    marks.append(("build", time.perf_counter()))
    T, Cp = case.fields(seed)
    jax.block_until_ready((T, Cp))
    marks.append(("fields", time.perf_counter()))
    T = case.step(T, Cp)
    T.block_until_ready()
    marks.append(("first dispatch", time.perf_counter()))
    first_dispatch_s = marks[-1][1] - marks[-2][1]
    jax.block_until_ready((case.copy(T), case.probe(T)))
    if int(traffic.get("frame_every", 0)):
        case.frame(T, traffic["frame_kind"])
    T = case.step(T, Cp)
    T.block_until_ready()
    marks.append(("warm rest", time.perf_counter()))
    tier, quarantined = case.served()
    log(f"served tier {tier}; quarantined {dict(quarantined)}")
    log("set-up stages (s): " + ", ".join(
        f"{b[0]} {b[1] - a[1]:.4f}" for a, b in zip(marks, marks[1:])))
    return case, T, Cp, first_dispatch_s


def run(workload, seed, seconds, trace, *, t0, local_shape=None,
        n_inner=None, traffic=None, interpret=False, need_tpu=True,
        keep_trace=None, step_wrapper=None):
    """One run of one cell.  Returns (result dict, check lines).  The
    keyword hooks after `t0` serve the CPU rehearsal and the tests:
    smaller blocks, fewer steps per dispatch, another traffic mix,
    interpret-mode kernels, no look for a chip, and a wrapper that can
    break the timed path."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = Cell(workload)
    config, traffic = dict(cell.config), dict(traffic or cell.traffic)
    if local_shape is not None:
        config["local_shape"] = list(local_shape)
    if n_inner is not None:
        traffic["n_inner"] = n_inner
    devs = require_accelerator(cell.chips) if need_tpu else jax.devices()
    dev0 = devs[0]
    log(f"process start to devices: {time.perf_counter() - t0:.4f} s")
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": cell.chips}
    log(f"jax {jax.__version__}; {len(devs)} x {dev0.device_kind} "
        f"({dev0.platform}); cell {workload} on {cell.chips}")
    peak = peaks(dev0.device_kind) if need_tpu else None
    igg_env = {k: v for k, v in os.environ.items() if k.startswith("IGG_")}
    if igg_env:
        log(f"IGG_* environment: {igg_env}")

    case, T, Cp, first_dispatch_s = setup(
        cell, config, traffic, devs, seed, interpret=interpret,
        step_wrapper=step_wrapper)
    setup_s = time.perf_counter() - t0

    logdir = tempfile.mkdtemp(prefix="perfbench-trace-") if trace else None
    try:
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(logdir, profiler_options=opts)
        try:
            with CompileCounter() as compiles:
                w = window(case, T, Cp, min(seconds, TRACE_SECONDS)
                           if trace else seconds, traffic, seed,
                           config["tiers"], traced=bool(trace))
        finally:
            if trace:
                jax.profiler.stop_trace()
        del T
        log(f"window: {w['dispatches']} dispatches, {w['steps']} steps, "
            f"{w['frames']} frames, "
            f"{w['window_s']:.6f} s, tier {w['tier']}, attempted "
            f"{w['attempted']}, failed {w['failed']}, compiles "
            f"{compiles.n}"
            + (f", error {w['error']}" if w["error"] else ""))
        mem = memory_peak(devs[:cell.chips])
        metrics, extra = {}, {}
        if trace:
            from perfbench import trace as tr

            tz = tr.load(logdir)
            if keep_trace:
                shutil.copytree(logdir, keep_trace, dirs_exist_ok=True)
            lo, hi = tz.window()
            busy = [tr.length(v) for v in tr.per_device(tz).values()]
            ctx = dict(trace=tz, steps=w["steps"],
                       dispatches=w["dispatches"], chips=cell.chips,
                       compulsory_bytes=case.compulsory_bytes(), peak=peak,
                       first_dispatch_s=first_dispatch_s)
            for m in cell.per_layer:
                v = cell.reader(m)(ctx)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
            device.update(busy_s=float(np.mean(busy)) * 1e-9 if busy
                          else 0.0, window_s=(hi - lo) * 1e-9)
            extra["breakdown"] = tr.breakdown(tz)
        else:
            e2e = {"ms_per_step": w["window_s"] * 1e3 / max(w["steps"], 1),
                   "setup_s": setup_s}
            for m in cell.end_to_end:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
        device["memory_peak_bytes"] = mem
    finally:
        if logdir:
            shutil.rmtree(logdir, ignore_errors=True)

    samples = w.pop("samples")
    w.pop("T", None)
    case.close()
    del case
    t = time.perf_counter()
    # A dispatch that raised may have consumed its donated input: there is
    # then nothing sound to compare, and the run is not correct anyway.
    numbers = [] if w["error"] else cell.family.Checker(
        config, traffic, dev0).numbers(seed, samples)
    del samples
    log(f"reference check of {len(numbers)} dispatches: "
        f"{time.perf_counter() - t:.3f} s")
    limits = config["check"]
    checks = {}
    for name, limit in limits.items():
        vals = [n[name] for n in numbers if name in n]
        if vals:
            checks[name] = {"value": max(vals), "limit": limit}
    correct = (w["failed"] == 0 and bool(numbers)
               and all(c["value"] <= c["limit"] for c in checks.values()))
    result = {"correct": correct, "attempted": w["attempted"],
              "failed": w["failed"], "metrics": metrics, "device": device,
              **extra, "checks": checks}
    lines = [f"check {k}: {c['value']!r} limit {c['limit']!r}"
             for k, c in checks.items()]
    return result, lines
