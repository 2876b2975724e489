"""Rehearse a cell's run on the CPU at a tiny size.

    python perfbench/rehearse.py --workload d3-256pc-periodic.mesh221
    python perfbench/rehearse.py --workload d3-512-open.steady \
        --traffic frames

Drives the whole harness (grid, seeded fields, the user's entry, the
window with its snapshots and in-flight bound, the reference check) with
interpret-mode kernels on as many virtual CPU devices as the cell asks
for, at a local block that the CPU can step.  It prints what the run
would compare and count, never a metrics line: a CPU run gives no times.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = (16, 16, 128)     # the smallest block the x-slab kernels accept


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=2**31 + 7)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--n-inner", type=int, default=10)
    ap.add_argument("--traffic", default=None,
                    help="run this traffic file instead of the cell's")
    ap.add_argument("--frame-kind", default=None,
                    help="gather or slice: override the traffic's frames")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")
    sys.path.insert(0, ROOT)
    from perfbench import harness

    traffic = None
    if args.traffic:
        traffic = json.load(open(os.path.join(harness.HERE, "traffic",
                                              args.traffic + ".json")))
        traffic["n_inner"] = args.n_inner
        traffic["frame_every"] = args.n_inner * 2 if traffic.get(
            "frame_every") else 0
        traffic["frame_kind"] = args.frame_kind or traffic["frame_kind"]
    t0 = time.perf_counter()
    result, lines = harness.run(
        args.workload, args.seed, args.seconds, args.trace, t0=t0,
        local_shape=TINY, n_inner=args.n_inner, interpret=True,
        need_tpu=False, traffic=traffic)
    for line in lines:
        print(line)
    print(f"rehearsal (CPU, no times): correct={result['correct']} "
          f"attempted={result['attempted']} failed={result['failed']} "
          f"metrics read: {sorted(result['metrics'])}")
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
