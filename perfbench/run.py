"""Run one benchmark cell once and print one JSON result line.

    python perfbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Places JAX's persistent compilation cache at `<checkout>/.jax_cache`,
fails without a TPU (no CPU fallback), builds the cell's grid and seeded
fields, warms the cell's own programs, runs the window and checks what it
produced against the plain reference.  `--trace 0` reports the cell's
end-to-end metrics, `--trace 1` its per-layer metrics from a short traced
window.  Information goes to standard error; the compared numbers with
their limits are its last lines.  The last line of standard output is the
result.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the profiler trace to this directory")
    args = ap.parse_args(argv)

    # A fixed path inside the checkout: the path is part of the cache key.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                           ".jax_cache")
    sys.path.insert(0, ROOT)
    from perfbench import harness

    result, lines = harness.run(args.workload, args.seed, args.seconds,
                                args.trace, t0=T0,
                                keep_trace=args.keep_trace)
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
