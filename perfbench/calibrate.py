"""Readings that the limits of `correct` are set from, on the chip.

    python perfbench/calibrate.py --workload <name> --seeds 12

In one process, at the cell's own size and through the window's own
entry: for each seed, fresh fields, one dispatch, then one checked
dispatch (its input and output snapshotted as the window does).  For each
seed it prints the numbers compared for

- the program (the lower reading is their largest),
- the control: the reference in the nearest precision below the
  configuration's, in the program's place (the upper reading is its
  smallest),
- each fault the cell can have (`faults` of the family): the timed path
  broken on purpose.

The benchmark's own runs never run this.  Writes the table as JSON lines
to `--out` when given.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=4_000_000_000)
    ap.add_argument("--out", default=None)
    ap.add_argument("--cpu", action="store_true",
                    help="tiny size on CPU devices (for the tests)")
    args = ap.parse_args(argv)

    if not args.cpu:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
            ROOT, ".jax_cache")
    sys.path.insert(0, ROOT)
    import jax

    from perfbench import harness

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.Cell(args.workload)
    config, traffic = dict(cell.config), dict(cell.traffic)
    if args.cpu:
        config["local_shape"] = [16, 16, 128]
        traffic["n_inner"] = 10
        devs = jax.devices()
    else:
        devs = harness.require_accelerator(cell.chips)
    fam = cell.family
    case, T, Cp, _ = harness.setup(cell, config, traffic, devs,
                                   args.first_seed, interpret=args.cpu)
    del T, Cp
    checker = fam.Checker(config, traffic, devs[0])
    faults = fam.faults(config, traffic)
    rows = []
    out = open(args.out, "w") if args.out else None
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t = time.perf_counter()
        T, Cp = case.fields(seed)
        T = case.step(T, Cp)
        before = case.copy(T)
        T = case.step(T, Cp)
        tier, quarantined = case.served()
        row = {"seed": seed, "tier": tier, "quarantined": len(quarantined),
               "program": checker.numbers(seed, [(before, T)])[0],
               "control": checker.numbers(seed, [(before, T)],
                                          control=True)[0]}
        for name, wrap in faults.items():
            bad = wrap(case.step)(case.copy(before), Cp)
            row[name] = checker.numbers(seed, [(before, bad)])[0]
            del bad
        row["seconds"] = time.perf_counter() - t
        del T, Cp, before
        rows.append(row)
        print(json.dumps(row), flush=True)
        if out:
            out.write(json.dumps(row) + "\n")
    case.close()
    if out:
        out.close()
    for key in ("program", "control", *faults):
        for num in rows[0][key]:
            vals = [r[key][num] for r in rows]
            print(f"{key:12s} {num:12s} min {min(vals)!r} max {max(vals)!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
