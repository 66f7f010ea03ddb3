#!/usr/bin/env python3
"""``python3 benchmark/control.py --workload W --seeds 1,2,3 [--solves 3]``

Reads, in one process, the numbers a cell's check compares: for every
seed the program's (``sound``) and the control's, which is the program
with the configuration's lower-precision path switched on
(``control.options`` in the configuration's file).  A limit is set from
these two readings: above the largest sound number, below the smallest
control number.  The benchmark's own runs never run this.

Prints one JSON object: ``{"sound": {seed: {number: worst}}, "control":
{...}, "limits": {...}}``.  Runs on the chip only.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read_numbers(cell, devices, seeds, solves, *, control, platform="tpu"):
    from benchmark import harness

    session = harness.Session(cell, devices, platform, control=control)
    out = {}
    try:
        for seed in seeds:
            problem = cell.reference.make_problem(
                seed, cell.config, cell.traffic, devices[:cell.chips])
            cell.reference.prepare(problem)
            worst = {}
            for _ in range(solves):
                s = session.solve(problem)
                harness.keep_worst(worst, s["numbers"])
                if s["violations"]:
                    worst["violations"] = s["violations"]
            out[str(seed)] = worst
            harness.log(f"{'control' if control else 'sound'} seed {seed}: "
                        f"{worst}")
    finally:
        session.close()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default=None)
    ap.add_argument("--solves", type=int, default=2)
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".parsec_tpu_cache"))
    sys.path[0] = ROOT
    from benchmark import harness

    seeds = [int(s) for s in args.seeds.split(",")]
    cseeds = [int(s) for s in (args.control_seeds or args.seeds).split(",")]
    try:
        cell = harness.load_cell(ROOT, args.workload)
        devices = harness.look_for_devices(cell, "tpu")
    except harness.BenchError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    out = {"workload": args.workload, "limits": cell.config["limits"],
           "sound": read_numbers(cell, devices, seeds, args.solves,
                                 control=False),
           "control": read_numbers(cell, devices, cseeds, args.solves,
                                   control=True)}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
