#!/usr/bin/env python3
"""``python3 benchmark/run.py --workload W --seed N --seconds S --trace 0|1``

One process that holds the cell's chips, runs one cell of
``BENCHMARK.json`` and prints one JSON result as its last stdout line.
It refuses to run off the chip: without a TPU, with fewer chips than the
cell asks for, or with a ``device_kind`` missing from ``peaks.json`` it
exits non-zero and prints no result.
"""

import time

T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # the program keeps XLA's persistent cache, its executable store and
    # its tuning store under this one directory; a fixed path inside the
    # checkout, because the path is part of XLA's cache key
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".parsec_tpu_cache"))
    # the script's own directory (``trace/`` would shadow the stdlib
    # module) gives way to the checkout, where ``parsec_tpu`` lives
    sys.path[0] = ROOT
    from benchmark import harness

    return harness.main(ROOT, args.workload, args.seed, args.seconds,
                        bool(args.trace), t_process=T_PROCESS)


if __name__ == "__main__":
    sys.exit(main())
