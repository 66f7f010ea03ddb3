#!/usr/bin/env python3
"""``python3 benchmark/band_f32.py --workload W --n 16384 --nb 2048,512
--bands 1,2,4,8 --seeds 1,2,3 [--beta 0.03]``

How ``band_f32`` of the mixed-precision likelihood configuration was
fixed: at a size where the dense float64 likelihood is affordable (the
reference's ``dense_loglik``, ``numpy.linalg.cholesky``) the program's
``loglik`` is read for every band asked for, through the cell's own
driver, beside the numbers the cell's check compares.  The benchmark's
own runs never run this.  Prints one JSON line a (nb, seed, band) and a
last one with them all.  Runs on the chip only.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--n", type=int, default=16384)
    ap.add_argument("--nb", default="2048")
    ap.add_argument("--bands", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--beta", type=float, default=None)
    ap.add_argument("--dense", type=int, default=1,
                    help="0: no float64 likelihood (a size where it is "
                         "not affordable): the bands beside each other")
    ap.add_argument("--platform", default="tpu")
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".parsec_tpu_cache"))
    sys.path[0] = ROOT
    from benchmark import harness

    try:
        cell = harness.load_cell(ROOT, args.workload)
        devices = harness.look_for_devices(cell, args.platform)
    except harness.BenchError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    out = []
    for nb in (int(v) for v in args.nb.split(",")):
        config = dict(cell.config, n=args.n, nb=nb)
        if args.beta is not None:
            config["theta"] = [config["theta"][0], args.beta, 0.5]
        for seed in (int(s) for s in args.seeds.split(",")):
            problem = cell.reference.make_problem(seed, config, cell.traffic,
                                                  devices[:1])
            want = cell.reference.dense_loglik(
                problem, problem["theta"](0)) if args.dense else dict(
                    loglik=float("nan"), logdet=None, dot=None)
            for band in (int(b) for b in args.bands.split(",")):
                problem["band_f32"] = band
                cell.reference.prepare(problem)
                drv = cell.driver.open(config, cell.traffic,
                                       {"band_f32": band}, devices[:1],
                                       args.platform)
                try:
                    s = drv.solve(problem)
                    numbers = cell.reference.compare(problem, s["result"])
                    got = s["result"]["loglik"]
                    drv.release(s)
                finally:
                    drv.close()
                line = dict(n=args.n, nb=nb, seed=seed, band_f32=band,
                            theta=config["theta"], loglik=got,
                            float64=want["loglik"],
                            relative=abs(got - want["loglik"])
                            / abs(want["loglik"]),
                            logdet=[s["result"]["logdet"], want["logdet"]],
                            dot=[s["result"]["dot"], want["dot"]],
                            numbers=numbers, violations=s["violations"],
                            solve_s=s["times"]["tile_solve_s"])
                out.append(line)
                harness.log("band " + json.dumps(line))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
