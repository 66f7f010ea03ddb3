"""A later PR adds a configuration, a traffic mix, a driver and a
per-layer metric by adding files and entries only: here one of each is
added in a temporary directory and run through the unchanged harness."""

import json
import textwrap

from benchmark import harness

from bench_testlib import ROOT, benchmark_json

DRIVER = '''
import time
import numpy as np


def open(config, traffic, options, devices, platform):
    return Host()


class Host:
    """The factorization on the host, tile by tile: not a device path,
    only a stand-in that shows where a new path plugs in."""
    solves = 0

    def solve(self, problem):
        n, nb = problem["n"], problem["nb"]
        A = np.zeros((n, n), np.float32)
        for (i, j), t in problem["tiles"].items():
            A[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb] = t
        t0 = time.perf_counter()
        L = np.linalg.cholesky((np.tril(A) + np.tril(A, -1).T)
                               .astype(np.float64)).astype(np.float32)
        t1 = time.perf_counter()
        self.solves += 1
        tiles = {(i, j): L[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb]
                 for (i, j) in problem["tiles"]}
        return {"times": {"host_solve_s": t1 - t0}, "result": tiles,
                "violations": [], "t_done": t1}

    def release(self, solve):
        pass

    def counters(self):
        return {"host_solves": self.solves}

    def close(self):
        pass
'''

READER = '''
"""layer: test.  source: the driver's counter.  moves: host_solve_s."""


def read(run):
    return run.per_solve("host_solves")
'''


def test_one_of_each_is_added_as_files_and_entries(tmp_path):
    extra = tmp_path / "later_pr"
    for sub in ("configs", "traffic", "drivers", "layers"):
        (extra / sub).mkdir(parents=True)
    with open(f"{ROOT}/benchmark/configs/spotrf_tile_nb512_1chip.json") as f:
        config = json.load(f)
    config.update(n=96, nb=32, limits={"factor_error": 1e-5})
    (extra / "configs" / "spotrf_host.json").write_text(json.dumps(config))
    (extra / "traffic" / "host_closed.json").write_text(json.dumps(
        {"driver": "host_numpy", "loop": "closed", "clients": 1,
         "warmup_solves": 2, "discard_solves": 0}))
    (extra / "drivers" / "host_numpy.py").write_text(
        textwrap.dedent(DRIVER))
    (extra / "layers" / "host_solves.py").write_text(
        textwrap.dedent(READER))

    spec = benchmark_json()
    before = json.dumps(spec, sort_keys=True)
    spec["paths"] = spec["paths"] + [str(extra)]
    spec["configs"].append({
        "name": "spotrf_host", "source": config["source"] + " (host)",
        "file": str(extra / "configs" / "spotrf_host.json"),
        "reduced": config["reduced"], "why": "a stand-in"})
    spec["workloads"].append({
        "name": "host_n96", "config": "spotrf_host",
        "traffic": "host_closed", "chips": 1, "why": "a stand-in"})
    spec["end_to_end"].append({
        "name": "host_solve_s", "unit": "s", "better": "lower",
        "bound": 0.05, "source": "host_clock", "workloads": ["host_n96"]})
    spec["per_layer"].append({
        "name": "host_solves", "unit": "solves", "better": "higher",
        "source": "program_counter", "layer": "test",
        "moves": "host_solve_s", "workloads": ["host_n96"]})

    cell = harness.load_cell(ROOT, "host_n96", spec)
    assert set(cell.readers) == {"host_solves", "setup_compiles"}
    r = harness.run_cell(ROOT, cell, 9, 0.3, False, platform="cpu",
                         paths=spec["paths"])
    assert r["correct"] is True and r["attempted"] >= 1
    assert set(r["metrics"]) == {"host_solve_s", "setup_s"}
    run = harness.Run(cell=cell, readings=[], counters={"host_solves": 6},
                      solves=6, compiles={}, memory={}, peaks=None)
    assert cell.readers["host_solves"].read(run) == 1.0
    # nothing the benchmark already had was edited
    del spec["configs"][-1], spec["workloads"][-1]
    del spec["end_to_end"][-1], spec["per_layer"][-1]
    spec["paths"] = spec["paths"][:-1]
    assert json.dumps(spec, sort_keys=True) == before
