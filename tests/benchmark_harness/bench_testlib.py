"""Shared by the benchmark harness's tests: the repository's own
``BENCHMARK.json`` with every cell's traffic swapped for the tiny mix of
the same driver under ``traffic/`` here, and the configuration's sizes for
that mix's ``rehearsal`` sizes.  A rehearsal is by size only: the same
drivers, references and harness loop, on the CPU backend."""

import copy
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY = {"pump": "tiny_pump", "context": "tiny_context",
        "segmented": "tiny_segmented", "mesh2x2": "tiny_mesh2x2"}


def benchmark_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def tiny_spec():
    """``BENCHMARK.json`` with each cell on its driver's tiny traffic."""
    from benchmark import harness

    spec = copy.deepcopy(benchmark_json())
    for w in spec["workloads"]:
        with open(harness.find_file(ROOT, spec["paths"],
                                    f"traffic/{w['traffic']}.json")) as f:
            w["traffic"] = TINY[json.load(f)["driver"]]
    return spec


def tiny_cell(workload):
    from benchmark import harness

    cell = harness.load_cell(ROOT, workload, tiny_spec())
    cell.config.update(cell.traffic["rehearsal"])
    return cell
