"""``BENCHMARK.json`` against the contract, every cell's files found by
name, and the refusals: an unknown ``device_kind``, a run without a TPU,
a run alone in a directory."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmark import harness

from bench_testlib import ROOT, benchmark_json

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WORKLOADS = [w["name"] for w in benchmark_json()["workloads"]]


def test_benchmark_json_keeps_the_contract():
    spec = benchmark_json()
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= spec["run_seconds"] <= 51
    cells = 24  # what later PRs may grow it to
    assert (2 + 14 * cells) * (spec["run_seconds"] + 60) + cells * 180 \
        + 1200 <= 43200
    for p in spec["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    assert any(w.startswith(spec["paths"][0] + "/")
               for w in spec["command"])
    names = [c["name"] for c in spec["configs"]]
    assert len(set(names)) == len(names)
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["source"]) <= 200
        assert any(c["file"].startswith(p + "/") for p in spec["paths"])
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert all(k in cfg for k in c["reduced"])
        assert set(cfg["limits"]) and "control" in cfg
    four = sum(w["chips"] == 4 for w in spec["workloads"])
    assert four <= max(1, len(spec["workloads"]) // 2)
    pairs = [(w["config"], w["traffic"]) for w in spec["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in names and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
    assert {c["name"] for c in spec["configs"]} == \
        {w["config"] for w in spec["workloads"]}
    metrics = spec["end_to_end"] + spec["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", WORKLOADS)) <= set(WORKLOADS)
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert "setup_s" in {m["name"] for m in spec["end_to_end"]}
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        if "roofline" in m["name"]:
            assert m["name"].split(".")[0].endswith("_roofline")
            assert m["unit"] == "%"
    assert len(json.dumps(spec)) < 64 * 1024


@pytest.mark.parametrize("workload", WORKLOADS)
def test_every_file_of_a_cell_resolves_by_name(workload):
    cell = harness.load_cell(ROOT, workload)
    assert callable(cell.driver.open)
    for fn in ("make_problem", "prepare", "compare"):
        assert callable(getattr(cell.reference, fn))
    assert set(cell.readers) == {m["name"] for m in cell.per_layer}
    assert cell.traffic["loop"] == "closed" and cell.traffic["clients"] == 1
    assert cell.traffic["warmup_solves"] >= 2


@pytest.mark.parametrize("extra, message", [
    ({"n": 4096}, "belongs to the configuration"),
    ({"nb": 256}, "belongs to the configuration"),
    ({"precision": "bfloat16"}, "belongs to the configuration"),
    ({"warmup_solves": 1}, "2 or more whole solves")])
def test_a_traffic_file_sets_no_size_and_warms_up_twice(tmp_path, extra,
                                                        message):
    spec = benchmark_json()
    with open(harness.find_file(ROOT, spec["paths"],
                                "traffic/pump_n8192.json")) as f:
        traffic = dict(json.load(f), **extra)
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "pump_n8192.json").write_text(
        json.dumps(traffic))
    spec["paths"] = [str(tmp_path)] + spec["paths"]
    with pytest.raises(harness.BenchError, match=message):
        harness.load_cell(ROOT, "tile_pump_n8192", spec)


def test_a_split_quantity_shares_its_reader():
    spec = benchmark_json()
    assert harness.find_reader(
        ROOT, spec["paths"], "device_idle_pct.panel") == harness.find_reader(
        ROOT, spec["paths"], "device_idle_pct")
    with pytest.raises(harness.BenchError, match="no file"):
        harness.find_reader(ROOT, spec["paths"], "nothing.panel")


def test_an_unknown_workload_or_file_is_an_error():
    with pytest.raises(harness.BenchError, match="no workload"):
        harness.load_cell(ROOT, "nope")
    with pytest.raises(harness.BenchError, match="no file"):
        harness.find_file(ROOT, ["benchmark"], "traffic/nope.json")


def test_an_unknown_device_kind_is_an_error():
    assert harness.load_peaks(ROOT, ["benchmark"], "TPU v5 lite")[
        "bf16_flops_per_s"] == 197e12
    with pytest.raises(harness.BenchError, match="not in peaks.json"):
        harness.load_peaks(ROOT, ["benchmark"], "TPU v9 imaginary")


def _run(cwd, *args):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "JAX_COMPILATION_CACHE_DIR")}
    return subprocess.run(
        [sys.executable, "benchmark/run.py", *args], cwd=cwd,
        env=dict(env, JAX_PLATFORMS="cpu"), capture_output=True, text=True,
        timeout=300)


ARGS = ("--workload", "tile_pump_n8192", "--seed", "1", "--seconds", "1",
        "--trace", "0")


def test_a_real_cell_refuses_to_run_off_the_chip():
    p = _run(ROOT, *ARGS)
    assert p.returncode != 0 and '"correct"' not in p.stdout
    assert "runs on 'tpu' only" in p.stderr


def test_alone_in_a_directory_it_fails_and_prints_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in benchmark_json()["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, *ARGS)
    assert p.returncode != 0 and '"correct"' not in p.stdout
