"""``donated_outputs_pct`` (PR 41): the entry of ``BENCHMARK.json``, its
reader under ``benchmark/layers/``, and what the reader reads: the trace
recorded on a v5e before the spans carried ``don`` (nothing), that trace
with ``don`` planted on its task spans, and the tiny pump and stencil
solves traced here on the CPU backend (counts, never times)."""

import os
import shutil
import types

import jax
import pytest

from benchmark import harness
from benchmark.trace import spans as sp
from parsec_tpu import native

from bench_testlib import ROOT, benchmark_json, tiny_cell

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "recorded", "tiny_pump_spans.xplane.pb")
METRIC = "donated_outputs_pct"


def _reader():
    return harness.load_module(
        harness.find_reader(ROOT, benchmark_json()["paths"], METRIC))


def _run(cell_name, traced=True):
    cell = types.SimpleNamespace(name=cell_name, chips=1)
    return types.SimpleNamespace(cell=cell, trace=object() if traced else None)


def _trace_at(tmp_path, monkeypatch, xplane, cell="a_cell"):
    """``xplane`` where a traced run of ``cell`` leaves its own."""
    monkeypatch.setattr(sp, "ROOT", str(tmp_path))
    d = tmp_path / ".bench_trace" / cell / "plugins" / "profile" / "x"
    d.mkdir(parents=True)
    shutil.copy(xplane, d / "host.xplane.pb")
    return _run(cell)


def test_the_entry_and_its_reader():
    spec = benchmark_json()
    entry = next(m for m in spec["per_layer"] if m["name"] == METRIC)
    assert entry == {
        "name": METRIC, "unit": "%", "better": "higher",
        "source": "program_span", "layer": "device",
        "moves": "tile_solve_s",
        "workloads": ["tile_pump_n8192", "geqrf_pump_n16384",
                      "ooc_pump_n90112", "stencil_pump_n32768",
                      "mle_pump_n90112", "dtd_potrf_nb1024"]}
    # every cell it lists reports the end-to-end metric it moves
    moved = next(m for m in spec["end_to_end"]
                 if m["name"] == "tile_solve_s")
    assert set(entry["workloads"]) <= set(moved["workloads"])
    path = harness.find_reader(ROOT, spec["paths"], METRIC)
    assert path.endswith(f"layers/{METRIC}.py")
    reader = harness.load_module(path)
    assert reader.read(_run("a_cell", traced=False)) is None
    assert reader.read(_run("no_such_cell_was_ever_traced")) is None


def test_a_program_whose_spans_carry_no_don_gives_nothing(tmp_path,
                                                          monkeypatch):
    run = _trace_at(tmp_path, monkeypatch, RECORDED)
    assert sp.of_run(run) is not None      # the spans are there
    assert _reader().read(run) is None     # ``don`` is not


@pytest.mark.parametrize("don_of, expected", [
    (lambda n: 2 * n, 100.0),   # every output over its input
    (lambda n: n, 50.0),        # one of a task's two
    (lambda n: 0, 0.0),         # every output NEW: the stencil's cell
])
def test_the_share_is_don_over_outs_of_the_task_spans(
        tmp_path, monkeypatch, don_of, expected):
    run = _trace_at(tmp_path, monkeypatch, RECORDED)
    load = sp.load

    def planted(path):
        trace = load(path)
        for s in trace.spans:
            if s.name in sp.TASK_SPANS:
                n = int(s.args.get("n", 1))
                s.args.update(outs=2 * n, don=don_of(n))
        return trace
    monkeypatch.setattr(sp, "load", planted)
    assert _reader().read(run) == pytest.approx(expected)


@pytest.mark.skipif(not native.available(), reason="needs the native core")
@pytest.mark.parametrize("workload, expected", [
    ("tile_pump_n8192", 100.0),      # every dpotrf task rewrites a tile
    ("stencil_pump_n32768", 0.0),    # every output is a NEW generation
])
def test_the_tiny_solves_traced_here_read_what_their_dags_say(
        tmp_path, workload, expected):
    cell = tiny_cell(workload)
    devices = jax.devices()
    problem = cell.reference.make_problem(2147483999, cell.config,
                                          cell.traffic, devices[:cell.chips])
    cell.reference.prepare(problem)
    out = tmp_path / "traced"
    session = harness.Session(cell, devices, "cpu")
    try:
        assert session.solve(problem)["ok"]
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 2
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(out), profiler_options=opts)
        try:
            assert session.solve(problem)["ok"]
        finally:
            jax.profiler.stop_trace()
    finally:
        session.close()
    from benchmark.trace import reduce as tr

    trace = sp.load(tr.find_xplane(str(out)))
    took = [s for s in sp.clip_spans(trace.spans, trace.windows)
            if s.name in sp.TASK_SPANS]
    assert took and all("don" in s.args and "outs" in s.args for s in took)
    # (``of_run`` needs a device plane to cut idle time by: the CPU
    # backend has none, so the reader's own arithmetic is held here)
    outs = sum(s.args["outs"] for s in took)
    assert 100.0 * sum(s.args["don"] for s in took) / outs == expected
