"""``byte_cut_programs_pct`` (PR 45): the entry of ``BENCHMARK.json``, its
reader under ``benchmark/layers/``, and what the reader reads: the trace
recorded on a v5e before the ``dev:wave`` spans carried ``cut``
(nothing), that trace with ``cut`` planted on them, and the tiny pump and
stencil solves traced here on the CPU backend (counts, never times)."""

import jax
import pytest

from benchmark import harness
from benchmark.trace import reduce as tr
from benchmark.trace import spans as sp
from parsec_tpu import native

from bench_testlib import ROOT, benchmark_json, tiny_cell
from test_bench_donation import RECORDED, _run, _trace_at

METRIC = "byte_cut_programs_pct"


def _reader():
    return harness.load_module(
        harness.find_reader(ROOT, benchmark_json()["paths"], METRIC))


def test_the_entry_and_its_reader():
    spec = benchmark_json()
    entry = next(m for m in spec["per_layer"] if m["name"] == METRIC)
    moved = next(m for m in spec["end_to_end"]
                 if m["name"] == "tile_solve_s")
    # every cell that goes through ``_submit_wave`` when PR 45 was
    # written: the nine tile cells (the panel cell has no wave)
    assert entry == {
        "name": METRIC, "unit": "%", "better": "lower",
        "source": "program_span", "layer": "device",
        "moves": "tile_solve_s",
        "workloads": ["tile_pump_n8192", "tile_ctx_n8192",
                      "tile_2x2_n16384", "geqrf_pump_n16384",
                      "ooc_pump_n90112", "stencil_pump_n32768",
                      "mle_pump_n90112", "dtd_potrf_nb1024",
                      "geqrf_hqr_m262144"]}
    assert set(entry["workloads"]) <= set(moved["workloads"])
    path = harness.find_reader(ROOT, spec["paths"], METRIC)
    assert path.endswith(f"layers/{METRIC}.py")
    reader = harness.load_module(path)
    assert reader.read(_run("a_cell", traced=False)) is None
    assert reader.read(_run("no_such_cell_was_ever_traced")) is None


def test_a_program_whose_spans_carry_no_cut_gives_nothing(tmp_path,
                                                          monkeypatch):
    run = _trace_at(tmp_path, monkeypatch, RECORDED)
    assert sp.of_run(run) is not None      # the spans are there
    assert _reader().read(run) is None     # ``cut`` is not


@pytest.mark.parametrize("every, expected", [(1, 100.0), (2, 50.0),
                                             (4, 25.0), (0, 0.0)])
def test_the_share_is_the_wave_spans_the_bound_cut(tmp_path, monkeypatch,
                                                   every, expected):
    """``cut`` planted on the recorded trace's ``dev:wave`` spans: every
    ``every``-th says ``bytes`` (none at 0); a task alone has no width to
    cut and is not counted."""
    run = _trace_at(tmp_path, monkeypatch, RECORDED)
    load = sp.load

    def planted(path):
        trace = load(path)
        waves = [s for s in sp.clip_spans(trace.spans, trace.windows)
                 if s.name == "dev:wave"]
        assert len(waves) >= 8
        # (a whole number of fours, so that the shares are exact)
        for s in waves[len(waves) - len(waves) % 4:]:
            s.name = "dev:submit_one"
        for k, s in enumerate(waves):
            s.args["cut"] = "bytes" if every and k % every == 0 \
                else "tasks"
        return trace
    monkeypatch.setattr(sp, "load", planted)
    assert _reader().read(run) == pytest.approx(expected)


@pytest.mark.skipif(not native.available(), reason="needs the native core")
@pytest.mark.parametrize("workload, grids, expected", [
    # every wave of the tiny dpotrf fits its bound
    ("tile_pump_n8192", None, 0.0),
    # the tiny stencil (4 x 4 tiles, 4 sweeps) under a budget of three
    # grids: a chunk has room for three tiles.  Sweep 0 reads the host's
    # tiles, six tiles a task: 12 wave programs of one task, 7 of them
    # cut; a later sweep's operands were born on the device, one tile a
    # task: the four interior tasks 2 + 2 (the first cut), four edges of
    # two: 6 programs, 1 cut
    ("stencil_pump_n32768", 3, 100.0 * (7 + 3) / (12 + 3 * 6)),
])
def test_the_tiny_solves_traced_here_read_what_their_dags_say(
        tmp_path, workload, grids, expected):
    cell = tiny_cell(workload)
    devices = jax.devices()
    problem = cell.reference.make_problem(2147483999, cell.config,
                                          cell.traffic, devices[:cell.chips])
    cell.reference.prepare(problem)
    out = tmp_path / "traced"
    session = harness.Session(cell, devices, "cpu")
    try:
        assert session.solve(problem)["ok"]
        if grids:
            session.driver.dev.hbm_budget = \
                grids * 4 * int(cell.config["n"]) ** 2
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
    trace = sp.load(tr.find_xplane(str(out)))
    took = sp.clip_spans(trace.spans, trace.windows)
    waves = [s for s in took if s.name == "dev:wave"]
    assert waves and all(s.args["cut"] in ("bytes", "tasks")
                         and int(s.args["counted"]) > 0 for s in waves)
    # (``of_run`` needs a device plane to cut idle time by: the CPU
    # backend has none, so the reader's own arithmetic is called here)
    assert _reader().share(took) == pytest.approx(expected)
