"""The tile-QR cell: its rehearsal on the CPU through the whole harness,
its control and a broken timed path failing the check, its operation
counts against the captured graph, and its five readers on a synthetic
run (and finding nothing to read in a program from before the PR)."""

import numpy as np
import pytest

from benchmark import harness, ops_count_geqrf
from benchmark.trace import modules
from parsec_tpu import native

from bench_testlib import ROOT, tiny_cell, tiny_spec

CELL = "geqrf_pump_n16384"
needs_native = pytest.mark.skipif(not native.available(),
                                  reason="needs the native core")


def run(**kw):
    return harness.run_cell(ROOT, tiny_cell(CELL), 2147483999, 0.5, False,
                            platform="cpu", paths=tiny_spec()["paths"], **kw)


@needs_native
def test_the_rehearsal_runs_the_cell_and_reports_its_three_metrics():
    r = run()
    assert tuple(r) == harness.RESULT_KEYS
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["metrics"]) == {"tile_solve_s", "tile_home_s", "setup_s"}


@needs_native
def test_the_control_fails_the_check(capsys):
    r = run(control=True)
    assert r["correct"] is False and r["failed"] == r["attempted"] >= 3
    out = capsys.readouterr().out
    assert "FAILED solve" in out and "violations []" in out


@needs_native
def test_an_update_skipped_in_the_timed_path_fails_the_check(monkeypatch):
    from parsec_tpu.ops import qr

    monkeypatch.setattr(qr, "unmqr_tpu", lambda Q, C, **_: C + 0.0)
    r = run()
    assert r["correct"] is False and r["failed"] > 0


def test_the_reference_compares_what_it_says():
    cell = tiny_cell(CELL)
    import jax

    p = cell.reference.make_problem(7, cell.config, cell.traffic,
                                    jax.devices()[:1])
    n, nb, nt = p["n"], p["nb"], p["nt"]
    a = p["a"].astype(np.float64)
    cell.reference.prepare(p)
    r = np.linalg.qr(a, mode="r")

    def tiles_of(r):
        return {(i, j): r[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb]
                for i in range(nt) for j in range(nt)}
    good = cell.reference.compare(p, tiles_of(r))
    assert good["gram_error"] < 1e-12 and good["r_block_error"] < 1e-12
    assert good["lower_residue"] == 0.0
    # a row's sign is free, a wrong entry is not, nor one below the diagonal
    flipped = r.copy()
    flipped[3] *= -1
    assert cell.reference.compare(p, tiles_of(flipped))["r_block_error"] \
        < 1e-12
    wrong = r.copy()
    wrong[n - 1, n - 1] *= 1.1
    assert cell.reference.compare(p, tiles_of(wrong))["gram_error"] > 1e-4
    low = r.copy()
    low[nb + 1, 0] = 1e-3
    assert cell.reference.compare(p, tiles_of(low))["lower_residue"] == 1e-3
    upper_only = {k: t for k, t in tiles_of(r).items() if k[0] <= k[1]}
    assert cell.reference.compare(p, upper_only) == good
    del upper_only[(0, 1)]
    assert cell.reference.compare(p, upper_only)["gram_error"] == float("inf")


@needs_native
@pytest.mark.parametrize("nt", [2, 3, 5])
def test_the_counts_are_those_of_the_captured_graph(nt):
    from benchmark.drivers import _common as c
    from parsec_tpu.datadist import TiledMatrix
    from parsec_tpu.dsl.graph import capture

    cell = tiny_cell(CELL)
    nb = 32
    tiles = {(i, j): np.zeros((nb, nb), np.float32)
             for i in range(nt) for j in range(nt)}
    A = c.fresh_matrix(TiledMatrix, {"n": nt * nb, "nb": nb, "tiles": tiles})
    g = capture(cell.driver.geqrf_taskpool(A, {}), ranks=[0])
    by_class = {}
    for (cls, _locs) in g.nodes:
        by_class[cls] = by_class.get(cls, 0) + 1
    assert by_class == {k: v for k, v in
                        ops_count_geqrf.geqrf_tasks(nt).items() if v}
    assert set(ops_count_geqrf.geqrf_tasks(nt)) == set(
        ops_count_geqrf.CLASSES)
    assert ops_count_geqrf.geqrf_ntasks(nt) == len(g.nodes)


def test_the_counts_at_the_cells_size():
    assert ops_count_geqrf.geqrf_ntasks(32) == 11440
    assert ops_count_geqrf.panel_tasks(32) == 528
    assert ops_count_geqrf.geqrf_flops(16384) == pytest.approx(5.864e12,
                                                               rel=1e-3)
    assert ops_count_geqrf.update_flops_executed(32, 512) == pytest.approx(
        2 * 512 ** 3 * 496 + 8 * 512 ** 3 * 10416)
    assert ops_count_geqrf.scratch_bytes(16384, 512) == 2016 << 20
    assert ops_count_geqrf.matrix_bytes(16384) == 1 << 30
    assert ops_count_geqrf.r_bytes(16384, 512) == 528 << 20


class _Trace:
    busy_s, solves = 3.0, 2


def _run(counters, trace=None):
    cell = tiny_cell(CELL)
    cell.config.update(n=16384, nb=512)
    return harness.Run(cell=cell, readings=[], counters=counters, solves=2,
                       compiles={}, memory={},
                       peaks={"bf16_flops_per_s": 197e12}, trace=trace)


def test_the_counter_readers(monkeypatch):
    cell = tiny_cell(CELL)
    r = cell.readers
    run = _run({"scratch_bytes_in": 0, "scratch_bytes_out": 0,
                "bytes_out": 2 << 30})
    assert r["scratch_mb_per_solve"].read(run) == 0.0
    assert r["home_copies_per_tile"].read(run) == 1.0
    spilled = _run({"scratch_bytes_in": 6 << 20, "scratch_bytes_out": 2 << 20,
                    "bytes_out": 3 << 30})
    assert r["scratch_mb_per_solve"].read(spilled) == 4.0
    assert r["home_copies_per_tile"].read(spilled) == 1.5
    # a program without the counters: nothing to read, nothing raised
    before = _run({"bytes_out": 6 << 30})
    assert r["scratch_mb_per_solve"].read(before) is None
    assert r["home_copies_per_tile"].read(before) == 3.0


def test_the_trace_readers_split_device_time_by_class(monkeypatch):
    cell = tiny_cell(CELL)
    r = cell.readers
    run = _run({}, trace=_Trace())
    # 4N^3/3 at the bf16 peak is 29.8 ms; 1.5 s busy a solve
    assert r["geqrf_roofline.tile"].read(run) == pytest.approx(
        100 * 4 * 16384 ** 3 / 3 / 197e12 / 1.5)
    m = modules.Modules(solves=2, runs={}, seconds={
        "jit__wave_tsmqr": 1.6, "jit__wave_unmqr": 0.2, "jit_unmqr_tpu": 0.2,
        "jit__wave_tsqrt": 0.9, "jit_tsqrt_tpu": 0.1, "jit_geqrt_tpu": 0.056,
        "jit__wave": 5.0, "jit_call": 7.0})
    monkeypatch.setattr(modules, "of_run", lambda run: m)
    assert r["tsmqr_roofline"].read(run) == pytest.approx(
        100 * ops_count_geqrf.update_flops_executed(32, 512) / 197e12 / 1.0)
    assert r["tsmqr_roofline"].read(run) < 100 / 6
    assert r["panel_kernel_ms_per_task"].read(run) == pytest.approx(
        1e3 * 0.528 / 528)
    # a program whose modules carry no class (every one before this PR)
    m.seconds = {"jit__wave": 5.0, "jit_call": 7.0, "jit_potrf_tpu": 1.0}
    assert r["tsmqr_roofline"].read(run) is None
    assert r["panel_kernel_ms_per_task"].read(run) is None
    # an untraced run
    monkeypatch.undo()
    assert r["tsmqr_roofline"].read(_run({})) is None
    assert r["geqrf_roofline.tile"].read(_run({})) is None


def test_modules_reads_the_programs_of_a_trace_recorded_on_the_chip():
    m = modules.load(f"{ROOT}/tests/benchmark_harness/recorded/"
                     "tiny_pump.xplane.pb")
    assert m.solves == 2
    assert set(m.seconds) == {"jit_potrf_tpu", "jit__wave"}
    assert m.runs == {"jit_potrf_tpu": 8, "jit__wave": 22}
    assert m.seconds_of(("potrf",), ("potrf", "trsm")) == pytest.approx(
        m.seconds["jit_potrf_tpu"] / 2)
    assert m.seconds_of(("trsm",), ("potrf", "trsm")) is None
    assert modules.module_name("jit__wave_tsmqr(839150637)") == \
        "jit__wave_tsmqr"
    classes = ops_count_geqrf.CLASSES
    assert modules.class_of("jit__wave_tsmqr", classes) == "tsmqr"
    assert modules.class_of("jit_tsqrt_tpu", classes) == "tsqrt"
    assert modules.class_of("jit__wave", classes) is None
    assert modules.class_of("jit_call", classes) is None
