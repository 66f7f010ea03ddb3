"""The hierarchical tile-QR cell: its rehearsal on the CPU through the
whole harness, its control and a broken timed path failing the check, its
reference on a tall matrix, its operation counts against the captured
graph, its five readers on a synthetic run, and the refusal of a program
whose ``qr_ptg`` takes no tree."""

import numpy as np
import pytest

from benchmark import harness, ops_count_geqrf_hqr as hqr
from benchmark.trace import modules
from parsec_tpu import native

from bench_testlib import ROOT, benchmark_json, tiny_cell, tiny_spec

CELL = "geqrf_hqr_m262144"
CONFIG = "sgeqrf_hqr_nb512_1chip"
NEW_METRICS = {"hqr_update_roofline", "hqr_roofline", "hqr_kill_ms_per_task",
               "kill_wave_width", "scratch_peak_mb"}
needs_native = pytest.mark.skipif(not native.available(),
                                  reason="needs the native core")


def run(**kw):
    return harness.run_cell(ROOT, tiny_cell(CELL), 2147483999, 0.5, False,
                            platform="cpu", paths=tiny_spec()["paths"], **kw)


def test_the_new_entries_are_there_by_name():
    spec = benchmark_json()
    cfg = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert cfg["reduced"] == ["precision", "m"]
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "pump_geqrf_hqr", 1)
    by_name = {m["name"]: m for m in spec["end_to_end"] + spec["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "tile_solve_s"
    # the cell joins what the square tile QR reports, but the four
    # metrics whose counts are a square matrix's and the one whose list
    # a test of the accepted benchmark holds letter for letter
    square = {n for n, m in by_name.items()
              if "geqrf_pump_n16384" in m.get("workloads", ())}
    mine = {n for n, m in by_name.items() if CELL in m.get("workloads", ())}
    assert square - mine == {"geqrf_roofline.tile", "tsmqr_roofline",
                             "panel_kernel_ms_per_task",
                             "home_copies_per_tile", "donated_outputs_pct"}
    assert mine - square == NEW_METRICS
    assert {"tile_solve_s", "tile_home_s", "attach_s", "pop_batches",
            "scratch_mb_per_solve", "gil_wait_pct"} <= mine


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
def test_a_tt_kill_that_kills_nothing_fails_the_check(monkeypatch):
    import jax.numpy as jnp

    from parsec_tpu.ops import qr

    monkeypatch.setattr(
        qr, "ttqrt_tpu", lambda R, B, Q, **_: (
            R, jnp.zeros_like(B), jnp.eye(2 * R.shape[0], dtype=R.dtype)))
    r = run()
    assert r["correct"] is False and r["failed"] > 0


@needs_native
def test_every_solve_after_the_first_binds_the_stored_plan():
    from parsec_tpu.dsl import attach_plan

    attach_plan.clear()
    cell = tiny_cell(CELL)
    import jax

    session = harness.Session(cell, jax.devices(), "cpu")
    p = cell.reference.make_problem(3, cell.config, cell.traffic,
                                    jax.devices()[:1])
    cell.reference.prepare(p)
    try:
        for _ in range(3):
            assert session.solve(p)["ok"]
        c = session.driver.counters()
    finally:
        session.close()
    assert c["attach_plan_hits"] == 2
    # the Q blocks of a solve: born, freed, never across the host, and
    # the high-water mark below all of them
    mt, nt, a = hqr.grid_of(lambda k: int(cell.config[k]))
    nb = int(cell.config["nb"])
    assert c["scratch_tiles_born"] == c["scratch_tiles_freed"] \
        == 3 * hqr.kill_tasks(mt, nt, a)
    assert c["scratch_bytes_in"] == c["scratch_bytes_out"] == 0
    assert 0 < c["scratch_peak_sum"] / 3 <= hqr.scratch_bytes(mt, nt, a, nb)


def test_the_reference_compares_what_it_says():
    cell = tiny_cell(CELL)
    import jax

    p = cell.reference.make_problem(7, cell.config, cell.traffic,
                                    jax.devices()[:1])
    m, n, nb, mt, nt = p["m"], p["n"], p["nb"], p["mt"], p["nt"]
    assert (m, n, mt, nt) == (288, 96, 9, 3) and len(p["tiles"]) == 27
    a = p["a"].astype(np.float64)
    assert np.abs(a).max() <= 0.5 and abs(a.mean()) < 0.01
    again = cell.reference.make_problem(7, cell.config, cell.traffic,
                                        jax.devices()[:1])
    np.testing.assert_array_equal(p["a"], again["a"])
    other = cell.reference.make_problem(2 ** 31 + 7, cell.config,
                                        cell.traffic, jax.devices()[:1])
    assert np.abs(other["a"] - p["a"]).mean() > 0.2
    cell.reference.prepare(p)
    r = np.zeros((m, n))
    r[:n] = np.linalg.qr(a, mode="r")

    def tiles_of(r):
        return {(i, j): r[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb]
                for i in range(mt) for j in range(nt)}
    good = cell.reference.compare(p, tiles_of(r))
    assert good["gram_error"] < 1e-12 and good["r_block_error"] < 1e-12
    assert good["lower_residue"] == 0.0
    # a row's sign is free, a wrong entry is not, nor one under R
    flipped = r.copy()
    flipped[3] *= -1
    assert cell.reference.compare(p, tiles_of(flipped))["r_block_error"] \
        < 1e-12
    wrong = r.copy()
    col = p["cols"][-1]           # a sampled column of the last tile column
    wrong[col, col] *= 1.1
    assert cell.reference.compare(p, tiles_of(wrong))["gram_error"] > 1e-4
    low = r.copy()
    low[m - 2, n - 1] = 1e-3      # a killed tile that is not zeros
    assert cell.reference.compare(p, tiles_of(low))["lower_residue"] == 1e-3
    upper_only = {k: t for k, t in tiles_of(r).items() if k[0] <= k[1]}
    assert cell.reference.compare(p, upper_only) == good
    del upper_only[(0, 1)]
    assert cell.reference.compare(p, upper_only)["gram_error"] == float("inf")


@needs_native
@pytest.mark.parametrize("mt, nt, a", [(9, 3, 2), (4, 4, 4), (7, 2, 3),
                                       (5, 5, 1), (6, 3, 6)])
def test_the_counts_are_those_of_the_captured_graph(mt, nt, a):
    from parsec_tpu.dsl.graph import capture

    cell = tiny_cell(CELL)
    nb = 8
    tiles = {(i, j): np.zeros((nb, nb), np.float32)
             for i in range(mt) for j in range(nt)}
    A = cell.driver.fresh_matrix({"m": mt * nb, "n": nt * nb, "nb": nb,
                                  "tiles": tiles})
    tree = cell.driver.QRTree(mt, nt, a)
    g = capture(cell.driver.hqr_taskpool(A, tree, {}), ranks=[0])
    by_class = {}
    for (cls, _locs) in g.nodes:
        by_class[cls] = by_class.get(cls, 0) + 1
    assert by_class == {k: v for k, v in hqr.hqr_tasks(mt, nt, a).items()
                        if v}
    assert hqr.hqr_ntasks(mt, nt, a) == len(g.nodes)


def test_the_counts_at_the_cells_size():
    t = hqr.hqr_tasks(512, 8, 4)
    assert t == {"geqrt": 1020, "unmqr": 3578, "tsqrt": 3048,
                 "tsmqr": 10702, "ttqrt": 1012, "ttmqr": 3550}
    assert hqr.hqr_ntasks(512, 8, 4) == 22910
    assert hqr.kill_tasks(512, 8, 4) == 5080
    # the flat tree on a square grid is the square tile QR's DAG
    from benchmark import ops_count_geqrf

    flat = hqr.hqr_tasks(32, 32, 32)
    assert {k: v for k, v in flat.items() if v} == \
        ops_count_geqrf.geqrf_tasks(32)
    assert hqr.geqrf_flops(16384, 16384) == pytest.approx(
        ops_count_geqrf.geqrf_flops(16384))
    assert hqr.geqrf_flops(262144, 4096) == pytest.approx(8.750e12, rel=1e-3)
    assert hqr.update_flops_executed(512, 8, 4, 512) == pytest.approx(
        2 * 512 ** 3 * 3578 + 8 * 512 ** 3 * (10702 + 3550))
    assert hqr.scratch_bytes(512, 8, 4, 512) == (1020 + 4 * 4060) << 20
    assert hqr.matrix_bytes(262144, 4096) == 4 << 30
    assert hqr.r_bytes(4096, 512) == 36 << 20


class _Trace:
    busy_s, solves = 3.0, 2


def _run(counters, trace=None):
    cell = tiny_cell(CELL)
    cell.config.update(m=262144, n=4096, nb=512, qr_a=4)
    return harness.Run(cell=cell, readings=[], counters=counters, solves=2,
                       compiles={}, memory={},
                       peaks={"bf16_flops_per_s": 197e12}, trace=trace)


def test_the_counter_reader():
    r = tiny_cell(CELL).readers["scratch_peak_mb"]
    assert r.read(_run({"scratch_peak_sum": 4160 << 20})) == 2080.0
    # a program without the counter: nothing to read, nothing raised
    assert r.read(_run({})) is None
    assert r.read(_run({"scratch_peak_sum": 0})) is None


def test_the_trace_readers_split_device_time_by_class(monkeypatch):
    r = tiny_cell(CELL).readers
    run = _run({}, trace=_Trace())
    m = modules.Modules(solves=2, runs={}, seconds={
        "jit__wave_tsmqr": 1.0, "jit__wave_ttmqr": 0.4,
        "jit__wave_unmqr": 0.2, "jit_unmqr_tpu": 0.2, "jit_ttmqr_tpu": 0.2,
        "jit__wave_tsqrt": 3.0, "jit__wave_ttqrt": 1.0,
        "jit__wave_geqrt": 0.9, "jit_geqrt_tpu": 0.18,
        "jit__wave": 5.0, "jit_call": 7.0})
    monkeypatch.setattr(modules, "of_run", lambda run: m)
    assert r["hqr_update_roofline"].read(run) == pytest.approx(
        100 * hqr.update_flops_executed(512, 8, 4, 512) / 197e12 / 1.0)
    assert r["hqr_update_roofline"].read(run) < 100 / 6
    assert r["hqr_kill_ms_per_task"].read(run) == pytest.approx(
        1e3 * 2.54 / 5080)
    assert r["hqr_roofline"].read(run) == pytest.approx(
        100 * hqr.geqrf_flops(262144, 4096) / 197e12 / 3.54)
    assert r["hqr_roofline"].read(run) < 100 / 12
    # a program whose modules carry no class of this DAG
    m.seconds = {"jit__wave": 5.0, "jit_call": 7.0, "jit_potrf_tpu": 1.0}
    for name in ("hqr_update_roofline", "hqr_kill_ms_per_task",
                 "hqr_roofline"):
        assert r[name].read(run) is None
    # an untraced run
    monkeypatch.undo()
    for name in NEW_METRICS - {"scratch_peak_mb"}:
        assert r[name].read(_run({})) is None
    assert modules.class_of("jit__wave_ttmqr", hqr.CLASSES) == "ttmqr"
    assert modules.class_of("jit_ttqrt_tpu", hqr.CLASSES) == "ttqrt"


def test_a_program_whose_qr_ptg_takes_no_tree_is_refused(monkeypatch):
    from parsec_tpu.ops import qr

    spec = tiny_spec()
    path = harness.find_file(ROOT, spec["paths"],
                             "drivers/pump_geqrf_hqr.py")
    monkeypatch.setattr(qr, "qr_ptg", lambda *, use_tpu=True: None)
    with pytest.raises(harness.BenchError, match="takes no reduction tree"):
        harness.load_module(path)
