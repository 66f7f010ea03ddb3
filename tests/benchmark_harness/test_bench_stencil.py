"""The stencil cell: its rehearsal on the CPU through the whole harness
(by size only), its control and a wrongly wired halo failing the check,
the driver's refusal of a program whose stencil sends every generation
home and the guarantees it holds a solve to, its counts, and its four
readers on a synthetic run and on a program without the counters."""

import types

import pytest

from benchmark import harness, ops_count_stencil
from benchmark.trace import modules
from parsec_tpu import native
from parsec_tpu.core.lifecycle import AccessMode
from parsec_tpu.dsl.ptg import PTG

from bench_testlib import ROOT, benchmark_json, tiny_cell, tiny_spec

CELL = "stencil_pump_n32768"
CONFIG = "sstencil_2d5pt_nb4096_1chip"
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
def test_a_halo_left_out_of_the_timed_path_fails_the_check(monkeypatch):
    from parsec_tpu.ops import stencil

    sound = stencil._edges
    monkeypatch.setattr(
        stencil, "_edges", lambda OLD, UP, DOWN, LEFT, RIGHT: sound(
            OLD, None, DOWN, LEFT, RIGHT))
    r = run()
    assert r["correct"] is False and r["failed"] > 0


def test_the_cell_is_what_the_issue_names():
    spec = benchmark_json()
    cell = harness.load_cell(ROOT, CELL)
    w = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (
        CONFIG, "pump_stencil_n32768", 1)
    c = cell.config
    assert (c["n"], c["nb"], c["iters"], c["precision"], c["grid"]) == (
        32768, 4096, 100, "float32", [1, 1])
    assert c["fixed_program_set"] is True
    assert c["reduced"] == ["precision"] and "n" in c["reduced_why"]
    assert set(c["assumed"]) == set(c["assumed_why"]) == {
        "nb", "iters", "operator"}
    assert set(c["limits"]) == set(c["limits_why"]) == {
        "window_error", "edge_error"}
    assert c["control"]["options"] == {"bf16_updates": True}
    entry = next(e for e in spec["configs"] if e["name"] == CONFIG)
    assert entry["source"] == c["source"] and len(c["source"]) <= 200
    t = cell.traffic
    assert (t["driver"], t["loop"], t["clients"], t["warmup_solves"],
            t["discard_solves"], t["traced_solves"]) == (
        "pump_stencil", "closed", 1, 2, 0, 1)
    assert ops_count_stencil.stencil_ntasks(32768, 4096, 100) == 6400
    assert ops_count_stencil.grid_bytes(32768) == 4 * 2 ** 30
    # the least a sweep-a-pass program moves: two grids a sweep
    assert ops_count_stencil.sweep_hbm_bytes(32768, 100) == 800 * 2 ** 30
    names = {m["name"] for m in cell.per_layer}
    assert {"stencil_hbm_roofline", "grid_in_per_solve",
            "resident_grids_peak", "repeated_args_pct",
            "home_copies_per_tile", "scratch_mb_per_solve",
            "device_idle_pct", "dispatch_us_per_program"} <= names
    # the evict_* readers find no ``dev:evict`` span in a solve that
    # stays under the budget, and a cell's line has to hold every metric
    # that lists it: the driver holds ``evict_dirty`` at 0 instead
    assert not names & {"d2h_per_result", "h2d_per_tile",
                        "evictions_per_tile", "dpotrf_roofline.tile",
                        "geqrf_roofline.tile", "evict_home_mb_per_solve",
                        "evict_wait_s"}


def test_the_windows_at_the_cells_size():
    cell = harness.load_cell(ROOT, CELL)
    wins = cell.reference.windows_of(32768, 32768, 4096, 4096)
    assert len(wins) == 81 + 64 == 145
    inside = [w for w in wins if w[1] - w[0] == 16 and w[3] - w[2] == 16]
    assert len(inside) == 49 + 64
    assert sorted((w[1] - w[0]) * (w[3] - w[2]) for w in wins)[:4] == [64] * 4
    # a patch is at most (16 + 2 T)^2 points
    assert max((min(32768, w[1] + 100) - max(0, w[0] - 100))
               for w in wins) == 216


def test_the_driver_refuses_a_stencil_that_sends_generations_home():
    """By what the PTG says: a ``NEW`` flow that takes a collection's
    tile for every generation (the program before this cell) has no
    ``<- NEW`` source."""
    from parsec_tpu.ops import stencil

    cell = harness.load_cell(ROOT, CELL)
    born = cell.driver.born_on_the_device
    assert born(stencil.stencil_ptg(use_tpu=True, use_cpu=False))

    def old_form(**_kw):
        ptg = PTG("stencil2d")
        st = ptg.task_class("stencil", t="0 .. T-1", i="0 .. MT-1",
                            j="0 .. NT-1")
        st.flow("OLD", AccessMode.IN,
                "<- (t == 0) ? A(0, i, j) : NEW stencil(t-1, i, j)")
        st.flow("NEW", AccessMode.INOUT, "<- A((t+1) % 2, i, j)",
                "-> (t < T-1) ? OLD stencil(t+1, i, j)",
                "-> A((t+1) % 2, i, j)")
        st.body(tpu=lambda OLD, NEW, **_: OLD)
        return ptg

    assert not born(old_form())
    stencil_ptg = stencil.stencil_ptg
    try:
        stencil.stencil_ptg = old_form
        with pytest.raises(harness.BenchError, match="every generation"):
            harness.load_cell(ROOT, CELL)
    finally:
        stencil.stencil_ptg = stencil_ptg


@needs_native
def test_the_driver_holds_a_solve_to_its_guarantees():
    import jax

    cell = tiny_cell(CELL)
    p = cell.reference.make_problem(7, cell.config, cell.traffic,
                                    jax.devices()[:1])
    cell.reference.prepare(p)
    drv = cell.driver.open(cell.config, cell.traffic, {}, [], "cpu")
    try:
        first = drv.solve(p)
        assert first["violations"] == []
        assert harness.within_limits(
            cell.reference.compare(p, first["result"]),
            cell.config["limits"])
        # a second grid staged in, a generation written home, one
        # spilled, a dirty eviction: each is a violation of the next solve
        real = drv.counters
        for k in ("bytes_in", "bytes_out", "scratch_bytes_out",
                  "evict_dirty"):
            seen = []

            def skewed():
                out = real()
                if seen:  # the reading after the solve
                    out[k] += 4096
                seen.append(1)
                return out

            drv.counters = skewed
            s = drv.solve(p)
            assert len(s["violations"]) == 1, (k, s["violations"])
        drv.counters = real
        # a solve that binds no stored plan
        from parsec_tpu.dsl import attach_plan

        attach_plan.clear()
        s = drv.solve(p)
        assert len(s["violations"]) == 1 \
            and "attach plan" in s["violations"][0]
        assert drv.solve(p)["violations"] == []
    finally:
        drv.close()


def _run(counters, memory=None, trace=None):
    cell = tiny_cell(CELL)
    cell.config.update(n=32768, nb=4096, iters=100)
    return harness.Run(cell=cell, readings=[], counters=counters, solves=2,
                       compiles={}, memory=memory or {},
                       peaks={"hbm_bytes_per_s": 819e9}, trace=trace)


def test_the_counter_readers():
    r = tiny_cell(CELL).readers
    grid = 4 * 2 ** 30
    run_ = _run({"bytes_in": 2 * grid, "bytes_out": 2 * grid,
                 "scratch_bytes_in": 0, "scratch_bytes_out": 0,
                 "tile_args_passed": 64000, "tile_args_repeated": 12800},
                memory={"peak_bytes": 3.25 * grid})
    assert r["grid_in_per_solve"].read(run_) == 1.0
    assert r["home_copies_per_tile"].read(run_) == 1.0
    assert r["scratch_mb_per_solve"].read(run_) == 0.0
    assert r["resident_grids_peak"].read(run_) == 3.25
    assert r["repeated_args_pct"].read(run_) == 20.0
    # the program before the cell: two grids in, a hundred home
    before = _run({"bytes_in": 4 * grid, "bytes_out": 200 * grid})
    assert r["grid_in_per_solve"].read(before) == 2.0
    assert r["home_copies_per_tile"].read(before) == 100.0
    # ... and without the counters: nothing to read, nothing raised
    assert r["repeated_args_pct"].read(before) is None
    assert r["resident_grids_peak"].read(before) is None
    assert r["grid_in_per_solve"].read(_run({})) is None


def test_the_roofline_reader_takes_the_stencil_programs_time(monkeypatch):
    r = tiny_cell(CELL).readers["stencil_hbm_roofline"]
    run_ = _run({}, trace=types.SimpleNamespace())
    m = modules.Modules(solves=2, runs={}, seconds={
        "jit__wave_stencil": 5.0, "jit_stencil_tpu": 1.0, "jit_call": 7.0})
    monkeypatch.setattr(modules, "of_run", lambda run: m)
    # 800 GiB at 819 GB/s is 1.049 s; 3 s of stencil programs a solve
    least = 800 * 2 ** 30 / 819e9
    assert least == pytest.approx(1.0488, rel=1e-4)
    assert r.read(run_) == pytest.approx(100 * least / 3.0)
    # a program whose modules carry no class, and an untraced run
    m.seconds = {"jit__wave": 5.0, "jit_call": 7.0}
    assert r.read(run_) is None
    monkeypatch.undo()
    assert r.read(_run({})) is None
