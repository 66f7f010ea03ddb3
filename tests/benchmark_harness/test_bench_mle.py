"""The mixed-precision likelihood cell: its rehearsal on the CPU through
the whole harness (by size only), its all-bfloat16 control failing the
check, what the issue names, the driver's refusal of a program that
cannot hold the matrix on the device and the guarantees it holds a solve
to, and its five readers on a synthetic run and on a program without the
counters."""

import types

import pytest

from benchmark import harness, ops_count, ops_count_mle
from benchmark.trace import modules
from parsec_tpu import native

from bench_testlib import ROOT, benchmark_json, tiny_cell, tiny_spec

CELL = "mle_pump_n90112"
CONFIG = "smle_matern_mp_nb2048_1chip"
NEW_METRICS = ("mp_gemm_roofline", "dcmg_hbm_roofline", "converts_per_tile",
               "matrix_bytes_ratio", "wave_signatures")
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


def test_the_cell_is_what_the_issue_names():
    spec = benchmark_json()
    cell = harness.load_cell(ROOT, CELL)
    w = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (
        CONFIG, "pump_mle_n90112", 1)
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1
    c = cell.config
    assert (c["n"], c["nb"], c["grid"], c["theta"][0], c["theta"][2]) == (
        90112, 2048, [1, 1], 1.0, 0.5)
    assert c["theta"][1] in (0.1, 0.03) and 1 <= c["band_f32"] <= 8
    assert c["fixed_program_set"] is True
    assert c["reduced"] == ["precision"] and "n" in c["reduced_why"]
    assert set(c["assumed"]) == set(c["assumed_why"]) >= {
        "nb", "band_f32", "nu", "locations", "levels"}
    assert set(c["limits"]) == set(c["limits_why"]) == {
        "diagonal_error", "offdiag_error", "offdiag_lo_error",
        "solve_residual", "logdet_error", "nonfinite_values"}
    assert c["control"]["options"] == {"band_f32": 0}
    assert any("dtype of the map" in g for g in c["guarantees"])
    assert any("non-finite" in g for g in c["guarantees"])
    entry = next(e for e in spec["configs"] if e["name"] == CONFIG)
    assert entry["source"] == c["source"] and len(c["source"]) <= 200
    assert entry["reduced"] == ["precision"]
    t = cell.traffic
    assert (t["driver"], t["loop"], t["clients"], t["warmup_solves"],
            t["discard_solves"], t["traced_solves"]) == (
        "pump_mle", "closed", 1, 2, 0, 1)
    # the matrix the out-of-core cell streams stays resident here
    band = c["band_f32"]
    resident = ops_count_mle.matrix_bytes(90112, 2048, band)
    assert resident < 0.8 * 14.37e9 < ops_count.lower_tiles_bytes(
        90112, 2048) == ops_count_mle.matrix_bytes(90112, 2048, 44)
    assert ops_count_mle.ntasks(44, band) - ops_count_mle.converted_tiles(
        44, band) == 990 + ops_count.dpotrf_ntasks(44) + 990 + 88
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW_METRICS) <= names
    assert {"attach_s", "tasks_per_program", "pop_batches",
            "device_idle_pct", "flush_s", "writeback_s",
            "compiles_in_window", "dpotrf_roofline.tile", "copy_start_s",
            "gil_wait_pct", "idle_in_wait_pct", "setup_compiles"} <= names
    # nothing of the matrix goes in or comes home: the shares of a
    # matrix's bytes mean nothing here
    assert not names & {"d2h_per_result", "h2d_per_tile",
                        "home_copies_per_tile", "scratch_mb_per_solve",
                        "evictions_per_tile", "evict_wait_s",
                        "queue_wait_us_per_task"}
    for m in spec["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL] and m["moves"] == "tile_solve_s"
    for m in spec["end_to_end"]:
        if m["name"] in ("tile_solve_s", "tile_home_s"):
            assert CELL in m["workloads"]


def test_the_driver_refuses_a_program_that_cannot_hold_the_matrix(
        monkeypatch):
    """A matrix of one precision in host tiles (the program before this
    cell) is 16.6 GB staged in and brought home."""
    from parsec_tpu.datadist import TiledMatrix

    def before(self, m, n, mb, nb, *, name="A", dtype=float, nodes=1,
               myrank=0, uplo="full", init=None):
        raise AssertionError("never built")

    harness.load_cell(ROOT, CELL)
    monkeypatch.setattr(TiledMatrix, "__init__", before)
    with pytest.raises(harness.BenchError, match="born on the device"):
        harness.load_cell(ROOT, CELL)


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
        drv.release(first)
        # a tile of the matrix staged in or written home, one evicted, a
        # scratch tile spilled, a tile converted twice, one freed that
        # the matrix keeps: each is a violation of the next solve
        real = drv.counters
        for k in ("bytes_in", "bytes_out", "evictions",
                  "scratch_bytes_out", "convert_tiles",
                  "scratch_tiles_freed"):
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
        # no two solves of a session share a theta
        second = drv.solve(p)
        assert second["violations"] == []
        assert second["result"]["theta"] != first["result"]["theta"]
        # a solve that binds no stored plan
        from parsec_tpu.dsl import attach_plan

        attach_plan.clear()
        s = drv.solve(p)
        assert len(s["violations"]) == 1 \
            and "attach plan" in s["violations"][0]
        assert drv.solve(p)["violations"] == []
        assert drv.counters()["resident_peak_bytes"] > 0
    finally:
        drv.close()


def _run(counters, trace=None):
    cell = tiny_cell(CELL)
    cell.config.update(n=90112, nb=2048, band_f32=4)
    return harness.Run(cell=cell, readings=[], counters=counters, solves=2,
                       compiles={}, memory={},
                       peaks={"hbm_bytes_per_s": 819e9,
                              "bf16_flops_per_s": 197e12}, trace=trace)


def test_the_counter_readers():
    r = tiny_cell(CELL).readers
    run_ = _run({"convert_tiles": 228, "wave_signatures": 52,
                 "resident_peak_bytes": 2 * 9730785280})
    assert r["converts_per_tile"].read(run_) == 1.0
    assert r["wave_signatures"].read(run_) == 26.0
    assert r["matrix_bytes_ratio"].read(run_) == pytest.approx(0.5859,
                                                               rel=1e-3)
    # readers that convert on their own: 36.7 conversions a tile
    assert r["converts_per_tile"].read(_run({"convert_tiles": 8366})) \
        == pytest.approx(36.69, rel=1e-3)
    # without the counters: nothing to read, nothing raised
    for name in ("converts_per_tile", "wave_signatures",
                 "matrix_bytes_ratio"):
        assert r[name].read(_run({})) is None


def test_the_roofline_readers_take_their_programs_time(monkeypatch):
    r = tiny_cell(CELL).readers
    run_ = _run({}, trace=types.SimpleNamespace())
    m = modules.Modules(solves=2, runs={}, seconds={
        "jit__wave_gemm": 5.0, "jit__wave_syrk": 0.5, "jit_gemm_tpu": 0.5,
        "jit__wave_dcmg": 0.25, "jit_dcmg_tpu": 0.05, "jit__wave_gemv": 9.0,
        "jit_call": 7.0})
    monkeypatch.setattr(modules, "of_run", lambda run: m)
    flops = (946 + 2 * 13244) * 2.0 ** 33
    assert r["mp_gemm_roofline"].read(run_) == pytest.approx(
        100 * flops / 197e12 / 3.0)
    assert r["mp_gemm_roofline"].read(run_) < 100
    least = 9730785280 / 819e9
    assert r["dcmg_hbm_roofline"].read(run_) == pytest.approx(
        100 * least / 0.15)
    # a program whose modules carry no class, and an untraced run
    m.seconds = {"jit__wave": 5.0, "jit_call": 7.0}
    assert r["mp_gemm_roofline"].read(run_) is None
    assert r["dcmg_hbm_roofline"].read(run_) is None
    monkeypatch.undo()
    assert r["mp_gemm_roofline"].read(_run({})) is None
    assert r["dcmg_hbm_roofline"].read(_run({})) is None
