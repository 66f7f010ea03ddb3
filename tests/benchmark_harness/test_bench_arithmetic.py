"""Median and count arithmetic, limits, operation counts, and every
per-layer reader on a run built by hand."""

import math

import pytest

from benchmark import harness, ops_count
from benchmark.trace import reduce as tr

from bench_testlib import ROOT, benchmark_json, tiny_cell


def test_median_is_over_every_reading():
    assert harness.median([3.0, 1.0, 2.0]) == 2.0
    assert harness.median([4.0, 1.0, 2.0, 3.0]) == 2.5
    with pytest.raises(harness.BenchError):
        harness.median([])


@pytest.mark.parametrize("numbers,ok", [
    ({"factor_error": 1e-7}, True),
    ({"factor_error": 3e-6}, True),
    ({"factor_error": 3.1e-6}, False),
    ({"factor_error": float("nan")}, False),
    ({"factor_error": float("inf")}, False),
])
def test_within_limits(numbers, ok):
    assert harness.within_limits(numbers, {"factor_error": 3e-6}) is ok


def test_a_number_without_a_limit_is_an_error():
    with pytest.raises(harness.BenchError, match="no limit"):
        harness.within_limits({"other": 0.0}, {"factor_error": 3e-6})


def test_the_worst_number_is_kept_and_a_nan_sticks():
    worst = {}
    for numbers in ({"e": 1e-7}, {"e": 3e-7}, {"e": 2e-7}):
        harness.keep_worst(worst, numbers)
    assert worst == {"e": 3e-7}
    harness.keep_worst(worst, {"e": float("nan")})
    harness.keep_worst(worst, {"e": 1.0})
    assert math.isnan(worst["e"])


def test_operation_counts():
    assert ops_count.roofline_pct(197e12, 197e12, 1, 4.0) == 25.0
    assert ops_count.roofline_pct(197e12, 197e12, 4, 1.0) == 25.0
    assert ops_count.dpotrf_ntasks(16) == 816
    assert ops_count.dpotrf_ntasks(32) == 5984
    assert ops_count.dpotrf_flops(8192) == pytest.approx(8192 ** 3 / 3)
    assert ops_count.lower_tiles_bytes(8192, 512) == 136 * 512 * 512 * 4


def _run(workload, **kw):
    cell = tiny_cell(workload)
    base = dict(
        cell=cell,
        readings=[{"tile_solve_s": 1.0, "attach_s": 0.1, "flush_s": 0.02,
                   "rank_skew_pct": 1.0},
                  {"tile_solve_s": 3.0, "attach_s": 0.3, "flush_s": 0.04,
                   "rank_skew_pct": 3.0},
                  {"tile_solve_s": 2.0, "attach_s": 0.2, "flush_s": 0.03,
                   "rank_skew_pct": 2.0}],
        counters={"executed_tasks": 60, "wave_submits": 6, "wave_tasks": 48,
                  "bytes_out": 3 * 2 * ops_count.lower_tiles_bytes(128, 32),
                  "bytes_d2d": 3e6, "pop_batches": 12},
        solves=3, compiles={"window": 1, "setup": 7,
                            "setup_cache_misses": 2},
        memory={"peak_bytes": 5e9, "bytes_in_use": 4.5e9},
        peaks={"bf16_flops_per_s": 197e12})
    base.update(kw)
    return harness.Run(**base)


def _summary(busy_s, solves=2, window_s=1.0):
    return tr.Summary(window_s=window_s, solves=solves,
                      busy_by_chip={0: busy_s}, device_ops=[], idle_gaps=[])


def test_readers_from_counters_and_clocks():
    run = _run("tile_2x2_n16384")
    read = {n: m.read(run) for n, m in run.cell.readers.items()}
    assert read["attach_s"] == 0.2 and read["flush_s"] == 0.03
    assert read["rank_skew_pct"] == 2.0
    # 60 tasks in 6 wave programs + 12 alone
    assert read["tasks_per_program"] == pytest.approx(60 / 18)
    assert read["d2h_per_result"] == pytest.approx(2.0)
    assert read["d2d_mb_per_solve"] == pytest.approx(1.0)
    assert read["compiles_in_window"] == 1 and read["setup_compiles"] == 7
    # no trace: the readers of the trace find nothing and return nothing
    assert read["device_idle_pct"] is None
    assert read["dpotrf_roofline.tile"] is None
    assert _run("tile_pump_n8192").cell.readers["pop_batches"].read(
        _run("tile_pump_n8192")) == 4.0


def test_readers_from_the_trace():
    run = _run("panel_n32768", trace=_summary(0.25, solves=2))
    read = {n: m.read(run) for n, m in run.cell.readers.items()}
    assert read["device_idle_pct.panel"] == pytest.approx(75.0)
    assert read["hbm_resident_mb"] == pytest.approx(4500.0)
    n = run.size("n")
    least = n ** 3 / 3 / 197e12
    assert read["dpotrf_roofline.panel"] == pytest.approx(
        100 * least / 0.125)
    assert math.isfinite(read["dpotrf_roofline.panel"])


def test_every_metric_of_a_cell_has_a_reader_and_a_moves_it_reports():
    spec = benchmark_json()
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    for w in spec["workloads"]:
        cell = harness.load_cell(ROOT, w["name"], spec)
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in e2e and m["moves"] in reported, m
            assert callable(cell.readers[m["name"]].read)
