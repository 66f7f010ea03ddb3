"""The DTD cell: its rehearsal on the CPU through the whole harness (by
size only), its control and three planted faults failing the check, the
driver's refusals and its warm-up of every wave size, its entries in
``BENCHMARK.json``, its four readers, and every span and counter the
DTD front-end gained in a traced tiny run."""

import os
import shutil
import types

import jax
import numpy as np
import pytest

from benchmark import harness
from benchmark.trace import reduce as tr
from benchmark.trace import spans, waits
from parsec_tpu import native
from parsec_tpu.utils import mca_param

from bench_testlib import ROOT, benchmark_json, tiny_cell, tiny_spec

CELL = "dtd_potrf_nb1024"
CONFIG = "spotrf_dtd_nb1024_1chip"
NEW_METRICS = ("dtd_insert_us_per_task", "dtd_window_stall_s",
               "dtd_discovery_pct", "dtd_flush_s")
HERE = os.path.dirname(os.path.abspath(__file__))
OLD = os.path.join(HERE, "recorded", "tiny_pump_spans.xplane.pb")
needs_native = pytest.mark.skipif(not native.available(),
                                  reason="needs the native core")


def run(**kw):
    return harness.run_cell(ROOT, tiny_cell(CELL), 2147483999, 0.5, False,
                            platform="cpu", paths=tiny_spec()["paths"], **kw)


# -- (g) the rehearsal, and its control ---------------------------------------

@needs_native
def test_the_rehearsal_runs_the_cell_and_reports_its_three_metrics(capsys):
    r = run()
    assert tuple(r) == harness.RESULT_KEYS
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["metrics"]) == {"tile_solve_s", "tile_home_s", "setup_s"}
    out = capsys.readouterr().out
    assert "compared unwritten_tiles: worst of" in out
    assert '"compiles": {"window": 0' in out


@needs_native
def test_the_control_fails_the_check(capsys):
    r = run(control=True)
    assert r["correct"] is False and r["failed"] == r["attempted"] >= 3
    out = capsys.readouterr().out
    assert "FAILED solve" in out and "violations []" in out


@needs_native
def test_an_update_skipped_in_the_timed_path_fails_the_check(monkeypatch):
    from parsec_tpu.ops import tiles

    monkeypatch.setattr(tiles, "gemm_update_tpu",
                        lambda A, B1, B2, **_: A + 0.0)
    r = run()
    assert r["correct"] is False and r["failed"] > 0


# -- (f) planted faults, against the cell's own check ------------------------

def faulty_factor(problem, fault):
    """The insertion program with one fault planted, through the user's
    calling sequence; returns the factor's host tiles.  CPU bodies (a
    writer announced as a reader needs a body that writes in place), but
    for the task left out: numpy's ``potrf`` raises on what that leaves,
    the device's returns NaN and the solve runs to its check."""
    from parsec_tpu import Context, DEV_TPU
    from parsec_tpu.datadist import TiledMatrix
    from parsec_tpu.dsl import DTDTaskpool, IN, INOUT
    from parsec_tpu.ops import tiles as T

    n, nb, nt = problem["n"], problem["nb"], problem["nt"]
    A = TiledMatrix(n, n, nb, nb, name="A", dtype=np.float32)
    for (i, j), t in problem["tiles"].items():
        A.data_of(i, j).attach_copy(0, t.copy())
    d = A.data_of
    if fault == "trsm_left_out":
        potrf, trsm, syrk, gemm = ({DEV_TPU: f} for f in (
            T.potrf_tpu, T.trsm_tpu, T.syrk_tpu, T.gemm_update_tpu))
    else:
        potrf, trsm, syrk, gemm = (T.potrf_cpu, T.trsm_cpu, T.syrk_cpu,
                                   T.gemm_update_cpu)
    ctx = Context(nb_cores=4)
    try:
        tp = DTDTaskpool(ctx)
        for k in range(nt):
            tp.insert_task(potrf, (d(k, k), INOUT), name="potrf")
            for m in range(k + 1, nt):
                if fault == "trsm_left_out" and (k, m) == (0, nt - 1):
                    continue
                tp.insert_task(trsm, (d(k, k), IN), (d(m, k), INOUT),
                               name="trsm")
            for m in range(k + 1, nt):
                tp.insert_task(syrk, (d(m, m), INOUT), (d(m, k), IN),
                               name="syrk")
                for j in range(k + 1, m):
                    if fault == "a_version_early" \
                            and (k, m, j) == (j - 1, nt - 1, nt - 2):
                        continue   # the tile's last update never lands
                    # "gemm_in": the output announced as an input — a
                    # race the inference cannot see
                    out = IN if fault == "gemm_in" else INOUT
                    tp.insert_task(gemm, (d(m, j), out),
                                   (d(m, k), IN), (d(j, k), IN), name="gemm")
        assert tp.wait(timeout=300)
        tp.flush_all(A)
        counters = tp.counters()
        tp.close()
    finally:
        ctx.fini()
    return {k: np.asarray(d(*k).get_copy(0).payload)
            for k in problem["tiles"]}, counters


@pytest.fixture(scope="module")
def tiny_problem():
    cell = tiny_cell(CELL)
    problem = cell.reference.make_problem(
        2147483999, cell.config, cell.traffic, jax.devices()[:1])
    return cell, problem


def test_the_check_passes_the_sound_insertion_program(tiny_problem):
    cell, problem = tiny_problem
    tiles, counters = faulty_factor(problem, None)
    numbers = cell.reference.compare(problem, tiles)
    assert harness.within_limits(numbers, cell.config["limits"]), numbers
    assert counters["dtd_renames"] == 0


@pytest.mark.parametrize("fault", ["gemm_in", "a_version_early",
                                   "trsm_left_out"])
def test_a_planted_fault_fails_the_check(tiny_problem, fault):
    cell, problem = tiny_problem
    tiles, counters = faulty_factor(problem, fault)
    numbers = cell.reference.compare(problem, tiles)
    assert not harness.within_limits(numbers, cell.config["limits"]), numbers
    if fault == "trsm_left_out":
        # tile (nt-1, 0) came home as the user handed it in
        assert numbers["unwritten_tiles"] >= 1
    if fault == "gemm_in":
        # the panel solve found readers where it expected the last
        # writer, and renamed: the driver holds ``dtd_renames`` at 0
        assert counters["dtd_renames"] > 0


def test_the_check_refuses_a_factor_with_a_tile_missing(tiny_problem):
    cell, problem = tiny_problem
    tiles, _ = faulty_factor(problem, None)
    del tiles[(3, 1)]
    numbers = cell.reference.compare(problem, tiles)
    assert numbers["diagonal_error"] == float("inf")
    assert not harness.within_limits(numbers, cell.config["limits"])


# -- the driver ---------------------------------------------------------------

def test_the_driver_refuses_a_program_that_cannot_run_the_cell(monkeypatch):
    from parsec_tpu.device.device import Device
    from parsec_tpu.ops import cholesky

    path = harness.find_file(ROOT, tiny_spec()["paths"], "drivers/dtd.py")
    with monkeypatch.context() as m:
        m.delattr(Device, "flush_home")
        with pytest.raises(harness.BenchError, match="flush_home"):
            harness.load_module(path)
    with monkeypatch.context() as m:
        m.delattr(cholesky, "cholesky_dtd")
        with pytest.raises(harness.BenchError, match="insertion form"):
            harness.load_module(path)
    assert harness.load_module(path).open is not None


@needs_native
def test_the_warm_up_asks_for_every_wave_size_of_every_class():
    cell = tiny_cell(CELL)
    drv = cell.driver.open(cell.config, cell.traffic,
                           cell.config["options"], jax.devices()[:1], "cpu")
    try:
        waves = {(k[1], k[-1]) for k in drv.dev._jit_cache
                 if isinstance(k, tuple) and k and k[0] == "wave"}
        assert waves == {(cls, cnt) for cls in ("trsm", "syrk", "gemm")
                         for cnt in (1, 2, 4, 8, 16, 32)}
        assert drv.dev.stats["wave_fallbacks"] == 0
        # and nothing of it is left on the device or in the counters
        assert drv.dev.hbm_used == 0
        assert drv.counters()["dtd_inserted"] == 0
    finally:
        drv.close()


def test_the_cell_is_the_configuration_the_issue_names():
    cell = harness.load_cell(ROOT, CELL)
    cfg = cell.config
    n, nb = cfg["n"], cfg["nb"]
    assert nb == 1024 and n in (40960, 32768)
    nt = n // nb
    assert cfg["tasks"]["all"] == nt + nt * (nt - 1) \
        + nt * (nt - 1) * (nt - 2) // 6
    assert cfg["window"] == {
        "dtd_window_size": mca_param.get("dtd", "window_size"),
        "dtd_threshold_size": mca_param.get("dtd", "threshold_size")}
    assert cfg["reduced"] == ["precision", "n"]
    assert set(cfg["assumed"]) == set(cfg["assumed_why"])
    assert set(cfg["limits"]) == {"diagonal_error", "offdiag_error",
                                  "unwritten_tiles"}
    assert cfg["fixed_program_set"] is True
    assert cell.traffic["driver"] == "dtd"
    assert cell.traffic["discard_solves"] == 0
    assert cell.traffic["traced_solves"] == 1
    assert len(cfg["source"]) <= 200


def test_the_new_entries_of_benchmark_json_by_membership():
    spec = benchmark_json()
    w = {x["name"]: x for x in spec["workloads"]}[CELL]
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, "dtd_insert", 1)
    c = {x["name"]: x for x in spec["configs"]}[CONFIG]
    assert c["file"] == f"benchmark/configs/{CONFIG}.json"
    with open(os.path.join(ROOT, c["file"])) as f:
        import json
        assert json.load(f)["source"] == c["source"]
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert CELL in e2e["tile_solve_s"]["workloads"]
    assert CELL in e2e["tile_home_s"]["workloads"]
    per = {m["name"]: m for m in spec["per_layer"]}
    for name in NEW_METRICS:
        assert per[name]["workloads"] == [CELL]
        assert os.path.isfile(os.path.join(ROOT, "benchmark", "layers",
                                           f"{name}.py"))
    assert per["dtd_flush_s"]["moves"] == "tile_home_s"
    for name in ("d2h_per_result", "queue_wait_us_per_task",
                 "dpotrf_roofline.tile", "sched_us_per_task",
                 "idle_in_wait_pct", "compiles_in_window"):
        assert CELL in per[name]["workloads"]
    # every metric the cell lists has a reader the harness finds
    cell = harness.load_cell(ROOT, CELL)
    assert set(NEW_METRICS) <= set(cell.readers)


# -- the readers --------------------------------------------------------------

def test_the_readers_on_a_summary_and_on_a_program_without_the_spans(
        monkeypatch):
    cell = harness.load_cell(ROOT, CELL)
    summary = spans.Summary(
        solves=2, tasks=120.0, programs=30.0,
        self_ns={"core:dtd_insert": 2 * 120 * 50_000},
        total_ns={"core:dtd_flush": 3_000_000_000}, waited_us=0.0,
        h2d_wait_ns=0, idle_ns={})
    monkeypatch.setattr(spans, "of_run", lambda run: summary)
    run = types.SimpleNamespace(
        trace=object(), cell=cell,
        per_solve=lambda k: {"dtd_insert_done_s": 4.0}.get(k),
        median=lambda k: {"tile_solve_s": 5.0}.get(k))
    assert cell.readers["dtd_insert_us_per_task"].read(run) == 50.0
    assert cell.readers["dtd_flush_s"].read(run) == 1.5
    assert cell.readers["dtd_discovery_pct"].read(run) == 80.0
    # a program whose DTD has no span (the parent): nothing, no error
    old = spans.summarize(spans.load(OLD), 1)
    monkeypatch.setattr(spans, "of_run", lambda run: old)
    for name in ("dtd_insert_us_per_task", "dtd_flush_s",
                 "dtd_window_stall_s"):
        assert cell.readers[name].read(run) is None
    monkeypatch.setattr(spans, "of_run", lambda run: None)
    for name in ("dtd_insert_us_per_task", "dtd_flush_s",
                 "dtd_window_stall_s"):
        assert cell.readers[name].read(run) is None
    none = types.SimpleNamespace(per_solve=lambda k: None,
                                 median=lambda k: None)
    assert cell.readers["dtd_discovery_pct"].read(none) is None


# -- (h) every new span and counter in a traced tiny run ---------------------

@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Two warm solves, then two under the profiler as the harness traces
    a cell, the window at 32 so that it fills."""
    out = str(tmp_path_factory.mktemp("dtd"))
    cell = tiny_cell(CELL)
    devices = jax.devices()
    problem = cell.reference.make_problem(2147483999, cell.config,
                                          cell.traffic, devices[:1])
    session = harness.Session(cell, devices, "cpu")
    # (a pool reads the window when it is made: the driver's warm-up, 190
    # tasks behind a gate, ran under the registered 2,048)
    mca_param.params.set("dtd", "window_size", 32)
    mca_param.params.set("dtd", "threshold_size", 16)
    try:
        for _ in range(2):
            assert session.solve(problem)["ok"]
        before = session.driver.counters()
        shutil.rmtree(out, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 2
        opts.python_tracer_level = 0
        jax.profiler.start_trace(out, profiler_options=opts)
        try:
            for _ in range(2):
                assert session.solve(problem)["ok"]
        finally:
            jax.profiler.stop_trace()
        after = session.driver.counters()
    finally:
        session.close()
        mca_param.params.unset("dtd", "window_size")
        mca_param.params.unset("dtd", "threshold_size")
    trace = waits.load(tr.find_xplane(out))
    nested = spans.nest(spans.clip_spans(trace.spans, trace.windows))
    return nested, {k: after[k] - before[k] for k in after}, len(trace.windows)


@needs_native
def test_every_inserted_task_is_under_a_span_of_its_own(traced):
    nested, counters, solves = traced
    inserts = [s for s in nested if s.name == "core:dtd_insert"]
    assert len(inserts) == solves * 120 == counters["dtd_inserted"]
    assert {s.args["cls"] for s in inserts} == {"potrf", "trsm", "syrk",
                                                "gemm"}
    assert sum(s.args["deps"] for s in inserts) == counters["dtd_edges"]
    assert sum(s.args["ready"] for s in inserts) >= solves  # potrf(0)
    assert len({s.thread for s in inserts}) == 1   # the user's thread
    assert all(s.parent is None for s in inserts)
    # the hold at a full window is no part of an insertion
    assert not any(s.parent is not None and s.parent.name == "core:dtd_insert"
                   for s in nested if s.name == "wait:dtd_window")


@needs_native
def test_the_window_the_wait_and_the_flush_leave_their_events(traced):
    nested, counters, solves = traced
    stalls = [s for s in nested if s.name == "wait:dtd_window"]
    assert len(stalls) == counters["dtd_window_stalls"] >= solves
    assert all(s.args["in_flight"] == 32 for s in stalls)
    assert sum(s.args.get("helped", 0) for s in stalls) == \
        counters["dtd_helped"]
    assert counters["dtd_window_stall_s"] > 0
    assert counters["dtd_renames"] == 0
    assert counters["dtd_insert_done_s"] > 0
    waited = [s for s in nested if s.name == "core:dtd_wait"]
    assert len(waited) == 2 * solves  # the user's, and flush_all's own
    assert all(s.args["done"] == 1 for s in waited)
    parked = [s for s in nested if s.name == "dtd:parked"]
    assert all(s.parent.name == "core:dtd_wait" for s in parked)
    flushes = [s for s in nested if s.name == "core:dtd_flush"]
    assert len(flushes) == solves
    assert all(s.args["n"] == 36 and s.args["bytes"] == 36 * 32 * 32 * 4
               for s in flushes)
    assert counters["dtd_flushed_tiles"] == 36 * solves
    # the copies start together under the flush, on the user's thread;
    # the committer collects them beside it
    starts = [s for s in nested if s.name == "wait:d2h_start"
              and s.parent is not None and s.parent.name in
              ("core:dtd_flush", "dev:flush")]
    assert sum(s.args["n"] for s in starts) == 36 * solves
    home = [s for s in nested if s.name == "dev:writeback"]
    assert sum(s.args["tiles"] for s in home) == 36 * solves
    for f in flushes:
        assert all(f.start <= s.start and s.end <= f.end + 1 for s in home
                   if f.start <= s.start < f.end)
    assert not any(s.start < min(f.start for f in flushes) for s in home)
