"""The SPD-inverse cell (three pools composed on the pump path): its
entries by membership, its rehearsal on the CPU through the whole harness,
its control failing a limit, planted faults failing the reference, the
driver's guarantees and refusals, its counts, and its four readers on
synthetic runs and on solves traced here (counts, never times)."""

import numpy as np
import pytest

from benchmark import harness, ops_count, ops_count_poinv as counts
from benchmark.trace import modules
from benchmark.trace import reduce as tr
from benchmark.trace import spans as sp
from parsec_tpu import native

from bench_testlib import ROOT, benchmark_json, tiny_cell, tiny_spec

CELL = "poinv_pump_n49152"
CONFIG = "spoinv_tile_nb2048_1chip"
NEW_METRICS = {"member_gap_s", "poinv_roofline", "trtri_roofline",
               "lauum_roofline"}
needs_native = pytest.mark.skipif(not native.available(),
                                  reason="needs the native core")


def run(**kw):
    return harness.run_cell(ROOT, tiny_cell(CELL), 2147483999, 0.5, False,
                            platform="cpu", paths=tiny_spec()["paths"], **kw)


def test_the_new_entries_of_benchmark_json_by_membership():
    spec = benchmark_json()
    cfg = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert cfg["reduced"] == ["precision", "n"]
    assert cfg["file"] == f"benchmark/configs/{CONFIG}.json"
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "pump_poinv_n49152", 1)
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1
    assert sum(w["config"] == CONFIG for w in spec["workloads"]) == 1
    by_name = {m["name"]: m for m in spec["end_to_end"] + spec["per_layer"]}
    layers = {"member_gap_s": "scheduler"}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "tile_solve_s"
        assert by_name[name]["layer"] == layers.get(name, "ops kernels")
    # the cell joins what the out-of-core cell reports, but the roofline
    # whose count is N^3/3, the three eviction metrics (nothing is
    # evicted) and the two whose lists tests of the accepted benchmark
    # hold letter for letter (``test_bench_donation.py``,
    # ``test_bench_byte_cut.py``)
    ooc = {n for n, m in by_name.items()
           if "ooc_pump_n90112" in m.get("workloads", ())}
    mine = {n for n, m in by_name.items() if CELL in m.get("workloads", ())}
    assert ooc - mine == {"dpotrf_roofline.tile", "evictions_per_tile",
                          "evict_home_mb_per_solve", "evict_wait_s",
                          "donated_outputs_pct", "byte_cut_programs_pct"}
    assert mine - ooc == NEW_METRICS
    assert {"tile_solve_s", "tile_home_s", "h2d_per_tile",
            "d2h_per_result", "device_idle_pct", "idle_in_wait_pct"} <= mine


def test_the_configuration_says_what_it_runs():
    cfg = tiny_cell(CELL).config
    spec = benchmark_json()
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert cfg["source"] == entry["source"] and len(entry["source"]) <= 200
    assert set(cfg["reduced"]) == set(cfg["reduced_why"]) == {"precision",
                                                              "n"}
    assert set(cfg["assumed"]) == set(cfg["assumed_why"])
    assert set(cfg["limits"]) == set(cfg["limits_why"]) == {
        "inverse_residual", "diag_residual"}
    assert cfg["control"]["options"] == {"use_pallas": True,
                                         "bf16_updates": True}
    assert cfg["architecture"] is None and cfg["uplo"] == "lower"


@needs_native
def test_the_rehearsal_runs_the_cell_and_reports_its_three_metrics():
    r = run()
    assert tuple(r) == harness.RESULT_KEYS
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["metrics"]) == {"tile_solve_s", "tile_home_s", "setup_s"}


@needs_native
def test_the_control_fails_a_limit(capsys):
    r = run(control=True)
    assert r["correct"] is False and r["failed"] == r["attempted"] >= 3
    out = capsys.readouterr().out
    assert "FAILED solve" in out and "violations []" in out


# -- the reference -----------------------------------------------------------

def _problem(seed=7):
    import jax

    cell = tiny_cell(CELL)
    p = cell.reference.make_problem(seed, cell.config, cell.traffic,
                                    jax.devices()[:1])
    cell.reference.prepare(p)
    return cell, p


def _dense(p):
    n, nb = p["n"], p["nb"]
    a = np.zeros((n, n))
    for (i, j), t in p["tiles"].items():
        a[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb] = t
    return np.tril(a) + np.tril(a, -1).T


def _lower_tiles(p, w, dtype=np.float64):
    nb = p["nb"]
    return {(i, j): w[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb].astype(dtype)
            for (i, j) in p["tiles"]}


def test_the_reference_compares_what_it_says():
    cell, p = _problem()
    n, nb, nt = p["n"], p["nb"], p["nt"]
    assert (n, nb, nt) == (128, 32, 4) and len(p["tiles"]) == 10
    assert len(p["rows"]) == 4 * nt and n - 1 in p["rows"]
    a = _dense(p)
    np.testing.assert_array_equal(a, a.T)
    assert np.linalg.cond(a) < 20
    # the sampled columns are the matrix's, from the hash alone
    np.testing.assert_array_equal(p["cols"], a[:, p["rows"]])
    _, again = _problem()
    np.testing.assert_array_equal(again["tiles"][(3, 1)], p["tiles"][(3, 1)])
    _, other = _problem(2 ** 31 + 7)
    assert np.abs(other["tiles"][(3, 1)] - p["tiles"][(3, 1)]).mean() > 0.2
    w = np.linalg.inv(a)
    good = cell.reference.compare(p, _lower_tiles(p, w))
    assert good["inverse_residual"] < 1e-13
    assert good["diag_residual"] <= good["inverse_residual"]
    # the upper triangle of a diagonal tile is nobody's: only the lower
    # one is read
    junk = _lower_tiles(p, w)
    junk[(2, 2)] = np.tril(junk[(2, 2)]) + np.triu(np.full((nb, nb), 9.), 1)
    assert cell.reference.compare(p, junk) == good
    # float32 tiles of the exact inverse: what rounding alone reads
    f32 = cell.reference.compare(p, _lower_tiles(p, w, np.float32))
    assert 1e-9 < f32["inverse_residual"] < 1e-6
    missing = _lower_tiles(p, w)
    del missing[(3, 0)]
    assert cell.reference.compare(p, missing)["inverse_residual"] \
        == float("inf")
    short = _lower_tiles(p, w)
    short[(1, 0)] = short[(1, 0)][:, :-1]
    assert cell.reference.compare(p, short)["diag_residual"] == float("inf")


@pytest.mark.parametrize("fault", [
    "a tile at the factor's version", "a tile at trtri's version",
    "a diagonal tile at trtri's version", "lauum skipped",
    "trtri skipped", "a row of tiles one step of lauum early"])
def test_a_planted_fault_fails_compare(fault):
    cell, p = _problem(11)
    limits = cell.config["limits"]
    nb, nt = p["nb"], p["nt"]
    a = _dense(p)
    chol = np.linalg.cholesky(a)
    winv = np.linalg.inv(chol)
    tiles = _lower_tiles(p, np.linalg.inv(a))
    assert harness.within_limits(cell.reference.compare(p, tiles), limits)

    def block(m, i, j):
        return m[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb]
    if fault == "a tile at the factor's version":
        tiles[(2, 1)] = block(chol, 2, 1)
    elif fault == "a tile at trtri's version":
        tiles[(3, 0)] = block(winv, 3, 0)
    elif fault == "a diagonal tile at trtri's version":
        tiles[(1, 1)] = block(winv, 1, 1)
    elif fault == "lauum skipped":
        tiles = _lower_tiles(p, winv)
    elif fault == "trtri skipped":
        tiles = _lower_tiles(p, chol.T @ chol)
    else:
        # lauum's row 1 without the last step's update (k = NT - 1)
        last = winv[(nt - 1) * nb:]
        for j in range(2):
            tiles[(1, j)] = tiles[(1, j)] - block(last.T @ last, 1, j)
    numbers = cell.reference.compare(p, tiles)
    assert not harness.within_limits(numbers, limits), numbers
    assert numbers["inverse_residual"] > 100 * limits["inverse_residual"]


# -- the driver ---------------------------------------------------------------

@needs_native
def test_every_solve_is_held_to_the_compounds_guarantees():
    """Three solves: in and home the lower matrix once each, the members'
    counters 0, three plan hits from the second solve on; a member that
    sends something home is a violation."""
    import jax

    from parsec_tpu.dsl import attach_plan

    attach_plan.clear()
    cell, p = _problem(3)
    session = harness.Session(cell, jax.devices(), "cpu")
    try:
        for _ in range(3):
            s = session.solve(p)
            assert s["ok"] and s["violations"] == []
        c = session.driver.counters()
        lower = ops_count.lower_tiles_bytes(p["n"], p["nb"])
        assert c["bytes_in"] == c["bytes_out"] == 3 * lower
        assert c["executed_tasks"] == 3 * counts.poinv_ntasks(p["nt"])
        assert c["evictions"] == 0
        # a home set that keeps what a later member rewrites: planted
        from parsec_tpu.dsl import native_exec

        bind = native_exec.NativeExecutor._bind

        def forgetful(self, plan, held=()):
            return bind(self, plan, ())
        native_exec.NativeExecutor._bind = forgetful
        try:
            s = session.solve(p)
        finally:
            native_exec.NativeExecutor._bind = bind
        assert not s["ok"]
        assert any("went home from a member" in v for v in s["violations"])
        assert any("the lower matrix is" in v for v in s["violations"])
    finally:
        session.close()


def test_a_program_without_poinv_is_refused(monkeypatch):
    import parsec_tpu.ops as ops

    spec = tiny_spec()
    path = harness.find_file(ROOT, spec["paths"], "drivers/pump_poinv.py")
    monkeypatch.delattr(ops, "poinv")
    with pytest.raises(harness.BenchError, match="no parsec_tpu.ops.poinv"):
        harness.load_module(path)


@needs_native
def test_a_program_whose_executor_takes_no_compound_is_refused(monkeypatch):
    from parsec_tpu.dsl import native_exec

    spec = tiny_spec()
    path = harness.find_file(ROOT, spec["paths"], "drivers/pump_poinv.py")
    init = native_exec.NativeExecutor.__init__

    def parents(self, tp, **kw):
        tp.ptg  # the parent's executor reads a PTG taskpool's ``ptg``
        return init(self, tp, **kw)
    monkeypatch.setattr(native_exec.NativeExecutor, "__init__", parents)
    with pytest.raises(harness.BenchError, match="takes no compound"):
        harness.load_module(path)


# -- the counts and the readers ------------------------------------------------

def test_the_counts_at_the_cells_size():
    assert counts.member_ntasks(24) == 24 + 276 + 276 + 2024 == 2600
    assert counts.poinv_ntasks(24) == 7800
    assert counts.poinv_flops(49152) == pytest.approx(1.1875e14, rel=1e-3)
    assert counts.member_flops(49152) == ops_count.dpotrf_flops(49152)
    assert ops_count.lower_tiles_bytes(49152, 2048) == 300 << 24
    assert len(set(counts.CLASSES)) == 12


@needs_native
@pytest.mark.parametrize("nt", [3, 5])
def test_the_counts_are_those_of_the_captured_graphs(nt):
    from parsec_tpu.datadist import TiledMatrix
    from parsec_tpu.dsl.graph import capture

    cell = tiny_cell(CELL)
    A = TiledMatrix(nt * 4, nt * 4, 4, 4, name="A", dtype=np.float32)
    comp = cell.driver.poinv_compound(A, {})
    classes = (counts.POTRF_CLASSES, counts.TRTRI_CLASSES,
               counts.LAUUM_CLASSES)
    assert len(comp.members) == counts.MEMBERS
    for member, names in zip(comp.members, classes):
        g = capture(member, ranks=[0])
        assert len(g.nodes) == counts.member_ntasks(nt)
        assert {c for c, _ in g.nodes} == set(names)


class _Trace:
    busy_s, solves = 9.0, 2


def _run(counters, trace=None):
    cell = tiny_cell(CELL)
    cell.config.update(n=49152, nb=2048)
    return harness.Run(cell=cell, readings=[], counters=counters, solves=2,
                       compiles={}, memory={},
                       peaks={"bf16_flops_per_s": 197e12}, trace=trace)


def test_the_trace_readers_split_device_time_by_member(monkeypatch):
    r = tiny_cell(CELL).readers
    run = _run({}, trace=_Trace())
    m = modules.Modules(solves=2, runs={}, seconds={
        "jit__wave_gemm": 2.0, "jit__wave_syrk": 0.4, "jit_potrf_tpu": 0.3,
        "jit__wave_trsm": 0.5,
        "jit__wave_trtri_gemm": 2.2, "jit__wave_trtri_trsm_r": 0.5,
        "jit__wave_trtri_trsm_l": 0.5, "jit_trtri_diag_tpu": 0.2,
        "jit_trtri_gemm_tpu": 0.2,
        "jit__wave_lauum_gemm": 2.4, "jit__wave_lauum_syrk": 0.4,
        "jit_lauum_trmm_tpu": 0.1, "jit__wave_lauum_diag": 0.1,
        "jit__wave": 5.0, "jit_call": 7.0})
    monkeypatch.setattr(modules, "of_run", lambda run: m)
    third = counts.member_flops(49152)
    assert r["trtri_roofline"].read(run) == pytest.approx(
        100 * third / 197e12 / 1.8)
    assert r["lauum_roofline"].read(run) == pytest.approx(
        100 * third / 197e12 / 1.5)
    assert r["poinv_roofline"].read(run) == pytest.approx(
        100 * 3 * third / 197e12 / 4.5)
    for name in ("trtri_roofline", "lauum_roofline", "poinv_roofline"):
        assert r[name].read(run) < 100 / 6
    # a program whose modules carry no class of the two new DAGs (the
    # Cholesky's own ``trtri`` class among them)
    m.seconds = {"jit__wave_gemm": 2.0, "jit_trtri_tpu": 1.0,
                 "jit__wave_trtri": 1.0}
    assert r["trtri_roofline"].read(run) is None
    assert r["lauum_roofline"].read(run) is None
    # an untraced run
    monkeypatch.undo()
    for name in NEW_METRICS:
        assert r[name].read(_run({})) is None
    assert modules.class_of("jit__wave_trtri_gemm", counts.CLASSES) \
        == "trtri_gemm"
    assert modules.class_of("jit__wave_gemm", counts.CLASSES) == "gemm"
    assert modules.class_of("jit_lauum_gemm_bf16", counts.CLASSES) \
        == "lauum_gemm"


def test_the_gap_reader_on_a_synthetic_trace(monkeypatch):
    ms = 1_000_000
    spans = [
        sp.Span("pump:member", 0, 100 * ms, 1, {"member": 0}),
        sp.Span("pump:done", 90 * ms, 98 * ms, 1, {}),
        sp.Span("pump:member", 101 * ms, 200 * ms, 1, {"member": 1}),
        sp.Span("pump:member_gap", 101 * ms, 104 * ms, 1, {"member": 1}),
        sp.Span("dev:submit_batch", 104 * ms, 120 * ms, 1, {}),
        sp.Span("dev:dispatch", 107 * ms, 108 * ms, 1, {}),
        sp.Span("dev:dispatch", 110 * ms, 111 * ms, 1, {}),
        sp.Span("pump:done", 190 * ms, 199 * ms, 1, {}),
        sp.Span("pump:member", 200 * ms, 300 * ms, 1, {"member": 2}),
        sp.Span("pump:member_gap", 200 * ms, 201 * ms, 1, {"member": 2}),
        sp.Span("dev:dispatch", 203 * ms, 204 * ms, 1, {}),
        # another thread's dispatch is not the pump's
        sp.Span("dev:dispatch", 200 * ms, 201 * ms, 2, {}),
        # a second solve with one gap
        sp.Span("pump:done", 480 * ms, 490 * ms, 1, {}),
        sp.Span("pump:member_gap", 500 * ms, 501 * ms, 1, {"member": 1}),
        sp.Span("dev:dispatch", 505 * ms, 506 * ms, 1, {})]
    trace = sp.Trace(spans, [(0, 400 * ms), (450 * ms, 600 * ms)], {})
    reader = tiny_cell(CELL).readers["member_gap_s"]
    monkeypatch.setattr(sp, "of_run", lambda run: object())
    monkeypatch.setattr(sp, "load", lambda path: trace)
    monkeypatch.setattr(tr, "find_xplane", lambda d: "planted")
    # (107 - 98) + (203 - 199) + (505 - 490) ms over two solves
    assert reader.read(_run({}, trace=_Trace())) == pytest.approx(0.014)
    trace.spans[:] = [s for s in spans if s.name != "pump:member_gap"]
    assert reader.read(_run({}, trace=_Trace())) is None


def test_the_gap_reader_finds_nothing_in_a_recorded_program_without_it(
        tmp_path, monkeypatch):
    from test_bench_donation import RECORDED, _trace_at

    run = _trace_at(tmp_path, monkeypatch, RECORDED)
    assert sp.of_run(run) is not None      # the spans are there
    reader = harness.load_module(harness.find_reader(
        ROOT, benchmark_json()["paths"], "member_gap_s"))
    assert reader.read(run) is None        # the gap is not


@needs_native
def test_a_solve_traced_here_has_its_two_gaps_nested_in_their_members(
        tmp_path):
    import jax

    cell, p = _problem(5)
    out = tmp_path / "traced"
    session = harness.Session(cell, jax.devices(), "cpu")
    try:
        assert session.solve(p)["ok"]
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 2
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(out), profiler_options=opts)
        try:
            assert session.solve(p)["ok"]
        finally:
            jax.profiler.stop_trace()
    finally:
        session.close()
    trace = sp.load(tr.find_xplane(str(out)))
    spans = sp.nest(sp.clip_spans(trace.spans, trace.windows))
    members = [s for s in spans if s.name == "pump:member"]
    assert [s.args["member"] for s in members] == [0, 1, 2]
    assert all(s.args["tasks"] == counts.member_ntasks(p["nt"])
               and s.parent is None for s in members)
    gaps = [s for s in spans if s.name == "pump:member_gap"]
    assert [s.args["member"] for s in gaps] == [1, 2]
    assert [s.parent for s in gaps] == members[1:]
    lower = ops_count.lower_tiles_bytes(p["n"], p["nb"])
    assert all(s.args["kept_tiles"] == len(p["tiles"])
               and s.args["kept_bytes"] == lower for s in gaps)
    # every span of a member's run is inside its ``pump:member``
    for s in spans:
        if s.name in ("dev:submit_batch", "pump:done"):
            top = s
            while top.parent is not None:
                top = top.parent
            assert top.name == "pump:member", s.name
    # a gap holds the successor's first pop and ends before its first
    # batch goes to the device
    for gap, mem in zip(gaps, members[1:]):
        first = min(s.start for s in spans if s.name == "dev:submit_batch"
                    and s.start >= mem.start)
        assert gap.end <= first
        assert any(s.name == "pump:pop" and s.parent is gap for s in spans)
    assert len({s.args["pool"] for s in members}) == 3
    builds = [s for s in spans if s.name == "attach:build"]
    assert len(builds) == 3 and {s.args["plan"] for s in builds} == {"hit"}
