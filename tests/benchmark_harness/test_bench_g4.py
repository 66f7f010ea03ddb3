"""The four-accelerator cell (``tile_g4_n98304``: ONE ``Context`` over four
device modules): its rehearsal on the CPU backend's virtual devices
through the whole harness, its control failing the check, each guarantee
of the configuration's file broken when it is planted, its four readers on
the tiny run's own spans and counters and on a program without them, its
entries by membership.  Counts, never times."""

import inspect
import json
import os
import types

import pytest

from benchmark import harness, ops_count
from benchmark.trace import reduce as tr
from benchmark.trace import spans
from parsec_tpu import native

from bench_testlib import ROOT, benchmark_json, tiny_cell, tiny_spec
from test_bench_spans_program import traced_solves

CELL = "tile_g4_n98304"
CONFIG = "spotrf_tile_nb4096_g4"
NEW_METRICS = ("chip_busy_skew_pct", "d2d_copies_per_tile",
               "d2d_wait_us_per_task", "placed_by_advice_pct")
JOINED = ("device_idle_pct", "d2d_mb_per_solve", "tasks_per_program",
          "sched_us_per_task", "submit_us_per_task",
          "dispatch_us_per_program", "h2d_per_tile", "d2h_per_result",
          "compiles_in_window", "idle_in_dispatch_pct",
          "idle_in_submit_pct", "idle_in_transfer_pct", "idle_in_sched_pct",
          "idle_unattributed_pct", "dpotrf_roofline.tile", "flush_s",
          "writeback_s")
#: readers that take "rank r drives chip r" for granted
#: (``benchmark/trace/waits.py``, ``phases.py``): four modules of ONE rank
#: would read another chip's threads, so the cell does not list them
NOT_JOINED = ("idle_in_wait_pct", "gil_wait_pct", "donated_outputs_pct",
              "submit_laps_cover_pct", "stage_walk_us_per_task")
pytestmark = pytest.mark.skipif(not native.available(),
                                reason="needs the native core")


def run(**kw):
    return harness.run_cell(ROOT, tiny_cell(CELL), 2147483999, 0.5, False,
                            platform="cpu", paths=tiny_spec()["paths"], **kw)


def test_the_rehearsal_runs_the_cell_and_reports_its_three_metrics():
    r = run()
    assert tuple(r) == harness.RESULT_KEYS
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["metrics"]) == {"tile_solve_s", "tile_home_s", "setup_s"}


def test_the_control_fails_the_check(capsys):
    r = run(control=True)
    assert r["correct"] is False and r["failed"] == r["attempted"] >= 3
    out = capsys.readouterr().out
    assert "FAILED solve" in out and "violations []" in out


def test_an_update_skipped_in_the_timed_path_fails_the_check(monkeypatch):
    from parsec_tpu.ops import tiles

    monkeypatch.setattr(tiles, "gemm_update_tpu",
                        lambda A, B1, B2, **_: A + 0.0)
    r = run()
    assert r["correct"] is False and r["failed"] > 0


# -- the guarantees, each broken when it is planted -------------------------

@pytest.fixture
def session():
    import jax

    cell = tiny_cell(CELL)
    devices = jax.devices()
    problem = cell.reference.make_problem(7, cell.config, cell.traffic,
                                          devices[:cell.chips])
    cell.reference.prepare(problem)
    s = harness.Session(cell, devices, "cpu")
    try:
        yield s, problem
    finally:
        s.close()


def test_a_sound_solve_breaks_no_guarantee(session):
    s, problem = session
    for _ in range(2):
        got = s.solve(problem)
        assert got["ok"] and got["violations"] == []
    drv = s.driver
    assert len(drv.devs) == 4 and drv.ctx.nranks == 1
    assert len({d.jdev.id for d in drv.devs}) == 4
    assert drv.counters()["fallbacks"] == 0


def test_a_tile_bounced_over_the_host_breaks_the_bytes(session, monkeypatch):
    """A staging walk that takes a tile which has gone home from the HOST,
    though a peer holds it: the bytes host-to-chip exceed the matrix."""
    from parsec_tpu.data.data import Data

    def host_first(self, device_index):
        with self.lock:
            return self.newest_copy()   # (the host's copy wins a tie)

    s, problem = session
    monkeypatch.setattr(Data, "hold_source", host_first)
    got = s.solve(problem)
    assert not got["ok"]
    assert any(v.startswith("bytes_in ") for v in got["violations"])


def test_a_chip_that_executed_anothers_share_breaks_the_shares(
        session, monkeypatch):
    from parsec_tpu.device import device as devmod

    s, problem = session
    first = s.driver.devs[0]
    place = devmod._place

    def misplaced(context, task, accs):
        best = place(context, task, accs)
        if task.task_class.name == "potrf":   # chip 4's go to chip 1
            best = next(e for e in accs if e[0] is first)
        return best

    monkeypatch.setattr(devmod, "_place", misplaced)
    got = s.solve(problem)
    assert not got["ok"]
    assert any("the advice gives them" in v for v in got["violations"])
    # (the factor itself is still right: coherence does not depend on it)
    assert harness.within_limits(got["numbers"], s.limits)


def test_a_chip_with_no_chip_to_chip_landing_breaks_the_guarantee(
        session, monkeypatch):
    s, problem = session
    drv = s.driver
    before = [d.stats["bytes_d2d"] for d in drv.devs]
    each = drv._each

    def frozen(dev):
        out = each(dev)
        if dev is drv.devs[2]:
            out["bytes_d2d"] = before[2]
        return out

    monkeypatch.setattr(drv, "_each", frozen)
    got = s.solve(problem)
    assert any("landed no tile chip to chip" in v for v in got["violations"])


def test_an_evicted_dirty_tile_and_a_fallback_break_the_guarantees(session):
    s, problem = session
    drv = s.driver
    counters = drv.counters

    def moved():
        out = counters()
        moved.calls += 1
        if moved.calls > 1:                 # (after the solve)
            out["evict_dirty"] += 1
            out["fallbacks"] += 1
        return out

    moved.calls = 0
    drv.counters = moved
    got = s.solve(problem)
    assert "a dirty tile was evicted" in got["violations"]
    assert "1 fallbacks ran" in got["violations"]


def test_the_shares_are_reckoned_from_the_map():
    cell = harness.load_cell(ROOT, CELL)
    shares = cell.driver.advised_shares
    assert shares(24, 2, 2) == [650, 572, 650, 728]
    assert sum(shares(24, 2, 2)) == ops_count.dpotrf_ntasks(24) == 2600
    assert shares(6, 2, 2) == [14, 8, 14, 20]
    assert shares(3, 1, 1) == [ops_count.dpotrf_ntasks(3)]


def test_the_driver_refuses_a_program_whose_context_takes_no_accelerators(
        monkeypatch):
    """The parent's ``Context``: the cell fails when its files are loaded,
    before a tile is made."""
    from parsec_tpu import Context

    assert "accelerators" in inspect.signature(Context.__init__).parameters

    def old_init(self, nb_cores=None, *, scheduler=None, devices=None,
                 rank=0, nranks=1, comm=None):
        raise AssertionError("never built")

    monkeypatch.setattr(Context, "__init__", old_init)
    with pytest.raises(harness.BenchError, match="accelerators"):
        harness.load_cell(ROOT, CELL)


def test_the_driver_refuses_fewer_chips_than_accelerators():
    cell = tiny_cell(CELL)
    with pytest.raises(harness.BenchError, match="4 accelerators"):
        cell.driver.open(cell.config, cell.traffic, {}, [object()] * 3, "cpu")


# -- the configuration and the entries --------------------------------------

def test_the_configuration_is_the_deployment():
    cell = harness.load_cell(ROOT, CELL)
    c = cell.config
    assert (c["n"], c["nb"], c["n"] // c["nb"]) == (98304, 4096, 24)
    assert c["accelerators"] == 4 and c["device_grid"] == [2, 2]
    assert c["grid"] == [1, 1] and c["architecture"] is None
    assert c["reduced"] == ["precision", "n"]
    assert set(c["reduced_why"]) == set(c["reduced"])
    assert set(c["assumed_why"]) == set(c["assumed"]) and "nb" in c["assumed"]
    assert c["reference"] == "reference/spotrf_g4_hashed_tiles.py"
    users = [n for n in os.listdir(os.path.join(ROOT, "benchmark", "configs"))
             if json.load(open(os.path.join(ROOT, "benchmark", "configs", n)))
             .get("reference") == c["reference"]]
    assert users == [f"{CONFIG}.json"]  # (its own copy, nobody else's)
    assert set(c["limits"]) == set(c["limits_why"]) \
        == {"diagonal_error", "offdiag_error"}
    assert c["control"]["options"] == {"use_pallas": True,
                                       "bf16_updates": True}
    assert ops_count.lower_tiles_bytes(c["n"], c["nb"]) == 300 * 64 << 20
    t = cell.traffic
    assert (t["driver"], t["loop"], t["clients"]) == ("context_g4",
                                                      "closed", 1)
    assert (t["warmup_solves"], t["discard_solves"], t["traced_solves"]) \
        == (2, 0, 1)
    tiny = tiny_cell(CELL)
    assert tiny.traffic["rehearsal"] == {"n": 192, "nb": 32}


def test_the_new_entries_of_benchmark_json_by_membership():
    spec = benchmark_json()
    w = {x["name"]: x for x in spec["workloads"]}[CELL]
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, "context_g4", 4)
    assert len(w["why"]) <= 200
    cells = spec["workloads"]
    assert sum(x["chips"] == 4 for x in cells) <= len(cells) // 2
    c = {x["name"]: x for x in spec["configs"]}[CONFIG]
    assert c["file"] == f"benchmark/configs/{CONFIG}.json"
    assert c["reduced"] == ["precision", "n"]
    with open(os.path.join(ROOT, c["file"])) as f:
        assert json.load(f)["source"] == c["source"]
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert CELL in e2e["tile_solve_s"]["workloads"]
    assert CELL in e2e["tile_home_s"]["workloads"]
    per = {m["name"]: m for m in spec["per_layer"]}
    for name in NEW_METRICS:
        assert per[name]["workloads"] == [CELL]
        assert per[name]["moves"] == "tile_solve_s"
        assert os.path.isfile(os.path.join(ROOT, "benchmark", "layers",
                                           f"{name}.py"))
    for name in JOINED:
        assert CELL in per[name]["workloads"], name
    for name in NOT_JOINED:
        assert CELL not in per[name]["workloads"], name
    cell = harness.load_cell(ROOT, CELL)
    assert set(NEW_METRICS) | set(JOINED) | {"setup_compiles"} \
        == set(cell.readers)


# -- the readers, on the tiny run's own spans and counters --------------------

@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    return traced_solves(CELL, tmp_path_factory.mktemp("g4"), solves=1)


def test_every_device_span_carries_its_module(traced):
    _trace, nested, ntasks = traced
    dev_spans = [s for s in nested if s.name.startswith("dev:")]
    assert dev_spans and all("dev" in s.args and "rank" in s.args
                             for s in dev_spans)
    assert {int(s.args["dev"]) for s in dev_spans} == {1, 2, 3, 4}
    assert sum(s.args["n"] for s in nested
               if s.name in spans.TASK_SPANS) == ntasks
    # a module's task spans lie on its own manager's thread
    by_dev = {}
    for s in nested:
        if s.name in spans.TASK_SPANS:
            by_dev.setdefault(int(s.args["dev"]), set()).add(s.thread)
    assert all(len(t) == 1 for t in by_dev.values())
    assert len({next(iter(t)) for t in by_dev.values()}) == 4


def test_the_landings_are_spans_with_their_source(traced):
    _trace, nested, _ = traced
    lands = [s for s in nested if s.name == "dev:d2d"]
    assert lands and all(s.parent.name == "dev:stage_args" for s in lands)
    for s in lands:
        assert int(s.args["src"]) in {1, 2, 3, 4} - {int(s.args["dev"])}
        assert int(s.args["bytes"]) == int(s.args["tiles"]) * 32 * 32 * 4
    # nothing came over the host but each tile's first version
    h2d = [s for s in nested if s.name == "dev:h2d"]
    assert sum(int(s.args["tiles"]) for s in h2d) == 21


def _summary_of(nested, solves=1):
    total = {}
    for s in nested:
        total[s.name] = total.get(s.name, 0) + s.end - s.start
    tasks = sum(int(s.args.get("n", 1)) for s in nested
                if s.name in spans.TASK_SPANS)
    return spans.Summary(
        solves=solves, tasks=tasks / solves,
        programs=sum(s.name == "dev:dispatch" for s in nested) / solves,
        self_ns={}, total_ns=total, waited_us=0.0, h2d_wait_ns=0, idle_ns={})


def test_d2d_wait_reads_the_tiny_runs_spans(traced, monkeypatch):
    _trace, nested, ntasks = traced
    summary = _summary_of(nested)
    read = harness.load_cell(ROOT, CELL).readers["d2d_wait_us_per_task"].read
    monkeypatch.setattr(spans, "of_run", lambda run: summary)
    got = read(types.SimpleNamespace(trace=object()))
    assert got == summary.total_ns["dev:d2d"] / 1e3 / ntasks > 0
    # a program without the span (the parent), and an untraced run
    del summary.total_ns["dev:d2d"]
    assert read(types.SimpleNamespace(trace=object())) is None
    monkeypatch.setattr(spans, "of_run", lambda run: None)
    assert read(types.SimpleNamespace(trace=None)) is None


def _run_of(counters, solves=1, trace=None):
    return harness.Run(cell=tiny_cell(CELL), readings=[], counters=counters,
                       solves=solves, compiles={}, memory={}, peaks=None,
                       trace=trace)


def test_the_counter_readers_read_the_tiny_runs_counters(session):
    s, problem = session
    drv = s.driver
    before = drv.counters()
    assert s.solve(problem)["ok"]
    after = drv.counters()
    run = _run_of({k: after[k] - before[k] for k in after})
    r = tiny_cell(CELL).readers
    tiles = 6 * 7 // 2
    copies = r["d2d_copies_per_tile"].read(run)
    assert copies == run.counters["d2d_tiles"] / tiles and 1.0 < copies < 3.0
    assert r["placed_by_advice_pct"].read(run) == 100.0
    assert run.counters["selected_by_advice"] == tiles
    assert run.counters["selected_by_owner"] \
        == ops_count.dpotrf_ntasks(6) - tiles
    # the joined counter readers find their counters too
    assert r["h2d_per_tile"].read(run) == r["d2h_per_result"].read(run) == 1.0
    assert r["d2d_mb_per_solve"].read(run) \
        == run.counters["d2d_tiles"] * 32 * 32 * 4 / 1e6
    assert r["tasks_per_program"].read(run) >= 1.0


def test_the_counter_readers_find_nothing_in_a_program_without_them():
    r = tiny_cell(CELL).readers
    parent = _run_of({"bytes_d2d": 0, "executed_tasks": 56})
    assert r["d2d_copies_per_tile"].read(parent) is None
    assert r["placed_by_advice_pct"].read(parent) is None
    # one accelerator: the counters are there and nothing was chosen
    one = _run_of({"selected_by_owner": 0, "selected_by_advice": 0,
                   "selected_by_bytes": 0, "selected_by_load": 0,
                   "d2d_tiles": 0})
    assert r["placed_by_advice_pct"].read(one) is None
    assert r["d2d_copies_per_tile"].read(one) == 0.0
    mixed = _run_of({"selected_by_owner": 5, "selected_by_advice": 1,
                     "selected_by_bytes": 1, "selected_by_load": 1})
    assert r["placed_by_advice_pct"].read(mixed) == 75.0


def test_chip_busy_skew_reads_the_busiest_and_the_idlest_chip():
    read = tiny_cell(CELL).readers["chip_busy_skew_pct"].read

    def summary(busy):
        return tr.Summary(window_s=5.0, solves=1, busy_by_chip=busy,
                          device_ops=[], idle_gaps=[])

    assert read(_run_of({}, trace=summary({0: 4.0, 1: 3.0, 2: 3.5, 3: 2.0}))) \
        == 50.0
    assert read(_run_of({}, trace=summary({0: 2.0, 1: 2.0}))) == 0.0
    assert read(_run_of({}, trace=summary({0: 2.0}))) is None   # one chip
    assert read(_run_of({})) is None                            # untraced
