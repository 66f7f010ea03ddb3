"""``benchmark/trace/spans.py``: self time, clipping, the attribution of
idle time and the per-layer metrics that read them, on synthetic events;
and every new reader on a trace recorded on a TPU v5e with the program's
spans in it (``recorded/tiny_pump_spans.xplane.pb``: two solves of the
pump driver at N=1024, nb=256)."""

import os
import types

import pytest

from benchmark import harness
from benchmark.trace import reduce as tr
from benchmark.trace import spans as sp

from bench_testlib import ROOT, benchmark_json

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "recorded", "tiny_pump_spans.xplane.pb")
NEW = ["sched_us_per_task", "queue_wait_us_per_task", "submit_us_per_task",
       "dispatch_us_per_program", "stage_wait_us_per_task", "writeback_s",
       "idle_in_dispatch_pct", "idle_in_submit_pct", "idle_in_transfer_pct",
       "idle_in_sched_pct", "idle_unattributed_pct"]
IDLE = [m for m in NEW if m.startswith("idle_")]


def S(name, start, end, thread=1, **args):
    return sp.Span(name, start, end, thread, args)


def self_times(spans):
    return {(s.name, s.start): s.self_ns for s in sp.nest(spans)}


@pytest.mark.parametrize("spans, expected", [
    # a parent with one nested child
    ([S("dev:wave", 0, 100), S("dev:dispatch", 10, 70)],
     {("dev:wave", 0): 40, ("dev:dispatch", 10): 60}),
    # sibling children, and a grandchild that only its parent pays for
    ([S("dev:wave", 0, 100), S("dev:stage_args", 0, 20),
      S("dev:h2d", 5, 15), S("dev:dispatch", 20, 90)],
     {("dev:wave", 0): 10, ("dev:stage_args", 0): 10, ("dev:h2d", 5): 10,
      ("dev:dispatch", 20): 70}),
    # two spans one after the other: neither is the other's child
    ([S("pump:pop", 0, 10), S("pump:done", 10, 30)],
     {("pump:pop", 0): 10, ("pump:done", 10): 20}),
    # the same stretch on another thread is no child
    ([S("dev:submit_batch", 0, 100, thread=1),
      S("dev:stage_in", 20, 80, thread=2)],
     {("dev:submit_batch", 0): 100, ("dev:stage_in", 20): 60}),
    # a child that outlasts its parent is cut to it
    ([S("dev:wave", 0, 50), S("dev:epilog", 40, 60)],
     {("dev:wave", 0): 40, ("dev:epilog", 40): 10}),
])
def test_self_time_is_duration_minus_children_on_the_same_thread(
        spans, expected):
    assert self_times(spans) == expected


def test_parents_follow_the_nesting_on_a_thread():
    spans = sp.nest([S("dev:submit_batch", 0, 100), S("dev:wave", 10, 90),
                     S("dev:dispatch", 20, 30), S("pump:done", 100, 110),
                     S("dev:writeback", 15, 25, thread=2)])
    parent = {s.name: s.parent.name if s.parent else None for s in spans}
    assert parent == {"dev:submit_batch": None, "dev:wave": "dev:submit_batch",
                      "dev:dispatch": "dev:wave", "pump:done": None,
                      "dev:writeback": None}


@pytest.mark.parametrize("span, windows, pieces", [
    ((10, 20), [(0, 100)], [(10, 20)]),           # inside
    ((90, 120), [(0, 100)], [(90, 100)]),         # cut at the window's end
    ((-5, 30), [(0, 100)], [(0, 30)]),            # and at its start
    ((100, 150), [(0, 100), (200, 300)], []),     # between two solves
    ((50, 250), [(0, 100), (200, 300)], [(50, 100), (200, 250)]),
])
def test_spans_are_clipped_at_a_windows_edge(span, windows, pieces):
    got = sp.clip_spans([S("dev:wave", *span, n=4)], windows)
    assert [(s.start, s.end) for s in got] == pieces
    assert all(s.args == {"n": 4} for s in got)


@pytest.mark.parametrize("a, b, inside, outside", [
    ([(0, 10), (20, 30)], [(5, 22), (28, 40)],
     [(5, 10), (20, 22), (28, 30)], [(0, 5), (22, 28)]),
    ([(0, 10)], [], [], [(0, 10)]),
    ([(0, 100)], [(10, 20), (30, 40), (90, 120)],
     [(10, 20), (30, 40), (90, 100)], [(0, 10), (20, 30), (40, 90)]),
    ([(0, 10), (10, 20), (50, 60)], [(5, 55)],
     [(5, 10), (10, 20), (50, 55)], [(0, 5), (55, 60)]),
    ([(5, 6)], [(0, 10)], [(5, 6)], []),
])
def test_intersect_and_subtract_cut_one_list_by_another(a, b, inside,
                                                        outside):
    assert sp.intersect(a, b) == inside
    assert sp.subtract(a, b) == outside
    assert tr.length(inside) + tr.length(outside) == tr.length(a)
    assert inside == tr.clip(a, b)     # the quadratic one of reduce.py


@pytest.mark.parametrize("spans, expected", [
    # one thread: the innermost span of each stretch counts
    ([S("dev:submit_batch", 0, 60), S("dev:dispatch", 10, 40),
      S("pump:done", 60, 80)],
     {"dispatch": 30, "submit": 30, "transfer": 0, "sched": 20,
      "unattributed": 20}),
    # two threads over the same stretch: dispatch wins over transfer,
    # transfer over scheduler, and no nanosecond is counted twice
    ([S("pump:pop", 0, 100, thread=1), S("dev:stage_in", 20, 60, thread=2),
      S("dev:dispatch", 40, 50, thread=3)],
     {"dispatch": 10, "submit": 0, "transfer": 30, "sched": 60,
      "unattributed": 0}),
    # submit wins over transfer; the wait for the lane is transfer
    ([S("dev:wave", 0, 30, thread=1), S("dev:writeback", 0, 50, thread=2),
      S("pump:stage_wait", 50, 70, thread=1)],
     {"dispatch": 0, "submit": 30, "transfer": 40, "sched": 0,
      "unattributed": 30}),
    # h2d under stage_args is transfer, on the submitting thread
    ([S("dev:stage_args", 0, 100), S("dev:h2d", 10, 90)],
     {"dispatch": 0, "submit": 20, "transfer": 80, "sched": 0,
      "unattributed": 0}),
    # attach and the scheduling core are the scheduler's
    ([S("attach:build", 0, 40), S("core:select", 50, 60)],
     {"dispatch": 0, "submit": 0, "transfer": 0, "sched": 50,
      "unattributed": 50}),
])
def test_idle_time_is_cut_among_the_spans_in_a_fixed_order(spans, expected):
    got = sp.attribute([(0, 100)], sp.nest(spans))
    assert got == expected and sum(got.values()) == 100


def test_only_idle_time_is_attributed():
    spans = sp.nest([S("dev:dispatch", 0, 100)])
    assert sp.attribute([(0, 10), (50, 60)], spans)["dispatch"] == 20


def _trace():
    """Two solves of 1000 ns on one thread, a lane and a committer; the
    chip is busy 100 ns of each."""
    spans = []
    for t0, pool in ((0, 7), (2000, 8)):
        spans += [
            S("attach:build", t0, t0 + 100, pool=pool, tasks=6),
            S("pump:pop", t0 + 100, t0 + 120, pool=pool, batch=0, n=6),
            S("dev:stage_in", t0 + 120, t0 + 200, thread=2, pool=pool,
              batch=0, tiles=3, bytes=3 << 20),
            S("pump:stage_wait", t0 + 120, t0 + 200, pool=pool, batch=0,
              n=6),
            S("dev:submit_batch", t0 + 200, t0 + 800, pool=pool, batch=0,
              n=6),
            S("dev:wave", t0 + 200, t0 + 600, pool=pool, cls="gemm", n=4,
              waited_us=8),
            S("dev:stage_args", t0 + 200, t0 + 300, pool=pool, tiles=12,
              host_tiles=1, bytes=1 << 20),
            S("dev:h2d", t0 + 220, t0 + 280, pool=pool, tiles=1,
              bytes=1 << 20),
            S("dev:jit", t0 + 300, t0 + 310, pool=pool),
            S("dev:dispatch", t0 + 310, t0 + 510, pool=pool),
            S("dev:epilog", t0 + 510, t0 + 600, pool=pool),
            S("dev:submit_one", t0 + 600, t0 + 800, pool=pool, cls="potrf",
              n=1, waited_us=4),
            S("dev:dispatch", t0 + 650, t0 + 750, pool=pool),
            S("dev:wave", t0 + 620, t0 + 640, thread=4, pool=pool,
              cls="syrk", n=1, waited_us=0),   # another rank's thread
            S("dev:dispatch", t0 + 625, t0 + 635, thread=4, pool=pool),
            S("pump:done", t0 + 800, t0 + 850, pool=pool, batch=0, n=6),
            S("dev:writeback", t0 + 700, t0 + 950, thread=3, pool=pool,
              tiles=6, bytes=6 << 20),
            S("dev:writeback", t0 + 1400, t0 + 1500, thread=3, pool=pool),
        ]
    return sp.Trace(spans=spans, windows=[(0, 1000), (2000, 3000)],
                    device={0: [(520, 620), (2520, 2620), (1200, 1300)],
                            1: [(0, 1000), (2000, 3000)]})


def test_summary_counts_from_the_spans_own_arguments():
    s = sp.summarize(_trace(), chips=1)
    assert s.solves == 2 and s.tasks == 6 and s.programs == 3
    # pump:pop 20 + pump:done 50, per solve of 6 tasks
    assert s.sched_us_per_task == pytest.approx(70 / 6 / 1e3)
    # submit_batch 600-400-200, wave 400-100-10-200-90 (+20-10 on the
    # other thread), stage_args 100-60, jit 10, epilog 90, submit_one
    # 200-100
    assert s.submit_us_per_task == pytest.approx(
        (0 + 0 + 10 + 40 + 10 + 90 + 100) / 6 / 1e3)
    assert s.dispatch_us_per_program == pytest.approx(310 / 3 / 1e3)
    assert s.stage_wait_us_per_task == pytest.approx((80 + 60) / 6 / 1e3)
    assert s.queue_wait_us_per_task == pytest.approx(12 / 6)
    # the committer's span between the solves is outside the windows
    assert s.writeback_s == pytest.approx(250e-9)


def test_idle_shares_are_of_the_idlest_chip_and_sum_to_100():
    s = sp.summarize(_trace(), chips=2)     # chip 1 is never idle
    assert sum(s.idle_ns.values()) == 1800  # chip 0: 2 x (1000 - 100)
    assert sum(s.idle_pct(c) for c in (*sp.CLASSES, "unattributed")) == \
        pytest.approx(100.0)
    per_solve = {c: v / 2 for c, v in s.idle_ns.items()}
    # the chip is busy 520..620: under the epilog and the start of
    # submit_one, whose idle time is only what lies outside it
    assert per_solve == {
        "dispatch": 200 + 100 + 10,     # the wave's, submit_one's, rank 4's
        # stage_args 20 + 20, jit 10, epilog 510..520, submit_one
        # 635..650 and 750..800, the other thread's wave 620..625
        "submit": 40 + 10 + 10 + 15 + 50 + 5,
        # the lane under stage_wait, h2d, the committer after 800 (before
        # it, submit_one and its dispatch win)
        "transfer": 80 + 60 + 150,
        # attach, pop; pump:done lies under the committer's span
        "sched": 100 + 20, "unattributed": 50}


def test_report_names_each_long_gap_by_the_span_chain_that_covers_it():
    text = sp.report(_trace(), chips=1, top=3)
    assert "2 solves, 6 tasks and 3 device programs a solve" in text
    assert "dispatch 34.44%" in text and "unattributed 5.56%" in text
    gaps = text.split("longest idle gaps of chip 0:\n")[1].splitlines()
    assert len(gaps) == 3
    # 0..520 of each solve: the wave is the innermost span over at least
    # half of it; its staging says one tile was still on the host
    assert gaps[0].split("ms  ")[1] == (
        "dev:wave(cls=gemm n=4 waited_us=8) < dev:submit_batch(batch=0 "
        "n=6) ; its dev:stage_args(tiles=12 host_tiles=1 bytes=1048576)")
    spans = sp.nest(sp.clip_spans(_trace().spans, [(0, 1000)]))
    assert sp.cover((960, 990), spans) == "no span"
    assert sp.cover((105, 115), spans) == "pump:pop(batch=0 n=6)"
    assert sp.report(sp.Trace([], [(0, 10)], {0: [(1, 2)]}), 1).startswith(
        "no parsec:* span")


def test_a_program_without_spans_gives_nothing_to_read():
    t = _trace()
    t.spans = []
    assert sp.summarize(t, chips=1) is None
    t = _trace()
    t.windows = []
    with pytest.raises(RuntimeError, match="bench:solve"):
        sp.summarize(t, chips=1)
    with pytest.raises(RuntimeError, match="uses 4 chips"):
        sp.summarize(_trace(), chips=4)


def _run(cell_name, traced=True, chips=1):
    cell = types.SimpleNamespace(name=cell_name, chips=chips)
    return types.SimpleNamespace(cell=cell, trace=object() if traced else None)


def _readers():
    spec = benchmark_json()
    return {name: harness.load_module(
        harness.find_reader(ROOT, spec["paths"], name)) for name in NEW}


@pytest.fixture
def recorded_run(tmp_path, monkeypatch):
    """The recorded trace where a traced run of a cell leaves its own."""
    import shutil

    monkeypatch.setattr(sp, "ROOT", str(tmp_path))
    d = tmp_path / ".bench_trace" / "a_cell" / "plugins" / "profile" / "x"
    d.mkdir(parents=True)
    shutil.copy(RECORDED, d / "host.xplane.pb")
    return _run("a_cell")


@pytest.mark.parametrize("metric", NEW)
def test_every_new_metric_is_an_entry_with_a_reader_of_its_own(metric):
    spec = benchmark_json()
    entry = next(m for m in spec["per_layer"] if m["name"] == metric)
    assert entry["source"] == "program_span" and entry["better"] == "lower"
    assert entry["moves"] == ("tile_home_s" if metric == "writeback_s"
                              else "tile_solve_s")
    assert "panel_n32768" not in entry["workloads"]
    assert ("tile_pump_n8192" in entry["workloads"]) == \
        (metric != "queue_wait_us_per_task")
    path = harness.find_reader(ROOT, spec["paths"], metric)
    assert path.endswith(f"layers/{metric}.py")
    reader = harness.load_module(path)
    # an untraced run, and a traced run that left no trace: nothing to read
    assert reader.read(_run("a_cell", traced=False)) is None
    assert reader.read(_run("no_such_cell_was_ever_traced")) is None


@pytest.mark.parametrize("metric", NEW)
def test_each_reader_reads_the_trace_recorded_on_the_v5e(metric,
                                                         recorded_run):
    value = _readers()[metric].read(recorded_run)
    assert value is not None and value >= 0
    if metric in IDLE:
        assert value <= 100
    if metric == "queue_wait_us_per_task":
        assert value == 0      # the pump has no ready queue in the device
    elif metric not in ("idle_in_transfer_pct", "stage_wait_us_per_task"):
        assert value > 0


def test_recorded_trace_holds_the_pump_spans_and_the_shares_sum_to_100(
        recorded_run):
    t = sp.load(RECORDED)
    assert len(t.windows) == 2 and sorted(t.device) == [0]
    names = {s.name for s in t.spans}
    assert {"attach:build", "pump:pop", "pump:stage_wait", "pump:land",
            "pump:retire", "pump:done", "dev:submit_batch", "dev:wave",
            "dev:submit_one", "dev:stage_args", "dev:jit", "dev:dispatch",
            "dev:epilog", "dev:stage_in", "dev:writeback",
            "dev:detach"} <= names
    s = sp.summarize(t, chips=1)
    n, nb = 1024, 256
    nt = n // nb
    assert s.tasks == nt * (nt + 1) * (nt + 2) // 6    # 20 tasks a solve
    assert 1 <= s.programs <= s.tasks
    readers = _readers()
    assert sum(readers[m].read(recorded_run) for m in IDLE) == \
        pytest.approx(100.0, abs=1e-6)
    # the idle time attributed is the idle time reduce.summarize reads
    whole = tr.summarize(tr.load_events(RECORDED), chips=1)
    idle_s = whole.window_s - whole.busy_by_chip[0]
    assert sum(s.idle_ns.values()) / 1e9 == pytest.approx(idle_s)
    # the earlier recording has no spans of the program: nothing to read
    old = sp.summarize(sp.load(os.path.join(HERE, "recorded",
                                            "tiny_pump.xplane.pb")), chips=1)
    assert old is None
