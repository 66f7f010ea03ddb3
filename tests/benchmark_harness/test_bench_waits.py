"""``benchmark/trace/waits.py``: the time a thread was off the CPU inside a
span and inside its children, clipping, the spans that count for
``gil_wait_pct``, the idle time under a wait, and the five per-layer
metrics that read them, on synthetic spans; then a tiny pump solve on the
CPU backend under the profiler, whose ``parsec-wait:*`` events nest where
``docs/TRACING.md`` "Waits" says (counts and nesting, never a time), and
the trace recorded on a v5e before PR 34, which gives nothing to read."""

import os
import types

import pytest

from benchmark import harness
from benchmark.trace import reduce as tr
from benchmark.trace import spans as sp
from benchmark.trace import waits as wt

from bench_testlib import ROOT, benchmark_json, tiny_cell

HERE = os.path.dirname(os.path.abspath(__file__))
OLD = os.path.join(HERE, "recorded", "tiny_pump_spans.xplane.pb")
NEW = ["res_lock_wait_us_per_task", "copy_start_s",
       "dispatch_blocked_us_per_program", "gil_wait_pct",
       "idle_in_wait_pct"]
TILE_CELLS = ["tile_pump_n8192", "tile_ctx_n8192", "tile_2x2_n16384",
              "geqrf_pump_n16384", "ooc_pump_n90112", "stencil_pump_n32768"]


def S(name, start, end, cpu=None, thread=1, **args):
    """A span of ``end - start`` ns that was ``cpu`` ns on the CPU (all of
    it where nothing is said)."""
    args["cpu_us"] = (end - start if cpu is None else cpu) / 1e3
    return sp.Span(name, start, end, thread, args)


def nested(spans, windows=((0, 1000),)):
    got = wt.nested(sp.Trace(list(spans), list(windows), {}))
    return {(s.name, s.start): s for s in got}


@pytest.mark.parametrize("spans, self_off", [
    # a span alone: what it was not on the CPU
    ([S("dev:epilog", 0, 100, cpu=70)], {("dev:epilog", 0): 30}),
    # a wait under it takes its own time off the CPU out of the parent's
    ([S("dev:epilog", 0, 100, cpu=30), S("wait:res_lock", 10, 60, cpu=0)],
     {("dev:epilog", 0): 20, ("wait:res_lock", 10): 50}),
    # a child that worked all through leaves the parent's as it was
    ([S("dev:wave", 0, 100, cpu=80), S("dev:jit", 20, 40)],
     {("dev:wave", 0): 20, ("dev:jit", 20): 0}),
    # a grandchild is taken out of its parent alone
    ([S("dev:wave", 0, 100, cpu=40), S("dev:epilog", 10, 90, cpu=35),
      S("wait:d2h_start", 20, 60, cpu=5)],
     {("dev:wave", 0): 15, ("dev:epilog", 10): 10,
      ("wait:d2h_start", 20): 35}),
    # the same stretch on another thread is no child
    ([S("dev:epilog", 0, 100, cpu=90),
      S("dev:evict", 0, 100, cpu=10, thread=2)],
     {("dev:epilog", 0): 10, ("dev:evict", 0): 90}),
])
def test_self_off_cpu_time_takes_the_childrens_out(spans, self_off):
    got = nested(spans)
    assert {k: round(s.self_off_ns) for k, s in got.items()} == self_off
    for s in got.values():      # wall = on the CPU + off it, self and whole
        assert s.self_ns == pytest.approx(s.self_cpu_ns + s.self_off_ns)
        assert s.end - s.start == pytest.approx(s.cpu_ns + s.off_ns)


@pytest.mark.parametrize("span, pieces", [
    ((10, 20), [(10, 20, 5.0)]),                       # inside
    ((900, 1100), [(900, 1000, 50.0)]),                # cut at the end
    ((-100, 100), [(0, 100, 50.0)]),                   # and at the start
    ((1200, 1400), []),                                # between two solves
    ((500, 2500), [(500, 1000, 250.0), (2000, 2500, 250.0)]),
])
def test_a_span_cut_at_a_windows_edge_keeps_that_share_of_its_cpu_time(
        span, pieces):
    s, e = span
    got = wt.nested(sp.Trace([S("dev:epilog", s, e, cpu=(e - s) / 2)],
                             [(0, 1000), (2000, 3000)], {}))
    assert [(g.start, g.end, g.cpu_ns) for g in got] == pieces


def test_a_span_without_cpu_us_is_not_timed_and_counts_in_no_cpu_sum():
    """The clock is read within a budget, by whole trees of spans
    (``pins._cpu_tree``): an event without the argument is wall time
    alone.  A trace in which NO ``parsec:*`` span has it is a program
    from before PR 34."""
    bare = sp.Span("dev:submit_batch", 0, 500, 1, {"pool": 1})
    child = sp.Span("dev:epilog", 100, 200, 1, {"pool": 1})
    got = nested([bare, child, S("dev:epilog", 600, 700, cpu=40)])
    assert not got[("dev:submit_batch", 0)].timed
    assert not got[("dev:epilog", 100)].timed
    assert got[("dev:submit_batch", 0)].self_ns == 400
    assert got[("dev:epilog", 600)].timed
    assert got[("dev:epilog", 600)].off_ns == 60
    assert wt.nested(sp.Trace([bare], [(0, 1000)], {})) is None
    # a wait's own ``cpu_us`` does not make the spans new
    assert wt.nested(sp.Trace([bare, S("wait:res_lock", 10, 20)],
                              [(0, 1000)], {})) is None


def test_the_cpu_ratios_are_over_the_timed_trees_alone():
    """A second batch whose tree read no clock: its waits count (wall
    time), its programs and its Python count in no ratio of CPU time."""
    def bare(name, start, end, **args):
        return sp.Span(name, start, end, 1, args)

    extra = [bare("dev:submit_batch", 900, 1000, n=0),
             bare("dev:dispatch", 910, 950),
             bare("wait:res_lock", 950, 990, holder="dev:evict")]
    base = wt.summarize(_trace(), chips=1)
    w = wt.summarize(_trace(extra), chips=1)
    assert w.programs == base.programs + 0.5           # 3 in two solves
    assert (w.dispatch_timed, base.dispatch_timed) == (4, 4)
    assert w.dispatch_blocked_us_per_program == \
        base.dispatch_blocked_us_per_program
    assert (w.gil_off_ns, w.gil_wall_ns) == (base.gil_off_ns,
                                             base.gil_wall_ns)
    assert w.wait_ns["res_lock"] == base.wait_ns["res_lock"] + 40


def _trace(extra=(), chips_busy=((500, 600), (2500, 2600))):
    """Two solves of 1000 ns; the pump is thread 1, the lane thread 2,
    another rank's manager thread 4.  A solve: one wave of 4 tasks and a
    task alone, two programs; the chip is busy 100 ns of it."""
    spans = []
    for t0 in (0, 2000):
        spans += [
            S("pump:pop", t0, t0 + 100, cpu=80, n=5),
            S("dev:stage_in", t0 + 100, t0 + 400, cpu=100, thread=2),
            S("dev:evict", t0 + 150, t0 + 350, cpu=50, thread=2),
            S("dev:submit_batch", t0 + 100, t0 + 900, cpu=400, n=5),
            S("dev:wave", t0 + 100, t0 + 700, cpu=300, n=4, rank=0),
            S("dev:dispatch", t0 + 150, t0 + 350, cpu=50),
            S("dev:epilog", t0 + 400, t0 + 700, cpu=150),
            S("wait:res_lock", t0 + 420, t0 + 520, cpu=0,
              holder="dev:evict"),
            S("wait:d2h_start", t0 + 600, t0 + 680, cpu=60, n=4,
              bytes=4 << 20),
            S("dev:submit_one", t0 + 700, t0 + 900, cpu=100, n=1, rank=0),
            S("dev:dispatch", t0 + 750, t0 + 850, cpu=50),
            # another rank: its thread submits too
            S("dev:wave", t0 + 300, t0 + 500, cpu=110, thread=4, n=1,
              rank=1),
            S("wait:res_lock", t0 + 310, t0 + 350, cpu=0, thread=4,
              holder="dev:stage_in"),
            S("wait:d2h_start", t0 + 400, t0 + 450, cpu=0, thread=4, n=1,
              bytes=1 << 20),
            # the lane waits too, and submits nothing
            S("wait:res_lock", t0 + 110, t0 + 140, cpu=0, thread=2,
              holder="dev:epilog"),
        ]
    spans += list(extra)
    return sp.Trace(spans, [(0, 1000), (2000, 3000)],
                    {0: list(chips_busy),
                     1: [(0, 1000), (2000, 3000)]})


def test_the_five_metrics_from_the_spans_own_numbers():
    w = wt.summarize(_trace(), chips=1)
    assert (w.solves, w.tasks, w.programs, w.chip, w.rank) == (2, 6, 2, 0, 0)
    # the submitting threads' (1 and 4) waits for the lock, not the lane's
    assert w.wait_ns == {"res_lock": 2 * 140, "d2h_start": 2 * 130}
    assert w.res_lock_wait_us_per_task == pytest.approx(140 / 6 / 1e3)
    # one chip: every submitting thread is its own
    assert w.copy_start_s == pytest.approx(130e-9)
    # (200 - 50) + (100 - 50) a solve, two programs
    assert w.dispatch_blocked_us_per_program == pytest.approx(
        200 / 2 / 1e3)
    # pop 100 (20 off), submit_batch 0, wave 100 (100 - (300-50-150) = 0),
    # epilog 300-100-80 = 120 of which CPU 150-0-60 = 90 (30 off),
    # submit_one 100 (100-50 CPU: 50 off), the other rank's wave
    # 200-40-50 = 110 all on the CPU; dispatch and the waits left out
    assert (w.gil_off_ns, w.gil_wall_ns) == (
        pytest.approx(2 * (20 + 0 + 30 + 50)),
        pytest.approx(2 * (100 + 0 + 100 + 120 + 100 + 110)))
    assert w.gil_wait_pct == pytest.approx(100 * 100 / 530)
    # idle 900 a solve; under a wait of a submitting thread: 310..350,
    # 400..450 (rank 1's), 420..500 of 420..520 (busy from 500),
    # 600..680: 40 + 50 + 50 + 80 with 420..450 counted once
    assert w.idle_ns == 2 * 900
    assert w.idle_wait_ns == 2 * (40 + 100 + 80)
    assert w.idle_in_wait_pct == pytest.approx(100 * 220 / 900)


def test_a_time_of_one_thread_is_of_the_idlest_chips_rank():
    """On several chips ``copy_start_s``, ``gil_wait_pct`` and
    ``idle_in_wait_pct`` are of the threads that submit for the idlest
    chip's rank; the per-task and per-program numbers of every rank's."""
    t = _trace()
    w = wt.summarize(t, chips=2)            # chip 1 is never idle
    assert (w.chip, w.rank) == (0, 0)
    assert w.own_wait_ns == {"res_lock": 2 * 100, "d2h_start": 2 * 80}
    assert w.copy_start_s == pytest.approx(80e-9)
    assert w.res_lock_wait_us_per_task == pytest.approx(140 / 6 / 1e3)
    assert w.gil_wall_ns == pytest.approx(2 * 420)
    assert w.idle_wait_ns == 2 * (80 + 80)
    t.device[0], t.device[1] = t.device[1], t.device[0]
    w = wt.summarize(t, chips=2)            # now rank 1's chip is
    assert (w.chip, w.rank) == (1, 1)
    assert w.own_wait_ns == {"res_lock": 2 * 40, "d2h_start": 2 * 50}
    assert (w.gil_off_ns, w.gil_wall_ns) == (0, pytest.approx(2 * 110))
    assert w.gil_wait_pct == 0.0


@pytest.mark.parametrize("name", sorted(wt.BLOCKING) + [
    "comm:send", "comm:recv", "wait:wb_capacity", "wait:dev_lock"])
def test_a_span_that_blocks_by_design_is_no_wait_for_the_gil(name):
    base = wt.summarize(_trace(), chips=1)
    extra = [S(name, 910, 990, cpu=1)]      # 79 ns off the CPU
    w = wt.summarize(_trace(extra), chips=1)
    assert (w.gil_off_ns, w.gil_wall_ns) == (base.gil_off_ns,
                                             base.gil_wall_ns)


@pytest.mark.parametrize("name", [
    "pump:pop", "pump:land", "pump:done", "core:select", "core:schedule",
    "attach:build", "dev:submit_batch", "dev:wave", "dev:stage_args",
    "dev:jit", "dev:epilog", "dev:evict", "dev:stage_in"])
def test_python_work_that_was_off_the_cpu_waited_for_the_gil(name):
    base = wt.summarize(_trace(), chips=1)
    w = wt.summarize(_trace([S(name, 910, 990, cpu=20)]), chips=1)
    assert w.gil_off_ns == pytest.approx(base.gil_off_ns + 60)
    assert w.gil_wall_ns == pytest.approx(base.gil_wall_ns + 80)
    if name not in wt.SUBMITTING:   # on a thread that submits nothing
        w = wt.summarize(_trace([S(name, 910, 990, cpu=20, thread=3)]),
                         chips=1)
        assert w.gil_off_ns == pytest.approx(base.gil_off_ns)


def test_nothing_waited_reads_zero_and_an_old_program_reads_nothing():
    quiet = [s for s in _trace().spans if not wt.is_wait(s.name)]
    for s in quiet:     # every span on the CPU for all of its time
        s.args["cpu_us"] = (s.end - s.start) / 1e3
    t = _trace()
    t.spans = quiet
    w = wt.summarize(t, chips=1)
    assert [getattr(w, m) for m in NEW] == [0.0] * 5
    assert all(isinstance(getattr(w, m), float) for m in NEW)
    for s in t.spans:   # the same program before PR 34
        del s.args["cpu_us"]
    assert wt.summarize(t, chips=1) is None
    t.spans = []
    assert wt.summarize(t, chips=1) is None
    t = _trace()
    t.windows = []
    with pytest.raises(RuntimeError, match="bench:solve"):
        wt.summarize(t, chips=1)


def test_the_report_splits_the_waits_by_holder_and_the_gil_by_thread():
    text = wt.report(_trace(), chips=2)
    assert "2 solves, 6 tasks and 2 device programs a solve; the idlest " \
        "chip is 0 (rank 0)" in text
    rows = [ln.split() for ln in text.splitlines()
            if ln.startswith("wait:")]
    # what, thread, holder, under, per solve, wall ms
    assert ["wait:res_lock", "submitting", "dev:evict", "dev:epilog",
            "1.0", "0.000"] in rows
    assert ["wait:res_lock", "other", "dev:epilog", "dev:stage_in", "1.0",
            "0.000"] in rows
    assert ["wait:d2h_start", "submitting", "-", "dev:epilog", "1.0",
            "0.000", "n=4", "MB=4.2"] in rows
    assert "timed (cpu_us read, within the clock's budget): 30 of 30 " \
        "events, 100.0% of the spans' time" in text
    # dev:epilog closes: self = its waits + its CPU + the remainder
    assert "dev:epilog: self 0.000 = wait:d2h_start 0.000 + " \
        "wait:res_lock 0.000 + CPU 0.000 + off-CPU 0.000" in text
    by_thread = text.split("by submitting thread:\n")[1].splitlines()[1:]
    assert [ln.split()[:3] for ln in by_thread] == [
        ["1", "0", "5.0"], ["4", "1", "1.0"]]
    old = sp.load(OLD)
    assert wt.report(old, 1).startswith("nothing to read")


# ---------------------------------------------------------------------------
# the entries of BENCHMARK.json and their readers
# ---------------------------------------------------------------------------

def _run(cell_name, traced=True, chips=1):
    cell = types.SimpleNamespace(name=cell_name, chips=chips)
    return types.SimpleNamespace(cell=cell, trace=object() if traced else None)


def _reader(metric):
    spec = benchmark_json()
    return harness.load_module(
        harness.find_reader(ROOT, spec["paths"], metric))


def _leave(monkeypatch, tmp_path, path_or_none, cell="a_cell"):
    """A trace where a traced run of ``cell`` leaves its own."""
    import shutil

    monkeypatch.setattr(sp, "ROOT", str(tmp_path))
    d = tmp_path / ".bench_trace" / cell / "plugins" / "profile" / "x"
    d.mkdir(parents=True)
    if path_or_none is not None:
        shutil.copy(path_or_none, d / "host.xplane.pb")
    return d / "host.xplane.pb"


@pytest.mark.parametrize("metric", NEW)
def test_every_new_metric_is_an_entry_with_a_reader_of_its_own(metric):
    spec = benchmark_json()
    entry = next(m for m in spec["per_layer"] if m["name"] == metric)
    assert entry == {
        "name": metric, "unit": entry["unit"], "better": "lower",
        "source": "program_span", "layer": "device",
        "moves": "tile_solve_s", "workloads": TILE_CELLS}
    assert entry["unit"] == {"res_lock_wait_us_per_task": "us/task",
                             "copy_start_s": "s",
                             "dispatch_blocked_us_per_program": "us/program",
                             "gil_wait_pct": "%",
                             "idle_in_wait_pct": "%"}[metric]
    submit = next(m for m in spec["per_layer"]
                  if m["name"] == "submit_us_per_task")
    assert entry["workloads"] == submit["workloads"]
    # appended: the five are the last of the list, in the issue's order
    assert [m["name"] for m in spec["per_layer"][-5:]] == NEW
    path = harness.find_reader(ROOT, spec["paths"], metric)
    assert path.endswith(f"layers/{metric}.py")
    reader = harness.load_module(path)
    # an untraced run, and a traced run that left no trace: nothing to read
    assert reader.read(_run("a_cell", traced=False)) is None
    assert reader.read(_run("no_such_cell_was_ever_traced")) is None


@pytest.mark.parametrize("metric", NEW)
def test_a_program_from_before_the_waits_leaves_the_metric_out(
        metric, tmp_path, monkeypatch):
    """The driver lays this PR's readers over the parent's checkout: its
    spans carry no ``cpu_us``, and the reader returns nothing, without a
    raise."""
    _leave(monkeypatch, tmp_path, OLD)
    assert _reader(metric).read(_run("a_cell")) is None


# ---------------------------------------------------------------------------
# the program's own waits, as the profiler records them (CPU backend)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def pump(tmp_path_factory):
    from parsec_tpu import native

    if not native.available():
        pytest.skip("needs the native core")
    from test_bench_spans_program import traced_solves

    from parsec_tpu.profiling import pins

    out = tmp_path_factory.mktemp("pump")
    free = pins._CPU_FREE_NS    # (a loaded machine's reads of the clock
    pins._CPU_FREE_NS = 10 ** 12    # would run into its budget)
    try:
        old, _spans, ntasks = traced_solves("tile_pump_n8192", out)
    finally:
        pins._CPU_FREE_NS = free
    path = tr.find_xplane(str(out))
    return old, wt.load(path), ntasks, path, tiny_cell(
        "tile_pump_n8192").config


def test_the_waits_are_loaded_beside_the_spans_that_spans_py_loads(pump):
    old, trace, _ntasks, _path, _config = pump
    mine = [s for s in trace.spans if not wt.is_wait(s.name)]
    assert [(s.name, s.start, s.end, s.thread) for s in mine] == \
        [(s.name, s.start, s.end, s.thread) for s in old.spans]
    assert trace.windows == old.windows and len(trace.windows) == 2
    assert all("cpu_us" in s.args for s in mine)
    waits = [s for s in trace.spans if wt.is_wait(s.name)]
    assert waits and {s.name for s in waits} <= {
        "wait:res_lock", "wait:dev_lock", "wait:wb_capacity",
        "wait:d2h_start"}


def test_the_copy_starts_nest_under_the_commit_and_count_the_tiles(pump):
    _old, trace, _ntasks, _path, config = pump
    nest = wt.nested(trace)
    threads = wt.submitting_threads(nest)
    assert len(threads) == 1                    # the pump's
    starts = [s for s in nest if s.name == "wait:d2h_start"
              and s.thread in threads]
    assert starts and {s.parent.name for s in starts} == {"dev:epilog"}
    nb = config["nb"]
    nt = config["n"] // nb
    home = len(trace.windows) * nt * (nt + 1) // 2  # the lower tiles
    assert sum(s.args["n"] for s in starts) == home
    assert sum(s.args["bytes"] for s in starts) == home * nb * nb * 4
    # a span under which a wait lies is the epilog's ``home`` > 0
    assert all(s.parent.args["home"] >= s.args["n"] > 0 for s in starts)
    for s in nest:
        if s.name == "wait:res_lock":
            assert s.args["holder"] in {n.name for n in nest} | {"none"}
            assert s.parent is not None


def test_a_program_with_the_code_reads_five_numbers(pump, tmp_path,
                                                    monkeypatch):
    """The CPU backend leaves no device plane: with a hand-made one (the
    chip busy for the middle half of each solve) the five readers give
    numbers — shares within 0..100, never a time to be believed."""
    _old, trace, ntasks, path, _config = pump
    trace.device = {0: [(s + (e - s) // 4, e - (e - s) // 4)
                        for s, e in trace.windows]}
    w = wt.summarize(trace, chips=1)
    assert w is not None and w.tasks == ntasks and w.programs >= 1
    for m in NEW:
        assert isinstance(getattr(w, m), float) and getattr(w, m) >= 0
    assert w.gil_wait_pct <= 100 and w.idle_in_wait_pct <= 100
    assert w.copy_start_s > 0
    assert abs(w.idle_ns - sum(e - s for s, e in trace.windows) / 2) <= 4
    # through a reader: the trace where the harness leaves it
    monkeypatch.setattr(wt, "load", lambda p: trace)
    _leave(monkeypatch, tmp_path, path)
    assert _reader("copy_start_s").read(_run("a_cell")) == w.copy_start_s
