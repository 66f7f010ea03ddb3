"""``_Span.lap``: a stretch of a span that is there, recorded as a field
of it.  Without a profiler session a lap reads no clock and keeps nothing
(the shared no-op, and the span a PINS subscriber alone hears); under one
the event carries ``laps``, nanoseconds in the order the stretches ran, a
name that recurs standing again; no site fires for a lap.  Then
``benchmark/trace/phases.py``, which lays the laps over their span: on
synthetic spans (a span whose laps tile it is covered whole; children and
waits come out of a lap; the drain's stamps), on a recorded trace of the
tiny Context cell (``recorded/tiny_ctx_laps.xplane.pb``: the CPU backend,
with a device plane written in for the idlest chip's sake, one operation
over the middle half of each solve) and on one recorded before there were
laps, which gives nothing to read."""

import glob
import json
import os

import jax
import pytest

from parsec_tpu.profiling import binary, pins

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RECORDED = os.path.join(ROOT, "tests", "benchmark_harness", "recorded")
LAPS = os.path.join(RECORDED, "tiny_ctx_laps.xplane.pb")
OLD = os.path.join(RECORDED, "tiny_pump_spans.xplane.pb")


@pytest.fixture(autouse=True)
def _clean_pins():
    pins.clear()
    yield
    pins.clear()


@pytest.fixture
def clock(monkeypatch):
    """``pins._wall_ns`` counted: how often a span read the wall clock."""
    calls = []
    real = pins._wall_ns

    def counted():
        calls.append(1)
        return real()

    monkeypatch.setattr(pins, "_wall_ns", counted)
    return calls


def _events(trace_dir, prefix="parsec"):
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out += [(e.name, dict(e.stats), int(e.duration_ns))
                    for e in line.events if e.name.startswith(prefix)]
    return out


# -- without a session ---------------------------------------------------------

def test_the_quiet_span_laps_nothing_and_reads_no_clock(clock):
    assert not jax.profiler.TraceAnnotation.is_enabled()
    with pins.span("dev:stage_args", pool=1, rank=0) as sp:
        assert sp is pins._QUIET
        for name in ("walk", "put", "sole", "own"):
            assert sp.lap(name) is None
    assert clock == []
    assert pins._QuietSpan.__slots__ == ()   # nothing to keep a lap in


def test_a_subscriber_without_a_session_gets_no_lap_and_no_clock_read(clock):
    log = []
    for site in ("dev:epilog_begin", "dev:epilog_end"):
        pins.subscribe(site, lambda es, p, site=site: log.append((site, p)))
    with pins.span("dev:epilog", pool=1, rank=0) as sp:
        assert sp is not pins._QUIET     # the subscriber's span
        sp.lap("hooks")
        sp.lap("commit")
        sp.note(n=2)
    assert clock == []
    assert sp._laps is None         # nowhere to keep one
    assert log == [("dev:epilog_begin", {"pool": 1, "rank": 0}),
                   ("dev:epilog_end", {"pool": 1, "rank": 0, "n": 2})]


def test_no_site_fires_for_a_lap_and_the_rank_traces_table_is_as_it_was(
        tmp_path):
    heard = []
    for site in pins.ALL_SITES + ["dev:wave_begin", "dev:wave_end",
                                  "dev:wave_lap", "lap"]:
        pins.subscribe(site, lambda es, p, site=site: heard.append(site))
    with jax.profiler.trace(str(tmp_path)):
        with pins.span("dev:wave", pool=1, rank=0, n=1) as sp:
            sp.lap("room")
            sp.lap("stage")
    assert heard == ["dev:wave_begin", "dev:wave_end"]
    assert not any("lap" in k for k in binary.SPAN_KEYWORDS)


# -- under a session -----------------------------------------------------------

def test_under_a_session_the_event_carries_its_laps_in_order(tmp_path, clock):
    with jax.profiler.trace(str(tmp_path)):
        with pins.span("dev:stage_args", pool=1, rank=0) as sp:
            before = len(clock)
            for name in ("walk", "put", "sole", "own"):
                sp.lap(name)
            assert len(clock) - before == 4      # one read a lap
        with pins.span("dev:jit", pool=1, rank=0):
            pass                                 # no lap: no argument
        with pins.wait("res_lock", holder="none"):
            pass
    events = {n: (a, ns) for n, a, ns in _events(tmp_path)}
    args, ns = events["parsec:dev:stage_args"]
    laps = [part.split(":") for part in args["laps"].split("/")]
    assert [name for name, _ in laps] == ["walk", "put", "sole", "own"]
    assert all(v.isdigit() for _, v in laps)
    assert 0 < sum(int(v) for _, v in laps) <= ns
    assert "laps" not in events["parsec:dev:jit"][0]
    assert "laps" not in events["parsec-wait:res_lock"][0]


def test_a_name_that_recurs_stands_again_and_its_reader_sums(
        tmp_path, monkeypatch):
    ticks = iter(range(1000, 100000, 1000))
    monkeypatch.setattr(pins, "_wall_ns", lambda: next(ticks))
    monkeypatch.setattr(pins, "_cpu_tree", lambda: False)
    with jax.profiler.trace(str(tmp_path)):
        with pins.span("dev:wave", pool=1, rank=0, n=2) as sp:
            for name in ("room", "key", "call", "key", "call", "commit"):
                sp.lap(name)
    (_, args, _ns), = _events(tmp_path, "parsec:dev:wave")
    # the origin is read at the span's start; a read a lap, 1,000 apart
    assert args["laps"] == ("room:1000/key:1000/call:1000/key:1000/"
                            "call:1000/commit:1000")
    from benchmark.trace import phases, spans
    p = phases.summarize(spans.Trace(
        [spans.Span("dev:wave", 0, 6000, 1, dict(args, n=2)),
         spans.Span("dev:dispatch", 2000, 3000, 1, {})],
        [(0, 6000)], {0: [(0, 10)]}), 1)
    assert p.own_ns[("dev:wave", "key")] == 2000
    assert p.own_ns[("dev:wave", "call")] == 1000   # the first is the child's


def test_a_span_that_began_before_the_session_takes_no_laps(tmp_path, clock):
    sp = pins.span("dev:wave", pool=1, rank=0)
    assert sp is pins._QUIET
    pins.subscribe("dev:wave_end", lambda es, p: None)
    with pins.span("dev:wave", pool=1, rank=0) as sp:
        with jax.profiler.trace(str(tmp_path)):
            sp.lap("room")      # its origin was never read
    assert clock == [] and sp._laps is None


# -- the reader: benchmark/trace/phases.py -------------------------------------

from benchmark import harness  # noqa: E402
from benchmark.trace import phases  # noqa: E402
from benchmark.trace import spans as sp_  # noqa: E402

from bench_testlib import benchmark_json  # noqa: E402
from test_bench_waits import _leave, _reader, _run  # noqa: E402


def S(name, start, end, thread=1, **args):
    return sp_.Span(name, start, end, thread, args)


def laps_of(**named):
    return "/".join(f"{k}:{v}" for k, v in named.items())


def _phases(spans, windows=((0, 10_000),), busy=((4000, 5000),), chips=1):
    trace = sp_.Trace(list(spans), list(windows),
                      {c: list(busy) for c in range(chips)})
    return phases.summarize(trace, chips)


def test_parse_laps_reads_names_and_nanoseconds_in_order():
    assert phases.parse_laps("walk:41200/put:3000/own:0") == [
        ("walk", 41200), ("put", 3000), ("own", 0)]
    assert phases.parse_laps("") == []


def test_a_span_whose_laps_tile_it_is_covered_whole():
    p = _phases([
        S("dev:wave", 0, 1000, n=4, cls="gemm",
          laps=laps_of(room=100, stage=200, key=100, flatten=100, call=200,
                       count=100, commit=200)),
        S("dev:dispatch", 500, 700)])
    assert p.submit_laps_cover_pct == 100.0
    assert p.tasks == 4 and p.programs == 1 and p.solves == 1
    # the child comes out of the lap it lies in, and of no other
    assert p.own_ns[("dev:wave", "call")] == 0
    assert p.own_ns[("dev:wave", "flatten")] == 100
    assert p.submit_flatten_us_per_task == 100 / 1e3 / 4
    assert p.submit_key_us_per_task == 200 / 1e3 / 4     # room + key
    assert p.handover_us_per_task is None                # no stamp


def test_children_and_waits_come_out_of_a_lap_and_the_cover_keeps_the_waits():
    p = _phases([
        S("dev:submit_one", 0, 1000, n=1, cls="potrf",
          laps=laps_of(stage=400, key=100, flatten=50, call=150, count=50,
                       commit=250)),
        S("dev:stage_args", 0, 400, laps=laps_of(walk=200, put=100, sole=10,
                                                 own=90)),
        S("wait:res_lock", 50, 150, holder="dev:stage_in"),
        S("dev:h2d", 210, 290),
        S("dev:jit", 420, 480),
        S("dev:dispatch", 550, 700),
        S("dev:epilog", 760, 1000,
          laps=laps_of(hooks=10, commit=100, settle=10, home=60, zeros=0,
                       complete=60)),
        S("wait:d2h_start", 890, 930, n=1, bytes=4096),
        S("core:complete_exec", 950, 990)])
    # dev:submit_one's laps count with dev:wave's: a program's
    assert p.own_ns[("dev:wave", "stage")] == 0
    assert p.own_ns[("dev:wave", "key")] == 100 - 60
    assert p.submit_key_us_per_task == (40 + 60) / 1e3   # + dev:jit's self
    assert p.stage_walk_us_per_task == (200 - 100) / 1e3
    assert p.wait_ns[("dev:stage_args", "walk")] == 100
    assert p.stage_own_us_per_task == (100 - 80 + 10 + 90) / 1e3
    assert p.epilog_commit_us_per_task == 120 / 1e3
    assert p.epilog_home_us_per_task == (60 - 40) / 1e3
    assert p.epilog_complete_us_per_task == (60 - 40) / 1e3
    # every span's laps tile it: with the waits left in all is covered
    # but ``dev:jit``'s 60, which has no lap (self times: 150 + 320 + 60
    # + 200)
    assert p.self_ns == {"dev:submit_one": 150, "dev:stage_args": 320,
                         "dev:jit": 60, "dev:epilog": 200}
    assert p.submit_laps_cover_pct == pytest.approx(100.0 * 670 / 730)


def test_the_pumps_batch_and_the_managers_stamps_make_the_units():
    pump = _phases([
        S("dev:submit_batch", 0, 2000, batch=1, n=8,
          laps=laps_of(units=300, waves=1600, retry=100)),
        S("dev:wave", 400, 1800, n=8, cls="gemm",
          laps=laps_of(room=0, stage=0, key=0, flatten=0, call=1000, count=0,
                       commit=400)),
        S("dev:dispatch", 400, 1400)])
    assert pump.submit_units_us_per_task == (300 + 200 + 100) / 1e3 / 8
    assert pump.units_alone_us_per_task == 300 / 1e3 / 8
    assert pump.handover_us_per_task is None
    ctx = _phases([
        S("dev:wave", 1000, 2000, n=4, cls="gemm", batch=7, direct=3,
          hand_us=120.0, handed=3, units_us=8.0,
          laps=laps_of(room=0, stage=0, key=0, flatten=0, call=900, count=0,
                       commit=100)),
        S("dev:dispatch", 1000, 1900),
        S("dev:wave", 2000, 3000, n=2, cls="gemm", batch=7, direct=0,
          laps=laps_of(room=0, stage=0, key=0, flatten=0, call=900, count=0,
                       commit=100)),
        S("dev:dispatch", 2000, 2900)])
    assert ctx.stamped == 1 and ctx.handed == 3
    assert ctx.handover_us_per_task == 40.0
    assert ctx.submit_units_us_per_task == 8.0 / 6
    # neither lies under a span: the cover does not count them
    assert ctx.submit_laps_cover_pct == 100.0


def test_a_lap_is_laid_from_the_unclipped_start_and_cut_at_the_window():
    p = _phases([
        S("dev:wave", 0, 1000, n=2, cls="syrk",
          laps=laps_of(room=100, stage=300, key=100, flatten=100, call=200,
                       count=100, commit=100)),
        S("dev:dispatch", 600, 800)],
        windows=((450, 10_000),), busy=((4000, 5000),))
    assert ("dev:wave", "room") not in p.own_ns          # before the window
    assert p.own_ns[("dev:wave", "key")] == 50           # cut at its edge
    assert p.own_ns[("dev:wave", "flatten")] == 100
    assert p.submit_laps_cover_pct == 100.0


def test_the_idle_time_under_a_lap_is_the_idlest_chips_on_its_ranks_thread():
    spans = []
    for rank, thread in ((0, 1), (1, 2)):
        spans += [
            S("dev:wave", 0, 1000, thread=thread, rank=rank, n=1, cls="x",
              laps=laps_of(room=0, stage=0, key=400, flatten=0, call=500,
                           count=0, commit=100)),
            S("dev:dispatch", 400, 900, thread=thread, rank=rank)]
    trace = sp_.Trace(spans, [(0, 1000)],
                      {0: [(0, 1000)], 1: [(300, 1000)]})  # chip 1 idles
    p = phases.summarize(trace, 2)
    assert p.idle_total_ns == 300
    assert p.idle_ns[("dev:wave", "key")] == 300     # rank 1's, once
    assert p.own_ns[("dev:wave", "key")] == 800      # both ranks'


def test_spans_without_laps_give_nothing_to_read():
    assert _phases([S("dev:wave", 0, 1000, n=4, cls="gemm"),
                    S("dev:dispatch", 500, 700)]) is None
    assert "nothing to read" in phases.report(
        sp_.Trace([S("dev:wave", 0, 1000, n=4)], [(0, 1000)],
                  {0: [(0, 10)]}), 1)


# -- the readers, on recorded traces -------------------------------------------

def known():
    """What ``recorded/tiny_ctx_laps.xplane.pb`` reads (two solves of the
    tiny Context cell on the CPU backend: the numbers of that file, not
    speeds of anything; the file is ``test_bench_spans_program.
    traced_solves("tile_ctx_n8192")``'s trace with a ``/device:TPU:0`` plane
    added through ``tensorflow.tsl.profiler.protobuf.xplane_pb2``)."""
    with open(os.path.join(RECORDED, "tiny_ctx_laps.json")) as f:
        return json.load(f)


def test_the_recorded_trace_is_the_tiny_context_solve_with_its_laps():
    p = phases.summarize(phases.waits.load(LAPS), 1)
    assert (p.solves, p.tasks) == (2, 20)
    assert p.handed == 2 * 19 and p.stamped >= 2
    for span, names in phases.LAPS.items():
        if span != "dev:submit_batch":      # (the pump's)
            assert {k[1] for k in p.own_ns if k[0] == span} == set(names)
    text = phases.report(phases.waits.load(LAPS), 1)
    assert "submit_laps_cover_pct" in text and "hand_us" in text


@pytest.mark.parametrize("metric", phases.METRICS)
def test_a_metric_reads_its_known_value_in_the_cells_it_lists(
        metric, tmp_path, monkeypatch):
    """``read(run)`` on the recorded trace, left where a traced run of
    each cell the entry lists leaves its own; without a trace, nothing."""
    entry = next(m for m in benchmark_json()["per_layer"]
                 if m["name"] == metric)
    reader = _reader(metric)
    assert reader.read(_run(entry["workloads"][0], traced=False)) is None
    assert reader.read(_run("no_such_cell_was_ever_traced")) is None
    for cell in entry["workloads"]:
        _leave(monkeypatch, tmp_path / cell, LAPS, cell)
        value = reader.read(_run(cell))
        assert isinstance(value, float)
        assert value == pytest.approx(known()[metric], rel=1e-9)


@pytest.mark.parametrize("metric", phases.METRICS)
def test_a_program_from_before_the_laps_leaves_the_metric_out(
        metric, tmp_path, monkeypatch):
    """The driver lays this PR's readers over the parent's checkout: its
    spans carry no ``laps``, and the reader returns nothing, without a
    raise."""
    _leave(monkeypatch, tmp_path, OLD, "a_cell")
    assert _reader(metric).read(_run("a_cell")) is None


@pytest.mark.parametrize("metric", phases.METRICS)
def test_every_new_metric_is_an_appended_entry_with_a_reader_of_its_own(
        metric):
    spec = benchmark_json()
    per = {m["name"]: m for m in spec["per_layer"]}
    entry = per[metric]
    cells = per["handover_direct_pct" if metric == "handover_us_per_task"
                else "submit_us_per_task"]["workloads"]
    cover = metric == "submit_laps_cover_pct"
    # (by membership: a cell that joins ``submit_us_per_task`` later need
    # not join the laps.  PR 51's ``tile_g4_n98304`` does not: its four
    # device modules share ONE rank, and ``phases.py`` finds a chip's
    # submitting threads by "rank r drives chip r")
    listed = entry["workloads"]
    assert [c for c in cells if c in listed] == listed and len(listed) >= 3
    assert set(cells) - set(listed) <= {"tile_g4_n98304"}
    assert entry == {
        "name": metric, "unit": "%" if cover else "us",
        "better": "higher" if cover else "lower",
        "source": "program_span", "layer": "device",
        "moves": "tile_solve_s", "workloads": listed}
    names = [m["name"] for m in spec["per_layer"]]
    assert names.index(metric) > names.index("lauum_roofline")
    assert harness.find_reader(ROOT, spec["paths"], metric).endswith(
        f"layers/{metric}.py")
