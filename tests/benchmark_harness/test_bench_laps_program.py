"""The laps as the profiler records them: the tiny pump, Context, 2x2 and
DTD solves on the CPU backend under ``jax.profiler``
(``test_bench_spans_program.traced_solves``, ``test_bench_dtd``'s traced
run).  Every ``dev:submit_batch``, ``dev:wave``, ``dev:submit_one``,
``dev:stage_args`` and ``dev:epilog`` span carries ``laps`` with the
names of ``docs/TRACING.md``'s table in the table's order; a span's laps
lie inside it and leave out only its own head and tail; the first task
span of every ``Context`` drain carries the manager's stamps, and they
account for every task handed over; no span has a name the table did not
have.  Counts and structure, never a time to be believed: the CPU backend
gives no device number."""

import os
import re
import statistics

import jax
import pytest

from benchmark import harness
from benchmark.trace import phases
from benchmark.trace import spans as sp
from parsec_tpu import native

from bench_testlib import ROOT
from test_bench_dtd import traced as dtd_run  # noqa: F401  (a fixture)
from test_bench_spans_program import traced_solves

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="needs the native core")

BATCH = ("units", "waves", "retry")
WAVE = ("room", "stage", "key", "flatten", "call", "count", "commit")
STAGE = ("walk", "put", "sole", "own")
EPILOG = ("hooks", "commit", "settle", "home", "zeros", "complete")
#: span -> its laps, in order (``dev:submit_one`` has no ``room`` to wait
#: for: every other step of ``dev:wave`` exists there)
TABLE = {"dev:submit_batch": BATCH, "dev:wave": WAVE,
         "dev:submit_one": WAVE[1:], "dev:stage_args": STAGE,
         "dev:epilog": EPILOG}
STAMPS = ("hand_us", "handed", "units_us")


def traced(workload, out, solves=2):
    """``traced_solves`` and, beside what it returns, the tasks the device
    managers kept (``dev.stats["handed_direct"]``) while the profiler
    ran."""
    made, kept = [], []

    class Session(harness.Session):
        def __init__(self, *args, **kw):
            super().__init__(*args, **kw)
            made.append(self)

    def handed():
        drv = made[-1].driver
        return sum(d.stats["handed_direct"]
                   for d in getattr(drv, "devs", None) or [drv.dev])

    start, stop = jax.profiler.start_trace, jax.profiler.stop_trace

    def start_trace(*args, **kw):
        kept.append(handed())
        return start(*args, **kw)

    def stop_trace():
        kept.append(handed())
        return stop()

    with pytest.MonkeyPatch.context() as m:
        m.setattr(harness, "Session", Session)
        m.setattr(jax.profiler, "start_trace", start_trace)
        m.setattr(jax.profiler, "stop_trace", stop_trace)
        trace, spans, _ntasks = traced_solves(workload, out, solves)
    return trace.spans, spans, kept[1] - kept[0]


@pytest.fixture(scope="module")
def pump(tmp_path_factory):
    return traced("tile_pump_n8192", tmp_path_factory.mktemp("pump"))


@pytest.fixture(scope="module")
def ctx(tmp_path_factory):
    return traced("tile_ctx_n8192", tmp_path_factory.mktemp("ctx"))


@pytest.fixture(scope="module")
def mesh(tmp_path_factory):
    return traced("tile_2x2_n16384", tmp_path_factory.mktemp("mesh"),
                  solves=1)


@pytest.fixture(scope="module")
def dtd(dtd_run):
    nested, _counters, _solves = dtd_run
    return nested, nested, None


@pytest.fixture
def solve(request):
    return request.getfixturevalue(request.param)


def names_of(span):
    return tuple(n for n, _ns in phases.parse_laps(span.args["laps"]))


CELLS = ("pump", "ctx", "mesh", "dtd")


@pytest.mark.parametrize("name", sorted(TABLE))
@pytest.mark.parametrize("solve", CELLS, indirect=True)
def test_every_span_of_the_five_carries_the_tables_laps_in_its_order(
        solve, name):
    _whole, spans, _handed = solve
    mine = [s for s in spans if s.name == name]
    if name == "dev:submit_batch":
        # the pump's span: the Context path has no span over a drain
        assert bool(mine) == any(s.name.startswith("pump:") for s in spans)
    elif name not in sp.TASK_SPANS:     # (a tiny solve may have no wave)
        assert mine
    for s in mine:
        assert names_of(s) == TABLE[name], (s.name, s.args)


@pytest.mark.parametrize("solve", CELLS, indirect=True)
def test_nothing_else_carries_laps_and_the_laps_are_whole_numbers(solve):
    _whole, spans, _handed = solve
    lapped = {s.name for s in spans if "laps" in s.args}
    assert lapped <= set(TABLE) and lapped >= {"dev:stage_args",
                                               "dev:epilog"}
    for s in spans:
        if "laps" in s.args:
            assert re.fullmatch(r"[a-z]+:\d+(/[a-z]+:\d+)*", s.args["laps"])


@pytest.mark.parametrize("solve", CELLS, indirect=True)
def test_a_spans_laps_lie_inside_it_and_leave_out_its_head_and_tail(solve):
    """Σ laps <= the event's duration (two clocks: a microsecond), and
    what is left, the span's own begin and end, is under 5 us a lap for
    the median span of each name (a span that another thread took the GIL
    from at its end is longer by that, whatever the machine)."""
    whole, _spans, _handed = solve
    left = {}
    for s in whole:
        if "laps" in s.args:
            laps = phases.parse_laps(s.args["laps"])
            rest = (s.end - s.start) - sum(ns for _n, ns in laps)
            assert rest >= -1000, (s.name, s.args["laps"], s.end - s.start)
            left.setdefault(s.name, []).append(rest / len(laps))
    assert left
    for name, per_lap in left.items():
        assert statistics.median(per_lap) <= 5000, name


def first_of_each_drain(spans):
    """The task spans by ``(rank, batch)``: a drain's, in order."""
    drains = {}
    for s in sorted(spans, key=lambda s: s.start):
        if s.name in sp.TASK_SPANS:
            drains.setdefault((s.args["rank"], s.args["batch"]),
                              []).append(s)
    return drains


@pytest.mark.parametrize("solve", ("ctx", "mesh", "dtd"), indirect=True)
def test_the_first_task_span_of_every_drain_carries_the_managers_stamps(
        solve):
    whole, _spans, handed_direct = solve
    drains = first_of_each_drain(whole)
    assert len(drains) >= 2
    for (_rank, _batch), took in drains.items():
        first, rest = took[0], took[1:]
        assert all(k in first.args for k in STAMPS), first.args
        assert first.args["hand_us"] >= 0 and first.args["units_us"] > 0
        assert 0 <= first.args["handed"]
        assert not any(k in s.args for s in rest for k in STAMPS)
    stamped = [took[0] for took in drains.values()]
    # every task a manager kept was handed over before some drain, and is
    # one of the tasks the managers queued themselves (``direct``)
    handed = sum(s.args["handed"] for s in stamped)
    assert handed == sum(s.args["direct"] for s in whole
                         if s.name in sp.TASK_SPANS) > 0
    if handed_direct is not None:
        assert handed == handed_direct
    # a drain that handed nothing over took no time to speak of doing so
    assert all(s.args["hand_us"] > 0 for s in stamped if s.args["handed"])


def test_the_pump_path_has_no_manager_and_no_stamp(pump):
    whole, _spans, handed_direct = pump
    assert handed_direct == 0
    assert not any(k in s.args for s in whole for k in STAMPS)


def table_names():
    """Every span name of ``docs/TRACING.md``'s table (and of the sections
    under it that name a span's children)."""
    with open(os.path.join(ROOT, "docs", "TRACING.md")) as f:
        text = f.read()
    text = text[text.index("## Spans on the profiler's clock"):]
    return set(re.findall(
        r"`((?:attach|pump|core|dev|cc|comm|dtd):[a-z_0-9]+)`", text))


#: the names the parent's table had: this PR adds laps, not spans
PARENT_TABLE = {
    "attach:build", "attach:partition", "attach:plan", "attach:tree",
    "attach:bind", "pump:pop", "pump:stage_wait", "pump:land",
    "pump:retire", "pump:done", "pump:events", "pump:member",
    "pump:member_gap", "core:select", "core:prepare_input", "core:schedule",
    "core:complete_exec", "core:release_deps", "core:dtd_insert",
    "core:dtd_wait", "core:dtd_flush", "dtd:parked", "dev:submit_batch",
    "dev:wave", "dev:submit_one", "dev:stage_args", "dev:h2d", "dev:jit",
    "dev:dispatch", "dev:epilog", "dev:poll", "dev:block", "dev:flush",
    "dev:detach", "dev:stage_in", "dev:writeback", "dev:evict",
    "cc:compile", "comm:send", "comm:recv"}


@pytest.mark.parametrize("solve", CELLS, indirect=True)
def test_no_span_has_a_name_the_table_did_not_have(solve):
    _whole, spans, _handed = solve
    names = {s.name for s in spans if not s.name.startswith("wait:")}
    assert names <= PARENT_TABLE, names - PARENT_TABLE
    assert PARENT_TABLE <= table_names()   # the table lost none either


def test_the_nest_the_context_test_pins_is_as_it_was(ctx):
    _whole, spans, _handed = ctx
    up = {}
    for s in spans:
        up.setdefault(s.name, set()).add(s.parent.name if s.parent else None)
    assert up["core:complete_exec"] == {"dev:epilog"}
    assert up["core:select"] == up["core:prepare_input"] == {None}
    assert up["dev:wave"] | up["dev:submit_one"] == {None}
    assert up["dev:epilog"] | up["dev:stage_args"] <= {"dev:wave",
                                                       "dev:submit_one"}
