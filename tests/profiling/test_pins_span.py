"""``pins.span``: one begin/end pair, two sinks.  Without a profiler
session and without a subscriber it records nothing and calls nobody; a
subscriber gets begin and end in order with the payload the site carries;
a raise inside still closes both sinks; under ``jax.profiler.trace`` the
span is an event of the host plane with its arguments."""

import glob
import os

import jax
import pytest

from parsec_tpu.profiling import pins


@pytest.fixture(autouse=True)
def _clean_pins():
    pins.clear()
    yield
    pins.clear()


def _record(site, log):
    def cb(es, payload):
        log.append((site, es, payload))
    pins.subscribe(site, cb)


def _parsec_events(trace_dir):
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out += [(e.name, dict(e.stats)) for e in line.events
                    if e.name.startswith("parsec:")]
    return out


def test_without_session_and_subscriber_nothing_is_recorded_or_called():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    called = []
    _record("other_begin", called)
    with pins.span("pump:pop", pool=1, rank=0, batch=3) as sp:
        sp.note(n=4)
    assert called == []
    pins.clear()
    # with no sink at all it is one shared object that holds nothing
    assert pins.span("pump:pop", pool=1) is pins.span("dev:wave", pool=2)
    assert not pins.active("pump:pop_begin")
    assert not pins.active("pump:pop_end")


# (span name, the PINS sites it fires): pairs that predate the primitive
# keep the names their subscribers know, new spans fire <name>_begin/_end
SITES = [("core:select", pins.SELECT_BEGIN, pins.SELECT_END),
         ("core:prepare_input", pins.PREPARE_INPUT_BEGIN,
          pins.PREPARE_INPUT_END),
         ("core:complete_exec", pins.COMPLETE_EXEC_BEGIN,
          pins.COMPLETE_EXEC_END),
         ("core:release_deps", pins.RELEASE_DEPS_BEGIN,
          pins.RELEASE_DEPS_END),
         ("core:schedule", pins.SCHEDULE_BEGIN, pins.SCHEDULE_END),
         ("dev:stage_in", pins.STAGE_IN_BEGIN, pins.STAGE_IN_END),
         ("dev:writeback", pins.WRITEBACK_BEGIN, pins.WRITEBACK_END),
         ("cc:compile", pins.COMPILE_BEGIN, pins.COMPILE_END),
         ("comm:send", pins.COMM_SEND_BEGIN, pins.COMM_SEND_END),
         ("comm:recv", pins.COMM_RECV_BEGIN, pins.COMM_RECV_END),
         ("pump:pop", "pump:pop_begin", "pump:pop_end"),
         ("dev:wave", "dev:wave_begin", "dev:wave_end")]


@pytest.mark.parametrize("name, begin, end", SITES)
def test_a_subscriber_gets_begin_then_end_with_the_payload(name, begin, end):
    log = []
    _record(begin, log)
    _record(end, log)
    task, es = object(), object()
    with pins.span(name, es, task, pool=5, rank=2):
        log.append("inside")
    assert log == [(begin, es, task), "inside", (end, es, task)]


def test_the_keyword_arguments_are_the_payload_where_no_task_is_given():
    log = []
    _record("dev:wave_begin", log)
    _record("dev:wave_end", log)
    with pins.span("dev:wave", pool=5, rank=2, n=8) as sp:
        sp.note(host_tiles=3)
    (_, _, at_begin), (_, _, at_end) = log
    assert at_begin == {"pool": 5, "rank": 2, "n": 8}  # BEGIN's is not edited
    assert at_end == {"pool": 5, "rank": 2, "n": 8, "host_tiles": 3}


def test_end_gives_the_end_site_a_payload_of_its_own():
    log = []
    _record(pins.RELEASE_DEPS_BEGIN, log)
    _record(pins.RELEASE_DEPS_END, log)
    task, ready = object(), [object()]
    with pins.span("core:release_deps", None, task, pool=1, rank=0) as sp:
        sp.end((task, ready))
    assert [p for _, _, p in log] == [task, (task, ready)]


def test_only_the_subscribed_site_is_fired():
    log = []
    _record(pins.SELECT_END, log)
    with pins.span("core:select", None, rank=0) as sp:
        sp.end("the task")
    assert log == [(pins.SELECT_END, None, "the task")]


def test_a_raise_inside_still_closes_both_sinks(tmp_path):
    log = []
    _record("dev:dispatch_begin", log)
    _record("dev:dispatch_end", log)
    with jax.profiler.trace(str(tmp_path)):
        with pytest.raises(ZeroDivisionError):
            with pins.span("dev:dispatch", pool=9, rank=0):
                1 / 0
        with pins.span("dev:jit", pool=9, rank=0):
            pass
    assert [s for s, _, _ in log] == ["dev:dispatch_begin",
                                      "dev:dispatch_end"]
    names = [n for n, _ in _parsec_events(tmp_path)]
    # the raising span was closed: the next one is its sibling, recorded
    assert names == ["parsec:dev:dispatch", "parsec:dev:jit"]


def test_under_a_profiler_session_the_span_is_an_event_with_its_arguments(
        tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        assert jax.profiler.TraceAnnotation.is_enabled()
        with pins.span("dev:wave", pool=11, rank=1, cls="gemm", n=4) as sp:
            with pins.span("dev:stage_args", pool=11, rank=1) as inner:
                inner.note(tiles=12, host_tiles=2, bytes=2 << 20)
            sp.note(late=1)
    events = dict(_parsec_events(tmp_path))
    assert events["parsec:dev:wave"] == {"pool": 11, "rank": 1,
                                         "cls": "gemm", "n": 4, "late": 1}
    assert events["parsec:dev:stage_args"] == {
        "pool": 11, "rank": 1, "tiles": 12, "host_tiles": 2,
        "bytes": 2 << 20}
    assert not jax.profiler.TraceAnnotation.is_enabled()


def test_the_new_spans_reach_the_rank_traces_through_one_table(tmp_path):
    from parsec_tpu import native
    from parsec_tpu.profiling import binary

    if not native.available():
        pytest.skip("needs the native core")
    ts = binary.RankTraceSet(nranks=2).install()
    try:
        for name in binary.SPAN_KEYWORDS:
            assert pins.active(name + "_begin") and pins.active(name + "_end")
        with pins.span("pump:pop", pool=1, rank=1, batch=7) as sp:
            sp.note(n=5)
        with pins.span("dev:wave", pool=1, rank=0, batch=7, n=4):
            pass
        paths = ts.dump(str(tmp_path))
    finally:
        ts.uninstall()
        ts.close()
    by_rank = {r: [(e["name"], e["ph"], e["args"]["event_id"],
                    e["args"]["info"]) for e in binary.read_pbt(p)]
               for r, p in enumerate(paths)}
    assert by_rank[1] == [("pump:pop", "B", 7, 0), ("pump:pop", "E", 7, 5)]
    assert by_rank[0] == [("dev:wave", "B", 7, 4), ("dev:wave", "E", 7, 4)]


def test_the_write_back_says_how_often_a_copy_was_started_ahead():
    """``dev.stats`` carries ``wb_started_early`` / ``wb_early_hits`` and
    every ``dev:writeback`` span ``wait_us`` (its one wait) and ``early``
    (tiles whose copy home was started at hand-over for the version
    collected): a pump solve starts every tile that goes home ahead."""
    import numpy as np

    from parsec_tpu.datadist import TiledMatrix
    from parsec_tpu.dsl.native_exec import NativeExecutor
    from parsec_tpu.ops.cholesky import cholesky_ptg

    n, nb = 64, 16
    M = np.random.default_rng(2).standard_normal((n, n))
    A = TiledMatrix(n, n, nb, nb, name="A", dtype=np.float64).from_array(
        M @ M.T + n * np.eye(n))
    tp = cholesky_ptg(use_tpu=True, use_cpu=False).taskpool(NT=A.mt, A=A)
    log = []
    _record(pins.WRITEBACK_BEGIN, log)
    _record(pins.WRITEBACK_END, log)
    ex = NativeExecutor(tp, native_device=True)
    dev = ex.device
    assert dev.stats["wb_started_early"] == dev.stats["wb_early_hits"] == 0
    ex.run()
    ex.close()
    ends = [p for site, _es, p in log if site == pins.WRITEBACK_END]
    assert ends and len(ends) * 2 == len(log)
    for p in ends:
        assert p["wait_us"] >= 0 and 0 <= p["early"] <= p["tiles"]
        assert {"id", "tiles", "bytes", "batch", "pool", "rank"} <= set(p)
    assert all("wait_us" not in p for site, _es, p in log
               if site == pins.WRITEBACK_BEGIN)  # known at the end only
    tiles_home = A.mt * (A.mt + 1) // 2
    assert sum(p["tiles"] for p in ends) == tiles_home
    assert sum(p["early"] for p in ends) == dev.stats["wb_early_hits"] \
        == dev.stats["wb_started_early"] == tiles_home
