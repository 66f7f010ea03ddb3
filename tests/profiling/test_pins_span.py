"""``pins.span``: one begin/end pair, two sinks.  Without a profiler
session and without a subscriber it records nothing and calls nobody; a
subscriber gets begin and end in order with the payload the site carries;
a raise inside still closes both sinks; under ``jax.profiler.trace`` the
span is an event of the host plane with its arguments and ``cpu_us``, the
thread's CPU time inside it; ``pins.wait`` and ``pins.held`` leave
``parsec-wait:*`` events there, and nothing at all without a session."""

import glob
import os
import threading
import time

import jax
import pytest

from parsec_tpu.profiling import pins


@pytest.fixture(autouse=True)
def _clean_pins():
    pins.clear()
    yield
    pins.clear()


@pytest.fixture(autouse=True)
def a_free_clock(monkeypatch):
    """On a loaded machine a read of the thread-CPU clock can take over a
    microsecond, and the budget (``pins._cpu_tree``) would then leave
    some spans untimed: here no read is debited, so every span of a
    session carries ``cpu_us`` whatever ran before (the budget has a
    test of its own, which sets the allowance back)."""
    monkeypatch.setattr(pins, "_CPU_FREE_NS", 10 ** 12)
    monkeypatch.setattr(pins, "_cpu_credit_ns", float(pins._CPU_BURST_NS))


def _record(site, log):
    def cb(es, payload):
        log.append((site, es, payload))
    pins.subscribe(site, cb)


def _events(trace_dir, prefix):
    """``(name, arguments, duration in us)`` of the host events whose
    name starts with ``prefix``."""
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out += [(e.name, dict(e.stats), e.duration_ns / 1e3)
                    for e in line.events if e.name.startswith(prefix)]
    return out


def _parsec_events(trace_dir):
    return [(n, args) for n, args, _us in _events(trace_dir, "parsec:")]


def _wait_events(trace_dir):
    return _events(trace_dir, "parsec-wait:")


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
    # (``cpu_us``, which every event of a session carries, has its own
    # tests below)
    assert events["parsec:dev:wave"].pop("cpu_us") >= 0
    assert events["parsec:dev:stage_args"].pop("cpu_us") >= 0
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


# ---------------------------------------------------------------------------
# how long the thread worked, how long it waited, and for what
# ---------------------------------------------------------------------------

def test_without_a_session_held_is_the_lock_and_wait_is_the_no_op():
    lock = threading.RLock()
    assert pins.held(lock, "x") is lock
    assert pins.wait("x") is pins._QUIET
    # a PINS subscriber is no session: a wait has no site to fire
    _record("wait:x_begin", [])
    assert pins.held(lock, "x") is lock
    assert pins.wait("x", n=1) is pins._QUIET


def _burn(cpu_s):
    """Spin until the calling thread has used ``cpu_s`` of the CPU by its
    own clock, the one ``cpu_us`` is read from: a span around this reads
    at least that much whatever else the machine runs."""
    t0 = time.thread_time()
    x = 0
    while time.thread_time() - t0 < cpu_s:
        x += 1


def test_every_span_of_a_session_carries_its_threads_cpu_time(tmp_path):
    """The lengths are the test's own: a sleep of 100 ms (off the CPU)
    and a loop that ends when the thread has used 50 ms of it.  No bound
    leans on how the machine shared its cores meanwhile."""
    with jax.profiler.trace(str(tmp_path)):
        with pins.span("dev:wave", pool=1, rank=0, n=2):
            with pins.span("dev:dispatch", pool=1, rank=0):
                time.sleep(0.1)         # off the CPU
            with pins.span("dev:epilog", pool=1, rank=0) as sp:
                _burn(0.05)             # on it
                sp.note(n=2)
        with pins.wait("d2h_start") as w:
            w.note(n=3, bytes=4096)
    events = _events(tmp_path, "parsec")
    assert [n for n, _a, _us in events] == [
        "parsec:dev:wave", "parsec:dev:dispatch", "parsec:dev:epilog",
        "parsec-wait:d2h_start"]
    for name, args, us in events:
        # the reads lie inside the event (two clocks: 50 us)
        assert 0 <= args["cpu_us"] <= us + 50, name
    by_name = {n: (a, us) for n, a, us in events}
    args, us = by_name["parsec:dev:dispatch"]
    # (a sleeping thread uses none: the allowance is 25 ms and more,
    # over two ticks of the coarsest clock the runtime meets)
    assert us >= 100e3 and args["cpu_us"] < 0.25 * us
    args, us = by_name["parsec:dev:epilog"]
    # (the span's two reads enclose the loop's own, on the same clock)
    assert args["cpu_us"] >= 50e3 and args["n"] == 2
    args, us = by_name["parsec:dev:wave"]  # a parent's holds its children's
    assert args["cpu_us"] >= by_name["parsec:dev:epilog"][0]["cpu_us"]
    assert by_name["parsec-wait:d2h_start"][0]["n"] == 3
    assert by_name["parsec-wait:d2h_start"][0]["bytes"] == 4096


def test_where_the_clock_is_dear_whole_trees_are_timed_within_a_budget(
        tmp_path, monkeypatch):
    """A read of the thread-CPU clock that takes 0.4 ms (~17 us under
    the benchmark machine's kernel; 0.25 us here): the burst pays for the
    first tree of three events, the 0.5% of the wall time for one more
    after a pause; a tree is timed as a whole or not at all, and every
    event is recorded either way."""
    real = time.thread_time_ns

    def dear():
        t = time.perf_counter_ns()
        while time.perf_counter_ns() - t < 400_000:
            pass
        return real()

    monkeypatch.setattr(pins, "_thread_cpu_ns", dear)
    monkeypatch.setattr(pins, "_CPU_FREE_NS", 1_000)
    # (0.4 ms IS the read; a bracket that a loaded machine stretches
    # further is debited 0.5 ms at most, which the pause below repays)
    monkeypatch.setattr(pins, "_CPU_DEAR_NS", 500_000)
    monkeypatch.setattr(pins, "_cpu_credit_ns", float(pins._CPU_BURST_NS))
    monkeypatch.setattr(pins, "_cpu_credit_at", time.perf_counter_ns())

    def tree(k):
        with pins.span("dev:submit_batch", pool=1, rank=0, batch=k):
            with pins.span("dev:wave", pool=1, rank=0, batch=k):
                with pins.wait("d2h_start") as w:
                    w.note(n=k)

    with jax.profiler.trace(str(tmp_path)):
        for k in range(20):
            tree(k)
        time.sleep(0.3)     # 0.5% of it: 1.5 ms, against 0.4-1.0 overdrawn
        tree(20)
        tree(21)
    events = _events(tmp_path, "parsec")
    assert len(events) == 3 * 22
    timed = {name: sorted(a.get("batch", a.get("n")) for n, a, _us in events
                          if n == name and "cpu_us" in a)
             for name in ("parsec:dev:submit_batch", "parsec:dev:wave",
                          "parsec-wait:d2h_start")}
    assert timed["parsec:dev:submit_batch"] == timed["parsec:dev:wave"] \
        == timed["parsec-wait:d2h_start"] == [0, 20]
    assert pins._open_spans() == []


def test_a_span_heard_only_by_a_subscriber_reads_no_clock():
    log = []
    _record("dev:wave_end", log)
    with pins.span("dev:wave", pool=1, rank=0) as sp:
        assert sp._cpu0 is None
    assert log == [("dev:wave_end", None, {"pool": 1, "rank": 0})]
    assert pins._open_spans() == []


def test_a_wait_for_a_held_lock_is_one_event_that_names_the_holder(tmp_path):
    lock = threading.RLock()
    taken, waited = threading.Event(), []

    def holder():
        with pins.span("dev:stage_in", pool=1, rank=0):
            with pins.held(lock, "res_lock"):
                # the holder is named by what it is in when the wait
                # BEGINS, not by what it was in when it took the lock
                with pins.span("dev:evict", pool=1, rank=0):
                    taken.set()
                    time.sleep(0.05)

    with jax.profiler.trace(str(tmp_path)):
        t = threading.Thread(target=holder)
        t.start()
        taken.wait()
        with pins.span("dev:epilog", pool=1, rank=0):
            t0 = time.perf_counter()
            with pins.held(lock, "res_lock"):
                waited.append(time.perf_counter() - t0)
                with pins.held(lock, "res_lock"):  # the holder, again
                    pass
        t.join()
        with pins.held(lock, "res_lock"):  # nobody holds it
            pass
    (name, args, us), = _wait_events(tmp_path)
    assert name == "parsec-wait:res_lock"
    assert args["holder"] == "dev:evict"
    assert us >= 40e3 and us <= waited[0] * 1e6 + 50
    assert args["cpu_us"] < 0.25 * us  # a blocked thread is off the CPU
    assert pins._holders == {} and pins._open_spans() == []


class _TellingLock:
    """A lock that says when a thread is about to block on it, so that
    a test can hold it for a known time FROM then."""

    def __init__(self):
        self._lock = threading.Lock()
        self.about_to_block = threading.Event()

    def acquire(self, blocking=True):
        if self._lock.acquire(False):
            return True
        if not blocking:
            return False
        self.about_to_block.set()
        return self._lock.acquire()

    def release(self):
        self._lock.release()


def test_a_lock_taken_bare_has_no_name_to_give(tmp_path):
    """The lock is let go 30 ms after the waiter said it was about to
    block, and the wait's event began before it said so: the event holds
    the whole of the sleep, however late either thread got the CPU."""
    lock = _TellingLock()

    def let_go():
        lock.about_to_block.wait()
        time.sleep(0.03)
        lock.release()

    with jax.profiler.trace(str(tmp_path)):
        lock.acquire()  # as a bare ``with lock:`` would
        t = threading.Thread(target=let_go)
        t.start()
        with pins.held(lock, "dev_lock"):
            pass
        t.join()
    (name, args, us), = _wait_events(tmp_path)
    assert name == "parsec-wait:dev_lock" and args["holder"] == "none"
    assert us >= 30e3 - 50  # (two clocks)
    assert pins._holders == {}


def test_the_wait_for_the_gil_is_the_time_off_the_cpu(tmp_path):
    """Duration minus ``cpu_us`` is the time off the CPU, and a wait for
    the GIL is such time with no event of its own.  A Python loop that
    ends when its thread has used 30 ms of the CPU reads at least that
    in ``cpu_us`` (and no more than its wall time).  A span whose thread
    needs the GIL while another thread holds it through ONE call into C
    (``sum(range(..))`` gives it up nowhere) is off the CPU for at
    least as long as that call computes, which the other thread reads
    from its own CPU clock (its wall clock would count the wait to get
    the GIL BACK after the call, which is not the span's); the span
    began before the call did and cannot end before the call has."""
    in_c, held_s = threading.Event(), []

    def hold_the_gil():
        c0 = time.thread_time()
        in_c.set()
        sum(range(5_000_000))   # ~70 ms of CPU, all of it with the GIL
        held_s.append(time.thread_time() - c0)

    with jax.profiler.trace(str(tmp_path)):
        with pins.span("pump:land", pool=1, rank=0, alone=1):
            _burn(0.03)
        t = threading.Thread(target=hold_the_gil)
        with pins.span("pump:land", pool=1, rank=0, alone=0):
            t.start()
            in_c.wait()         # returns once the GIL comes back
        t.join()
    spans = {args["alone"]: (args["cpu_us"], us)
             for _n, args, us in _events(tmp_path, "parsec:pump:land")}
    cpu, us = spans[1]
    assert 30e3 <= cpu <= us + 50
    cpu, us = spans[0]
    # (10 ms: a tick of the coarsest thread-CPU clock the runtime meets)
    assert us - cpu >= held_s[0] * 1e6 - 10e3, (spans, held_s)


def test_a_solve_beside_a_session_leaves_the_locks_as_they_were(tmp_path):
    """A pump solve inside a session: every ``parsec:*`` event carries
    ``cpu_us``, the waits that were recorded are of the known kinds,
    each ``wait:res_lock`` names a span (or nothing) as its holder, the
    copies home are started under ``wait:d2h_start`` with their count,
    and nothing is left held."""
    import numpy as np

    from parsec_tpu.datadist import TiledMatrix
    from parsec_tpu.dsl.native_exec import NativeExecutor
    from parsec_tpu.ops.cholesky import cholesky_ptg

    n, nb = 64, 16
    M = np.random.default_rng(3).standard_normal((n, n))
    A = TiledMatrix(n, n, nb, nb, name="A", dtype=np.float64).from_array(
        M @ M.T + n * np.eye(n))
    tp = cholesky_ptg(use_tpu=True, use_cpu=False).taskpool(NT=A.mt, A=A)
    with jax.profiler.trace(str(tmp_path)):
        ex = NativeExecutor(tp, native_device=True)
        ex.run()
        dev = ex.device
        ex.close()
    spans = _parsec_events(tmp_path)
    assert {"parsec:dev:wave", "parsec:dev:dispatch",
            "parsec:dev:epilog"} <= {n for n, _a in spans}
    assert all("cpu_us" in a for _n, a in spans)
    waits = _wait_events(tmp_path)
    assert {n for n, _a, _us in waits} <= {
        "parsec-wait:res_lock", "parsec-wait:dev_lock",
        "parsec-wait:wb_capacity", "parsec-wait:d2h_start"}
    names = {n[len("parsec:"):] for n, _a in spans} | {"none"}
    assert all(a["holder"] in names for n, a, _us in waits
               if n == "parsec-wait:res_lock")
    starts = [a for n, a, _us in waits if n == "parsec-wait:d2h_start"]
    tiles_home = A.mt * (A.mt + 1) // 2
    assert sum(a["n"] for a in starts) >= dev.stats["wb_started_early"] \
        == tiles_home
    assert pins._holders == {}
    assert np.allclose(np.tril(A.to_array()), np.linalg.cholesky(
        M @ M.T + n * np.eye(n)))
