"""Native C++ core: zone allocator + dataflow graph engine (the runtime's
native hot-path layer; reference roles: zone_malloc.c, scheduling.c)."""

import threading

import numpy as np
import pytest

from parsec_tpu import native


pytestmark = pytest.mark.skipif(
    not native.available(), reason=f"native core unavailable: {native.build_error()}")


# -- zone allocator ---------------------------------------------------------

def test_zone_alloc_release_coalesce():
    z = native.ZoneAllocator(1 << 20)
    a = z.alloc(1000)
    b = z.alloc(2000)
    c = z.alloc(4000)
    assert {a, b, c} and len({a, b, c}) == 3
    assert z.used == 1000 + 2000 + 4000
    # free the middle, then neighbours: everything must coalesce back
    z.release(b)
    z.release(a)
    z.release(c)
    assert z.used == 0
    assert z.largest_free == z.capacity
    z.close()


def test_zone_alignment_and_exhaustion():
    z = native.ZoneAllocator(4096)
    off = z.alloc(100, align=256)
    assert off % 256 == 0
    assert z.alloc(1 << 30) is None  # larger than capacity
    # fill completely
    got = []
    while True:
        o = z.alloc(512, align=1)
        if o is None:
            break
        got.append(o)
    assert z.alloc(512, align=1) is None
    for o in got:
        z.release(o)
    assert z.used >= 100  # the aligned first block still accounted
    z.release(off)
    assert z.used == 0  # nothing leaked or double-freed
    z.close()


def test_zone_unknown_offset_rejected():
    z = native.ZoneAllocator(1024)
    with pytest.raises(ValueError):
        z.release(12345)
    z.close()


def test_zone_use_after_close_raises_instead_of_crashing():
    """A closed zone must refuse every call: handing its cleared handle
    to the library dereferences NULL (seen on the chip as a SIGSEGV when
    the cycle collector finalized the zone before a device whose
    detach() still read ``used``)."""
    z = native.ZoneAllocator(1024)
    z.close()
    for call in (lambda: z.alloc(16), lambda: z.release(0), lambda: z.used,
                 lambda: z.capacity, lambda: z.largest_free,
                 lambda: z.num_live):
        with pytest.raises(RuntimeError, match="closed"):
            call()
    z.close()  # idempotent


def test_zone_threaded_stress():
    z = native.ZoneAllocator(1 << 22)
    errs = []

    def churn(seed):
        rng = np.random.default_rng(seed)
        mine = []
        try:
            for _ in range(500):
                if mine and rng.random() < 0.45:
                    z.release(mine.pop(rng.integers(len(mine))))
                else:
                    o = z.alloc(int(rng.integers(64, 4096)))
                    if o is not None:
                        mine.append(o)
            for o in mine:
                z.release(o)
        except Exception as e:  # pragma: no cover
            errs.append(e)

    ts = [threading.Thread(target=churn, args=(i,)) for i in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs
    assert z.used == 0
    z.close()


# -- graph engine -----------------------------------------------------------

def test_graph_chain_order_and_run():
    g = native.NativeGraph()
    ids = [g.add_task(user_tag=i) for i in range(10)]
    for a, b in zip(ids, ids[1:]):
        g.add_dep(a, b)
    assert g.order() == ids  # chain has a unique order

    ran = []
    for t in ids:
        g.commit(t)
    g.seal()
    n = g.run(lambda tid, tag: ran.append(tag), nthreads=2)
    assert n == 10
    assert ran == list(range(10))
    g.close()


def test_graph_priority_order():
    """Independent tasks come out highest-priority-first."""
    g = native.NativeGraph()
    ids = [g.add_task(priority=p) for p in (1, 9, 5, 7, 3)]
    order = g.order()
    prios = [(1, 9, 5, 7, 3)[i] for i in order]
    assert prios == sorted(prios, reverse=True)
    g.close()


def test_graph_diamond_respects_deps():
    g = native.NativeGraph()
    a, b, c, d = (g.add_task(user_tag=t) for t in range(4))
    g.add_dep(a, b)
    g.add_dep(a, c)
    g.add_dep(b, d)
    g.add_dep(c, d)
    seen = []
    lock = threading.Lock()
    for t in (a, b, c, d):
        g.commit(t)
    g.seal()
    g.run(lambda tid, tag: (lock.acquire(), seen.append(tag), lock.release()),
          nthreads=3)
    assert seen[0] == 0 and seen[-1] == 3 and set(seen) == {0, 1, 2, 3}
    g.close()


def test_graph_cycle_detected():
    g = native.NativeGraph()
    a = g.add_task()
    b = g.add_task()
    g.add_dep(a, b)
    g.add_dep(b, a)
    with pytest.raises(RuntimeError):
        g.order()
    g.close()


def test_graph_streaming_insertion():
    """DTD shape: a running body inserts more tasks."""
    g = native.NativeGraph()
    ran = []
    lock = threading.Lock()

    def body(tid, tag):
        with lock:
            ran.append(tag)
        if tag < 5:  # each task spawns the next (task-inserting-task)
            nxt = g.add_task(user_tag=tag + 1)
            g.add_dep(tid, nxt)  # returns False (tid still running? no: running != done)
            g.commit(nxt)
        if tag == 5:
            g.seal()

    first = g.add_task(user_tag=0)
    g.commit(first)
    n = g.run(body, nthreads=2)
    assert n == 6
    assert ran == [0, 1, 2, 3, 4, 5]
    g.close()


def test_graph_body_exception_propagates():
    g = native.NativeGraph()
    t = g.add_task()
    g.commit(t)
    g.seal()
    with pytest.raises(ZeroDivisionError):
        g.run(lambda tid, tag: 1 / 0, nthreads=1)
    g.close()


def test_graph_edge_to_done_pred_reports_satisfied():
    g = native.NativeGraph()
    a = g.add_task()
    g.commit(a)

    def body(tid, tag):
        pass

    # run a first, then add b depending on a: add_dep must report False
    t = threading.Thread(target=lambda: g.run(body, nthreads=1))
    b = g.add_task()
    t.start()
    import time
    deadline = time.monotonic() + 10
    while g.executed < 1:  # wait until a actually executed
        assert time.monotonic() < deadline, "runner never executed task a"
        time.sleep(0.005)
    assert g.add_dep(a, b) is False
    g.commit(b)
    g.seal()
    t.join(timeout=10)
    assert g.executed == 2
    g.close()


def test_graph_large_order_fast():
    """50k-task tiled-cholesky-shaped DAG orders quickly (native path)."""
    import time

    g = native.NativeGraph()
    NT = 36  # ~ NT^3/6 + O(NT^2) tasks
    ids = {}
    for k in range(NT):
        ids[("p", k)] = g.add_task(priority=3 * (NT - k))
        for i in range(k + 1, NT):
            ids[("t", k, i)] = g.add_task(priority=2 * (NT - k))
        for i in range(k + 1, NT):
            for j in range(k + 1, i + 1):
                ids[("g", k, i, j)] = g.add_task(priority=NT - k)
    for k in range(NT):
        for i in range(k + 1, NT):
            g.add_dep(ids[("p", k)], ids[("t", k, i)])
            for j in range(k + 1, i + 1):
                g.add_dep(ids[("t", k, i)], ids[("g", k, i, j)])
                if j < i:
                    g.add_dep(ids[("t", k, j)], ids[("g", k, i, j)])
        if k + 1 < NT:
            g.add_dep(ids[("g", k, k + 1, k + 1)], ids[("p", k + 1)])
    t0 = time.perf_counter()
    order = g.order()
    dt = time.perf_counter() - t0
    assert len(order) == len(ids)
    pos = {t: i for i, t in enumerate(order)}
    # spot-check dependency respect
    assert pos[ids[("p", 0)]] < pos[ids[("t", 0, 1)]] < pos[ids[("g", 0, 1, 1)]]
    assert dt < 2.0, f"native order too slow: {dt:.3f}s for {len(ids)} tasks"
    g.close()


# -- ASYNC chore protocol (pz_graph_run_async / pz_task_done) ----------------

def test_graph_async_out_of_order_completion():
    """ASYNC chores complete OUT OF ORDER from background threads via
    task_done; successor release order must still respect the DAG, and
    shutdown is clean with straggler callbacks still in flight (the
    device-manager completion shape behind native device dispatch)."""
    import time

    g = native.NativeGraph()
    # diamond: a -> (b, c) -> d ; b and c are ASYNC, completed by
    # background threads in REVERSE submission order
    a, b, c, d = (g.add_task() for _ in range(4))
    g.add_dep(a, b)
    g.add_dep(a, c)
    g.add_dep(b, d)
    g.add_dep(c, d)
    for t in (a, b, c, d):
        g.commit(t)
    g.seal()

    started, done_order = [], []
    lock = threading.Lock()
    threads = []

    def complete_later(tid, delay):
        time.sleep(delay)
        with lock:
            done_order.append(tid)
        assert g.task_done(tid) is True

    def body(tid, tag):
        with lock:
            started.append(tid)
        if tid in (b, c):
            # b (submitted first) completes LAST: out-of-order wrt submit
            delay = 0.08 if tid == b else 0.02
            th = threading.Thread(target=complete_later, args=(tid, delay))
            threads.append(th)
            th.start()
            return True  # ASYNC
        return False

    n = g.run_async(body, nthreads=2)
    assert n == 4
    # d ran only after BOTH async completions; c's completion preceded b's
    assert started[0] == a and started[-1] == d
    assert done_order == [c, b]
    assert set(started) == {a, b, c, d}
    for th in threads:
        th.join(timeout=5)
    # straggler callback after shutdown: harmless no-op, not a crash
    assert g.task_done(b) is False
    with pytest.raises(ValueError):
        g.task_done(999)
    g.close()


def test_graph_async_release_order_chain():
    """A chain behind an ASYNC head must not start until task_done."""
    import time

    g = native.NativeGraph()
    head = g.add_task()
    succ = g.add_task()
    g.add_dep(head, succ)
    g.commit(head)
    g.commit(succ)
    g.seal()
    events = []

    def body(tid, tag):
        events.append(("run", tid, time.monotonic()))
        if tid == head:
            def later():
                time.sleep(0.05)
                events.append(("done", head, time.monotonic()))
                g.task_done(head)
            threading.Thread(target=later).start()
            return True
        return False

    assert g.run_async(body, nthreads=2) == 2
    kinds = [(k, t) for (k, t, _ts) in events]
    assert kinds == [("run", head), ("done", head), ("run", succ)]
    g.close()


def test_graph_async_body_error_aborts_run():
    """A raising async-path body must abort the run loudly, never hang
    waiting for a completion that cannot arrive."""
    g = native.NativeGraph()
    a = g.add_task()
    b = g.add_task()
    g.add_dep(a, b)
    g.commit(a)
    g.commit(b)
    g.seal()

    def body(tid, tag):
        raise RuntimeError("enqueue exploded")

    with pytest.raises(RuntimeError, match="enqueue exploded"):
        g.run_async(body, nthreads=2)
    g.close()


def test_graph_fail_unblocks_async_run():
    """fail() releases workers parked on an ASYNC task whose completion
    never arrives (the failed-device-pool shape)."""
    import time

    g = native.NativeGraph()
    a = g.add_task()
    g.commit(a)
    g.seal()

    def body(tid, tag):
        threading.Thread(target=lambda: (time.sleep(0.05), g.fail())).start()
        return True  # ASYNC, and nobody will ever complete it

    with pytest.raises(RuntimeError, match="did not quiesce"):
        g.run_async(body, nthreads=2)
    g.close()


def test_native_required_symbols_present():
    """Build smoke (CI): every C entry point the bindings need exists in
    the built library — a stale native/build fails HERE with a readable
    message instead of a ctypes AttributeError deep in a consumer."""
    assert native.missing_symbols() == []
    for sym in ("pz_task_done", "pz_graph_run_async", "pz_graph_fail"):
        assert sym in native.REQUIRED_SYMBOLS


def test_graph_task_done_after_close_is_noop():
    """The shutdown promise holds even past close(): a straggler
    task_done/fail on a closed graph is a harmless no-op, never a NULL
    handle into the C layer."""
    g = native.NativeGraph()
    a = g.add_task()
    g.commit(a)
    g.seal()
    g.run_async(lambda tid, tag: False, nthreads=1)
    g.close()
    assert g.task_done(a) is False
    g.fail()  # no-op on a closed graph, not a crash
