"""Native-engine scheduler policies: per-worker bounded heaps with
hierarchical steal (lfq — reference mca/sched/lfq + hbbuffers,
sched_local_queues_utils.h:22-36) vs the global priority heap (gd).
"""

import pytest

from parsec_tpu import native

pytestmark = pytest.mark.skipif(
    not native.available(), reason=f"native core unavailable: {native.build_error()}")


def _wide_graph(levels, width):
    g = native.NativeGraph()
    prev, total = None, 0
    for _ in range(levels):
        src = g.add_task(0, 0)
        total += 1
        if prev is not None:
            g.add_dep(prev, src)
        kids = []
        for i in range(width):
            k = g.add_task(i % 7, 0)
            total += 1
            g.add_dep(src, k)
            kids.append(k)
        join = g.add_task(0, 0)
        total += 1
        for k in kids:
            g.add_dep(k, join)
        g.commit(src)
        for k in kids:
            g.commit(k)
        g.commit(join)
        prev = join
    g.seal()
    return g, total


@pytest.mark.parametrize("policy", ["lfq", "gd"])
def test_policies_execute_everything(policy):
    g, n = _wide_graph(4, 500)
    g.set_policy(policy)
    assert g.run_noop(8) == n


def test_lfq_steals_under_imbalance():
    """Deterministic imbalance: one source fans out 300 kids (flooding
    the completing worker's bounded local queue, cap 256; ~44 spill
    global) plus a high-priority chain head the worker KEEPS (keep-next
    fast path).  Each chain body extends the chain via streaming
    insertion until a steal is observed, so the flooding worker never
    pops its own local queue while the kids sit in it — the other
    workers drain the small global spill and then MUST steal.  The
    chain stops extending once ``g.steals > 0`` (or at a safety cap so
    a broken steal path fails the assert instead of hanging)."""
    g = native.NativeGraph()
    CHAIN, KID, SRC = 1, 0, 2
    src = g.add_task(5, SRC)  # NOT chain-tagged: exactly one chain exists,
    # so extension bodies run strictly serially (no counter race)
    head = g.add_task(10, CHAIN)  # higher prio than kids: the keep
    g.add_dep(src, head)
    kids = [g.add_task(0, KID) for _ in range(300)]
    for k in kids:
        g.add_dep(src, k)
    g.set_policy("lfq")
    extended = [0]

    def body(tid, tag):
        if tag == CHAIN and g.steals == 0 and extended[0] < 100_000:
            extended[0] += 1
            t = g.add_task(10, CHAIN)
            g.add_dep(tid, t)  # tid is mid-body: not done, edge records
            g.commit(t)

    g.commit(src)
    g.commit(head)
    for k in kids:
        g.commit(k)
    g.seal()
    executed = g.run(body, nthreads=8)
    assert executed == 302 + extended[0]  # src + head + 300 kids + chain
    assert g.steals > 0


def test_gd_never_steals():
    g, n = _wide_graph(4, 500)
    g.set_policy("gd")
    assert g.run_noop(8) == n
    assert g.steals == 0


def test_dispatch_big_graph_runs_every_task_once():
    """The 8-worker dispatch loop at the size the throughput bar was
    taken at (10 levels of 2,000: 20,020 tasks, every level a fan-out
    and a join): every task runs, and the engine's own count agrees with
    what the run returned.  How fast is not a tier-1 question (the benchmark's
    `sched_us_per_task` reads the dispatch loop on the chip)."""
    g, n = _wide_graph(10, 2000)
    assert n == 10 * 2002
    assert g.run_noop(8) == n
    assert g.executed == n


def test_python_bodies_still_correct_lfq():
    g = native.NativeGraph()
    ids = [g.add_task(0, i) for i in range(200)]
    for i in range(1, 200):
        g.add_dep(ids[(i - 1) // 2], ids[i])
    for i in ids:
        g.commit(i)
    g.seal()
    g.set_policy("lfq")
    seen = []
    g.run(lambda tid, tag: seen.append(tag), nthreads=4)
    assert sorted(seen) == list(range(200))


def test_hierarchical_steal_vpmap():
    """2-level steal: with a vpmap, victims in the SAME VP are tried
    before crossing domains.  Deterministic pins: one-VP-per-worker
    forces every steal cross-VP; all-one-VP forces every steal local."""
    import numpy as np

    from parsec_tpu import native

    if not native.available():
        import pytest

        pytest.skip(f"native core unavailable: {native.build_error()}")

    def run_fan(vpmap):
        ng = native.NativeGraph()
        # a root fanning out to many tiny tasks: the completing worker
        # keeps one and floods its local heap; others must steal
        root = ng.add_task(priority=0, user_tag=0)
        for _ in range(200):
            t = ng.add_task(priority=0, user_tag=0)
            ng.add_dep(root, t)
        for tid in range(201):
            ng.commit(tid)
        ng.seal()
        if vpmap is not None:
            ng.set_vpmap(vpmap)
        done = []

        def body(tid, tag):
            x = 0.0
            for i in range(200):
                x += i * 1.0
            done.append(tid)

        n = ng.run(body, nthreads=4)
        assert n == 201
        return ng.steals, ng.steals_remote

    s, r = run_fan([0, 0, 0, 0])  # one VP: nothing is ever cross-VP
    assert r == 0
    s2, r2 = run_fan([0, 1, 2, 3])  # one worker per VP: all steals cross
    assert s2 == r2
    s3, r3 = run_fan(None)  # flat (no vpmap): remote counter unused
    assert r3 == 0
