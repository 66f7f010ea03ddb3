"""Aggregated activations + broadcast propagation topologies.

Reference: one ``parsec_remote_deps_t`` per destination rank with an
output mask covering all flows (remote_dep.h:132-153), and broadcast
routing down star/chain/binomial trees with forward masks
(remote_dep.c:262-345).  These tests PIN the comm counts: aggregation
means one activation per (task, rank) and one payload per flow; binomial
means O(log R) root payload sends for a 1->R fan-out.
"""

import numpy as np
import pytest

from parsec_tpu.data import LocalCollection
from parsec_tpu.dsl.ptg import PTG, IN, INOUT
from parsec_tpu.utils import mca_param

from test_multirank import run_ranks


def test_activation_aggregation_one_message_per_rank():
    """A task with TWO data flows fanning out to THREE successor tasks on
    the same remote rank sends exactly ONE activation carrying both
    payloads once (previously: 3 activations, 3 payload copies)."""
    nranks = 2
    got = {}

    def build(rank, ctx):
        dc = LocalCollection("D", shape=(4,), nodes=nranks, myrank=rank,
                            init=lambda k: np.full(4, 1.0 + k))
        dc.rank_of = lambda *key: 0 if key[0] < 2 else 1

        ptg = PTG("agg")
        src = ptg.task_class("src")
        src.affinity("D(0)")
        src.flow("X", INOUT, "<- D(0)", "-> X a(0)", "-> X b(0)")
        src.flow("Y", INOUT, "<- D(1)", "-> Y a(0)")

        def src_body(X, Y):
            X += 10.0
            Y += 20.0

        src.body(cpu=src_body)

        def a_body(X, Y, i):
            # no writable flows: the body must return None (a returned
            # value would claim to be a flow output — loud since round 5)
            got.setdefault("a", (float(X[0]), float(Y[0])))

        a = ptg.task_class("a", i="0 .. 0")
        a.affinity("D(2)")
        a.flow("X", IN, "<- X src()")
        a.flow("Y", IN, "<- Y src()")
        a.body(cpu=a_body)

        def b_body(X, i):
            got.setdefault("b", float(X[0]))

        b = ptg.task_class("b", i="0 .. 0")
        b.affinity("D(3)")
        b.flow("X", IN, "<- X src()")
        b.body(cpu=b_body)
        return ptg.taskpool(D=dc)

    ctxs = run_ranks(nranks, build, timeout=30)
    assert got["a"] == (11.0, 22.0)
    assert got["b"] == 11.0
    rd0 = ctxs[0].comm.remote_dep
    # ONE aggregated activation for the one remote rank...
    assert rd0.stats["activations_sent"] == 1, dict(rd0.stats)
    # ...carrying each flow's payload exactly once
    assert rd0.stats["inline_sent"] == 2, dict(rd0.stats)
    assert ctxs[1].comm.remote_dep.stats["activations_recv"] == 1


def test_failed_get_fails_pool_fast():
    """A permanently lost payload (GET against a never-registered handle)
    must FAIL the taskpool promptly on EVERY rank — wait() returns False
    in seconds, not after the full timeout (the runtime knows
    the payload is gone; callers must not discover it via timeout).
    Rank 2 owns the home tile of the dead consumer's write-back (a
    pre-counted termdet runtime action) — without the abort broadcast it
    would block its full timeout even though rank 1 failed instantly."""
    import threading
    import time

    from parsec_tpu import Context
    from parsec_tpu.comm.inproc import InprocFabric

    nranks = 3
    mca_param.set_param("runtime", "comm_short_limit", 8)
    try:
        fabric = InprocFabric(nranks)
        ces = fabric.endpoints()
        # sabotage the producer: payloads are advertised but never
        # registered, so every consumer GET permanently fails
        ces[0].mem_register = lambda *a, **k: None
        ctxs = [Context(nb_cores=2, rank=r, nranks=nranks, comm=ces[r])
                for r in range(nranks)]
        waits = {}

        def build(rank, ctx):
            dc = LocalCollection("D", shape=(64,), nodes=nranks, myrank=rank,
                                 init=lambda k: np.full(64, 1.0))
            dc.rank_of = lambda *key: dc.data_key(*key) % nranks
            ptg = PTG("lost")
            src = ptg.task_class("src")
            src.affinity("D(0)")
            src.flow("X", INOUT, "<- D(0)", "-> X sink(1)")
            src.body(cpu=lambda X: X.__iadd__(1.0))
            sink = ptg.task_class("sink", r="1 .. 1")
            sink.affinity("D(r)")
            # write-back home tile D(2) lives on rank 2: that rank
            # pre-counts the write-back and can only quiesce if the
            # sink runs — or the abort reaches it
            sink.flow("X", INOUT, "<- X src()", "-> D(2)")
            sink.body(cpu=lambda X, r: None)
            return ptg.taskpool(D=dc)

        def worker(r):
            tp = build(r, ctxs[r])
            ctxs[r].add_taskpool(tp)
            t0 = time.monotonic()
            ok = tp.wait(timeout=30)
            waits[r] = (ok, time.monotonic() - t0, tp)

        threads = [threading.Thread(target=worker, args=(r,))
                   for r in range(nranks)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        # the consumer rank AND the write-back owner failed FAST (not by
        # exhausting the timeout); Context.wait agrees (pools left the
        # active set)
        for r in (1, 2):
            ok_r, dt_r, tp_r = waits[r]
            assert not ok_r and tp_r.failed, (r, waits)
            assert dt_r < 10.0, f"rank {r} fail-fast took {dt_r:.1f}s"
            assert ctxs[r].wait(timeout=5)
        for c in ctxs:
            c.fini()
    finally:
        mca_param.params.unset("runtime", "comm_short_limit")


@pytest.mark.parametrize("topo", ["star", "chain", "binomial"])
@pytest.mark.parametrize("seed", [11, 23])
def test_broadcast_topology_random_destinations_parity(topo, seed):
    """PR-8 satellite pin: for RANDOM destination subsets at 8 virtual
    ranks, every topology delivers exactly once to every destination —
    one activation received per destination, none anywhere else, the
    payload value seen exactly once — and every forwarded activation
    inherits the completing task's priority (a forwarding receiver must
    not deprioritize the rest of the tree)."""
    import threading

    from parsec_tpu import Context
    from parsec_tpu.comm.engine import TAG_ACTIVATE
    from parsec_tpu.comm.inproc import InprocFabric

    nranks = 8
    prio = 7
    rng = np.random.default_rng(seed)
    dests = sorted(rng.choice(np.arange(1, nranks), size=5,
                              replace=False).tolist())
    nd = len(dests)
    mca_param.set_param("runtime", "comm_short_limit", 64)
    mca_param.set_param("runtime", "bcast_topo", topo)
    try:
        fabric = InprocFabric(nranks)
        ces = fabric.endpoints()
        # spy BEFORE any context runs: (sender rank, priority) of every
        # activation on the wire, root sends and forwards alike
        sent = []
        sent_lock = threading.Lock()
        for ce in ces:
            orig = ce.send_am

            def spy(tag, dst, payload, *, priority=0, _ce=ce, _orig=orig,
                    **kw):
                if tag == TAG_ACTIVATE:
                    with sent_lock:
                        sent.append((_ce.rank, priority))
                return _orig(tag, dst, payload, priority=priority, **kw)

            ce.send_am = spy
        ctxs = [Context(nb_cores=2, rank=r, nranks=nranks, comm=ces[r])
                for r in range(nranks)]
        got = {r: [] for r in range(nranks)}

        def build(rank, ctx):
            dc = LocalCollection("D", shape=(256,), nodes=nranks,
                                 myrank=rank,
                                 init=lambda k: np.full(256, 7.0))
            # D(0) is the source tile on rank 0; D(1+i) places sink(i)
            # on the i-th random destination
            dc.rank_of = lambda *key: 0 if key[0] == 0 \
                else dests[key[0] - 1]

            ptg = PTG("bcast_rand")
            src = ptg.task_class("src")
            src.affinity("D(0)")
            src.priority(str(prio))
            src.flow("X", INOUT, "<- D(0)", "-> X sink(0 .. ND-1)")
            src.body(cpu=lambda X: X.__iadd__(35.0))
            sink = ptg.task_class("sink", r="0 .. ND-1")
            sink.affinity("D(r+1)")
            sink.flow("X", IN, "<- X src()")
            sink.body(cpu=lambda X, r: got[rank].append(float(X[0])))
            return ptg.taskpool(ND=nd, D=dc)

        results = {}

        def worker(r):
            tp = build(r, ctxs[r])
            ctxs[r].add_taskpool(tp)
            results[r] = tp.wait(timeout=60)

        threads = [threading.Thread(target=worker, args=(r,))
                   for r in range(nranks)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=90)
        assert all(results[r] for r in range(nranks)), results

        # exactly-once delivery: each destination saw the value once...
        for r in range(nranks):
            want = [42.0] * dests.count(r)
            assert got[r] == want, (topo, r, dests, got)
        rds = [c.comm.remote_dep for c in ctxs]
        # ...via exactly one received activation; silence elsewhere
        for r in range(nranks):
            exp = 1 if r in dests else 0
            assert rds[r].stats["activations_recv"] == exp, \
                (topo, r, dict(rds[r].stats))
        assert sum(rd.stats["activations_sent"] for rd in rds) == nd
        # forwards engage off-star and inherit the task's priority
        fwd = sum(rd.stats["forwarded"] for rd in rds)
        assert (fwd == 0) if topo == "star" else (fwd > 0), (topo, fwd)
        assert len(sent) == nd, sent
        assert all(p == prio for _r, p in sent), (topo, sent)
        if topo != "star":
            assert any(r != 0 for r, _p in sent), (topo, sent)
        for c in ctxs:
            c.fini()
    finally:
        mca_param.params.unset("runtime", "comm_short_limit")
        mca_param.params.unset("runtime", "bcast_topo")


@pytest.mark.parametrize("topo,root_sends,root_gets", [
    ("star", 7, 7),
    ("chain", 1, 1),
    ("binomial", 3, 3),   # ceil(log2(8)) payload sends at the root
])
def test_broadcast_topology_counts(topo, root_sends, root_gets):
    """1 -> R broadcast of an above-short-limit payload: under binomial
    the root ships O(log R) copies and O(R) total hops cover all ranks;
    under chain the root ships exactly one."""
    nranks = 8
    mca_param.set_param("runtime", "comm_short_limit", 64)
    mca_param.set_param("runtime", "bcast_topo", topo)
    try:
        got = {r: [] for r in range(nranks)}

        def build(rank, ctx):
            dc = LocalCollection("D", shape=(256,), nodes=nranks, myrank=rank,
                                init=lambda k: np.full(256, 7.0))
            dc.rank_of = lambda *key: dc.data_key(*key) % nranks

            ptg = PTG("bcast")
            src = ptg.task_class("src")
            src.affinity("D(0)")
            src.flow("X", INOUT, "<- D(0)", "-> X sink(0 .. NR-1)")
            src.body(cpu=lambda X: X.__iadd__(35.0))
            sink = ptg.task_class("sink", r="0 .. NR-1")
            sink.affinity("D(r)")
            sink.flow("X", IN, "<- X src()")
            sink.body(cpu=lambda X, r: got[rank].append(float(X[0])))
            return ptg.taskpool(NR=nranks, D=dc)

        ctxs = run_ranks(nranks, build, timeout=60)
        for r in range(nranks):
            assert got[r] == [42.0], (r, got)

        rds = [c.comm.remote_dep for c in ctxs]
        # exactly one activation reaches each non-root rank
        for r in range(1, nranks):
            assert rds[r].stats["activations_recv"] == 1, (r, dict(rds[r].stats))
        # one activation per destination rank in TOTAL, however routed
        assert sum(rd.stats["activations_sent"] for rd in rds) == nranks - 1
        # the root's share is the topology's fan-out
        assert rds[0].stats["activations_sent"] == root_sends, dict(rds[0].stats)
        assert rds[0].stats["get_advertised"] == root_gets, dict(rds[0].stats)
        # every rank pulled the payload exactly once, wherever from
        assert sum(rd.stats["get_issued"] for rd in rds) == nranks - 1
        # non-root forwarding only happens off-star
        fwd = sum(rd.stats["forwarded"] for rd in rds)
        assert (fwd == 0) if topo == "star" else (fwd > 0)
        # use-counted registrations self-reclaimed: no payload pinned
        assert not ctxs[0].comm.fabric.mem, ctxs[0].comm.fabric.mem
    finally:
        mca_param.params.unset("runtime", "comm_short_limit")
        mca_param.params.unset("runtime", "bcast_topo")
