"""Fairness floor (acceptance criterion): while a >=5k-task dpotrf
runs, concurrently submitted small jobs must complete with p95 latency
within a bounded factor of their solo latency — the weighted
deficit-round-robin scheduler (core/sched/wdrr.py) keeps the big
tenant from owning every pop.  (How far the small jobs starve with
fairness OFF is a perf figure, not a pass/fail floor: no test holds it.)"""

import threading
import time

import numpy as np

from parsec_tpu.datadist import TiledMatrix
from parsec_tpu.ops.cholesky import cholesky_ptg
from parsec_tpu.serve import RuntimeService
from parsec_tpu.core.sched.wdrr import QUANTUM, SchedWDRR
from parsec_tpu.core.taskpool import Taskpool
from parsec_tpu.core.task import Task, TaskClass

BIG_N, BIG_NB = 1024, 32  # NT=32 -> 5984 tasks


def _big_dpotrf():
    rng = np.random.default_rng(5)
    M = rng.standard_normal((BIG_N, BIG_N))
    spd = M @ M.T + BIG_N * np.eye(BIG_N)
    A = TiledMatrix(BIG_N, BIG_N, BIG_NB, BIG_NB, name="big")
    A.from_array(spd)
    return cholesky_ptg(use_tpu=False).taskpool(NT=A.mt, A=A), A


def _small_job(i):
    """A 12-task chain over a tiny tile — the latency-sensitive online
    workload."""
    from parsec_tpu.data import LocalCollection
    from parsec_tpu.dsl.ptg import PTG, INOUT

    dc = LocalCollection("S", shape=(1,), init=lambda k: np.zeros(4))
    ptg = PTG(f"small{i}")
    step = ptg.task_class("step", k="0 .. N-1")
    step.affinity("S(0)")
    step.flow("X", INOUT, "<- (k == 0) ? S(0) : X step(k-1)",
              "-> (k < N-1) ? X step(k+1) : S(0)")
    step.body(cpu=lambda X, k: X.__iadd__(1.0))
    return ptg.taskpool(N=12, S=dc), dc


def _p95(xs):
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(round(0.95 * (len(xs) - 1))))]


def test_wdrr_unit_fair_share_and_priority_within_tenant():
    """Scheduler-level pin: with equal weights the pops alternate
    tenants per quantum; weight 2 gets twice the slots; within one
    tenant the composed priority orders the pops."""

    class _Ctx:
        nb_workers = 1

    sched = SchedWDRR()
    sched.install(_Ctx())

    def mk_pool(tenant, weight):
        tp = Taskpool(f"p_{tenant}", nb_tasks=1)
        tp.tenant, tp.tenant_weight = tenant, weight
        return tp

    tc = TaskClass("t")
    a, b = mk_pool("a", 1), mk_pool("b", 1)
    tasks_a = [Task(a, tc, (i,), priority=i) for i in range(8)]
    tasks_b = [Task(b, tc, (i,), priority=i) for i in range(8)]
    sched.schedule(None, tasks_a)
    sched.schedule(None, tasks_b)
    order = [sched._key_of(sched.select(None)) for _ in range(16)]
    assert sched.select(None) is None
    # both tenants appear in the FIRST quantum-bounded window: nobody
    # waits for the other's whole backlog
    assert set(order[:2 * QUANTUM]) == {"a", "b"}
    assert order.count("a") == order.count("b") == 8

    # weight 2 drains twice as fast
    sched.install(_Ctx())
    heavy, light = mk_pool("h", 2), mk_pool("l", 1)
    sched.schedule(None, [Task(heavy, tc, (i,), priority=0)
                          for i in range(12)])
    sched.schedule(None, [Task(light, tc, (i,), priority=0)
                          for i in range(12)])
    first12 = [sched._key_of(sched.select(None)) for _ in range(12)]
    assert first12.count("h") == 2 * first12.count("l")

    # within one tenant: highest composed priority pops first
    sched.install(_Ctx())
    solo_pool = mk_pool("s", 1)
    ts = [Task(solo_pool, tc, (i,), priority=i) for i in range(5)]
    sched.schedule(None, ts)
    got = [sched.select(None).priority for _ in range(5)]
    assert got == sorted(got, reverse=True)


def test_small_jobs_not_starved_by_big_job():
    """The pinned floor: p95 small-job latency while a 5984-task dpotrf
    runs <= 5x the solo small-job latency (with a floor absorbing
    scheduler-independent machine noise — full starvation means waiting
    out the big job, seconds, far above it)."""
    # solo latencies: the service idle except for the small job
    with RuntimeService(nb_cores=4) as sv:
        solo = []
        for i in range(3):
            h = sv.submit("online", _small_job(f"solo{i}")[0])
            assert h.wait(timeout=60)
            solo.append(h.latency_s)
    solo_lat = sorted(solo)[len(solo) // 2]

    with RuntimeService(nb_cores=4) as sv:
        sv.tenant("batch", weight=1)
        sv.tenant("online", weight=1)
        big_tp, _ = _big_dpotrf()
        big = sv.submit("batch", big_tp)
        # wait until the big job is genuinely flowing
        deadline = time.monotonic() + 60
        while big_tp.nb_retired < 50:
            assert time.monotonic() < deadline, "big job never started"
            time.sleep(0.005)
        lats = []
        for i in range(8):
            h = sv.submit("online", _small_job(i)[0])
            assert h.wait(timeout=120), h.status()
            lats.append(h.latency_s)
        assert big.wait(timeout=600), big.status()
        assert big_tp.nb_retired == 5984
    p95 = _p95(lats)
    bound = max(5 * solo_lat, 0.25)
    assert p95 <= bound, (
        f"small-job p95 {p95:.4f}s vs solo {solo_lat:.4f}s "
        f"(bound {bound:.4f}s): the big tenant is starving the small "
        f"one — wdrr fairness broke")
