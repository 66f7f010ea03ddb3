"""Stall watchdog: hang diagnosis on a synthetically wedged 2-rank run
(frames held by the PR 5 ExplorerFabric deferral hook), strict-mode
fail-fast, and no false positives on healthy runs."""

import random
import threading
import time

import numpy as np
import pytest

from parsec_tpu import Context
from parsec_tpu.analysis.findings import CODES
from parsec_tpu.analysis.schedules import ExplorerFabric, _PerturbedInbox
from parsec_tpu.profiling.health import Watchdog


N, NB = 32, 8
_rng = np.random.default_rng(7)
_M = _rng.standard_normal((N, N))
SPD = _M @ _M.T + N * np.eye(N)


def _build_dpotrf(rank, ctx):
    from parsec_tpu.datadist import TwoDimBlockCyclic
    from parsec_tpu.ops.cholesky import cholesky_ptg

    A = TwoDimBlockCyclic(N, N, NB, NB, p=2, q=1, myrank=rank, name="A")
    A.from_array(SPD)
    return cholesky_ptg(use_tpu=False).taskpool(NT=A.mt, A=A), A


def test_obs_codes_registered():
    for code in ("OBS001", "OBS002", "OBS003", "OBS004", "OBS005",
                 "OBS006"):
        assert code in CODES


def test_watchdog_diagnoses_wedged_run_strict():
    """Wedge rank 1's inbound frame delivery (the ExplorerFabric
    deferral hook with an effectively-infinite budget) on a 2-rank
    dpotrf: cross-rank activations never land, both pools stall.  The
    strict watchdog must fail the pools within the window, and the
    diagnosis must name the blocked dependency counter (OBS002 with the
    dpotrf class) and the silent rank (OBS004: rank 1 never hears rank
    0's heartbeats through the wedged inbox).  The order of the two
    diagnoses is the test's, not the host's: only rank 0 ever holds a
    half-counted dependency (nothing reaches rank 1 to count), and a
    strict rank 1 that fired first would abort rank 0 before it had
    looked, so rank 1's window opens once rank 0 has reported."""
    fabric = ExplorerFabric(2, seed=3, delay_prob=0.0, max_delay=0)
    # wedge: every frame toward rank 1 defers for ~forever (bounded in
    # name only — the budget decrements one per empty pop)
    fabric.inboxes[1] = _PerturbedInbox(
        random.Random(0), delay_prob=1.0, max_delay=1 << 30)
    ces = fabric.endpoints()
    ctxs = [Context(nb_cores=2, rank=r, nranks=2, comm=ces[r])
            for r in range(2)]
    wds = [Watchdog(ctx, window=1.5, poll=0.25, strict=True)
           for ctx in ctxs]
    for ctx, wd in zip(ctxs, wds):
        ctx.watchdog = wd
    try:
        pools = []
        oks = [None, None]

        def worker(r):
            tp, _ = _build_dpotrf(r, ctxs[r])
            pools.append(tp)
            ctxs[r].add_taskpool(tp)
            oks[r] = tp.wait(timeout=60)

        threads = [threading.Thread(target=worker, args=(r,))
                   for r in range(2)]
        for t in threads:
            t.start()
        wds[0].start()
        deadline = time.monotonic() + 45
        while wds[0].last_report is None and time.monotonic() < deadline:
            time.sleep(0.05)
        wds[1].start()
        for t in threads:
            t.join(timeout=90)
        assert all(not t.is_alive() for t in threads), \
            "strict watchdog failed to unwedge wait() — the hang it " \
            "exists to prevent"
        # strict mode FAILED the pools instead of hanging to timeout
        assert oks == [False, False]
        for tp in pools:
            assert "watchdog" in (getattr(tp, "fail_reason", "") or "")

        # at least one rank diagnosed; its report names the blocked dep
        # counter class and the stall headline
        reports = [wd.last_report for wd in wds
                   if wd.last_report is not None]
        assert reports, "no watchdog report produced"
        all_findings = [f for rep in reports for f in rep.findings]
        codes = {f.code for f in all_findings}
        assert "OBS001" in codes
        dep_findings = [f for f in all_findings if f.code == "OBS002"]
        assert dep_findings, (
            "diagnosis must name the nonzero dep counters; findings: "
            + "; ".join(str(f) for f in all_findings))
        assert any(f.task in ("potrf", "trsm", "syrk", "gemm")
                   for f in dep_findings)
        # rank 1 heard nothing through its wedged inbox: rank 0 is
        # silent from ITS point of view
        r1_rep = wds[1].last_report
        assert r1_rep is not None
        assert any(f.code == "OBS004" for f in r1_rep.findings), \
            "wedged rank must report the silent peer"
    finally:
        for wd in wds:
            wd.stop()
        for ctx in ctxs:
            ctx.fini()


def test_watchdog_no_false_positive_on_healthy_run():
    from parsec_tpu.data import LocalCollection
    from parsec_tpu.dsl.ptg import PTG, INOUT

    ctx = Context(nb_cores=2)
    wd = Watchdog(ctx, window=10.0, poll=0.1, strict=True).start()
    ctx.watchdog = wd
    try:
        dc = LocalCollection("D", shape=(1,), init=lambda k: np.zeros(1))
        ptg = PTG("chain")
        step = ptg.task_class("step", k="0 .. N-1")
        step.affinity("D(0)")
        step.flow("X", INOUT, "<- (k == 0) ? D(0) : X step(k-1)",
                  "-> (k < N-1) ? X step(k+1) : D(0)")
        step.body(cpu=lambda X, k: X.__iadd__(1.0))
        tp = ptg.taskpool(N=12, D=dc)
        ctx.add_taskpool(tp)
        assert tp.wait(timeout=30)
        assert not wd.stalled
        assert wd.last_report is None
    finally:
        wd.stop()
        ctx.fini()


def test_diagnose_on_demand_names_pending_counters():
    """diagnose() is callable outside the monitor thread: a half-wedged
    pool (first task parked in a body) reports its pending dep counters
    without waiting for the window."""
    from parsec_tpu.data import LocalCollection
    from parsec_tpu.dsl.ptg import PTG, INOUT

    gate = threading.Event()
    ctx = Context(nb_cores=2)
    wd = Watchdog(ctx, window=60.0, poll=30.0).start()
    try:
        dc = LocalCollection("D", shape=(1,), init=lambda k: np.zeros(1))
        ptg = PTG("gated")
        step = ptg.task_class("step", k="0 .. N-1")
        step.affinity("D(0)")
        step.flow("X", INOUT, "<- (k == 0) ? D(0) : X step(k-1)",
                  "-> (k < N-1) ? X step(k+1) : D(0)")

        def body(X, k):
            if k == 0:
                assert gate.wait(timeout=60)

        step.body(cpu=body)
        tp = ptg.taskpool(N=4, D=dc)
        ctx.add_taskpool(tp)
        rep = wd.diagnose()
        assert any(f.code == "OBS001" for f in rep.findings)
        assert "gated" in rep.render()
        gate.set()
        assert tp.wait(timeout=30)
    finally:
        gate.set()
        wd.stop()
        ctx.fini()
