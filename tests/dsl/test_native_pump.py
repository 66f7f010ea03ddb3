"""Pump-mode (zero-interpreter lifecycle) coverage beyond the PINS pin
in test_native_device.py (ISSUE 18):

* a mixed DAG (one class with a CPU body only) takes the legacy
  two-entry ASYNC protocol by itself: the input the pump cannot run;
* seeded pop-order perturbation reaches the native scheduler
  (``sched_rnd_seed`` drives the SchedQ's xorshift mode) with
  bit-identical tile digests vs the Python ``rnd`` scheduler — the
  schedule-explorer leg, on dpotrf and the attention carry chain;
* the opt-in native ready-queue mirror (``sched_native_queue=1``)
  pops in exactly the Python spq/wdrr order;
* hb-check orders a pump run end-to-end from the batched event drain;
* the PR 9 serve fairness pin ported to ``run_native``: wdrr
  fair-share under native pop keeps a small tenant's completion
  latency bounded beside a 5984-task dpotrf.
"""

import time

import numpy as np
import pytest

from parsec_tpu import native
from parsec_tpu.profiling import pins
from parsec_tpu.utils import mca_param

pytestmark = pytest.mark.skipif(
    not native.available(),
    reason=f"native core unavailable: {native.build_error()}")


def _spd(n, seed=0):
    rng = np.random.default_rng(seed)
    M = rng.standard_normal((n, n))
    return M @ M.T + n * np.eye(n)


def _dpotrf_device_tp(n, nb, seed=0):
    from parsec_tpu.datadist import TiledMatrix
    from parsec_tpu.ops.cholesky import cholesky_ptg

    S = _spd(n, seed=seed)
    A = TiledMatrix(n, n, nb, nb, name="A", dtype=np.float64).from_array(S)
    tp = cholesky_ptg(use_tpu=True, use_cpu=False).taskpool(NT=A.mt, A=A)
    return S, A, tp


def _set(framework, name, value):
    mca_param.params.set(framework, name, value)


def _unset(framework, name):
    mca_param.params.unset(framework, name)


# ---------------------------------------------------------------------------
# a mixed DAG takes the legacy ASYNC protocol by itself
# ---------------------------------------------------------------------------

def _scale(T, k):
    return T * 2


def _shift(T, k):
    T += 1


def test_mixed_dag_takes_the_legacy_protocol_by_itself():
    """A class with a CPU body only keeps the DAG out of the pump: what
    the plan shows decides, no switch.  The PR 3 protocol runs it: two
    interpreter entries per DEVICE task (trampoline + completion), the
    CPU bodies inline in the native workers, no batch ever popped, and
    the numerics exact."""
    from parsec_tpu.core.lifecycle import AccessMode
    from parsec_tpu.datadist import TiledMatrix
    from parsec_tpu.dsl.native_exec import NativeExecutor
    from parsec_tpu.dsl.ptg import PTG

    nt, nb = 10, 8
    A = TiledMatrix(nt * nb, nb, nb, nb, name="A", dtype=np.float64) \
        .from_array(np.arange(nt * nb * nb, dtype=np.float64)
                    .reshape(nt * nb, nb))
    before = A.to_array()
    ptg = PTG("mixed")
    scale = ptg.task_class("scale", k="0 .. NT-1")
    scale.affinity("A(k, 0)")
    scale.flow("T", AccessMode.INOUT, "<- A(k, 0)", "-> T shift(k)")
    scale.body(tpu=_scale)
    shift = ptg.task_class("shift", k="0 .. NT-1")
    shift.affinity("A(k, 0)")
    shift.flow("T", AccessMode.INOUT, "<- T scale(k)", "-> A(k, 0)")
    shift.body(cpu=_shift)
    tp = ptg.taskpool(NT=A.mt, A=A)

    ex = NativeExecutor(tp, native_device=True)
    assert ex.graph.has_cpu_bodies and not ex._pump
    ran = ex.run(nthreads=2)
    stats = dict(ex.stats)
    ex.close()
    assert ran == 2 * nt
    assert stats["trampoline_entries"] == nt
    assert stats["completion_callbacks"] == nt
    assert stats["pop_batches"] == 0 and stats["pumped_tasks"] == 0
    np.testing.assert_array_equal(A.to_array(), 2 * before + 1)


# ---------------------------------------------------------------------------
# schedule-explorer leg: seeded native pop order, digests vs Python sched
# ---------------------------------------------------------------------------

def _pump_digest(builder, seed):
    """Run ``builder()``'s taskpool through the pump with the native
    SchedQ in seeded-perturbation mode; digest the user collection."""
    from parsec_tpu.analysis.schedules import tile_digest
    from parsec_tpu.dsl.native_exec import run_native

    user, tp = builder()
    _set("sched", "rnd_seed", seed)
    try:
        run_native(tp, native_device=True)
    finally:
        _unset("sched", "rnd_seed")
    return tile_digest(user)


def _python_digest(builder, seed):
    """Same taskpool through the dynamic runtime's seeded ``rnd``
    scheduler — the Python-side schedule the digests must match."""
    from parsec_tpu import Context
    from parsec_tpu.analysis.schedules import tile_digest

    user, tp = builder()
    _set("sched", "rnd_seed", seed)
    ctx = Context(nb_cores=2, scheduler="rnd")
    try:
        ctx.add_taskpool(tp)
        assert tp.wait(timeout=120)
    finally:
        ctx.fini()
        _unset("sched", "rnd_seed")
    return tile_digest(user)


EXPLORER_SEEDS = (0, 1, 7, 42)  # the 4 tier-1 seeds


def test_explorer_seeds_dpotrf_native_vs_python_bit_identical():
    """4 seeds x (native pump, Python rnd scheduler): every run of the
    dpotrf DAG lands bit-identical tiles — the native SchedQ's seeded
    pop-order perturbation respects the same dependence order the
    Python scheduler does.  Wave batching is disabled so both paths
    dispatch per-tile programs (wave composition is schedule-dependent
    and vmapped kernels need not be bitwise equal to singles)."""

    def builder():
        S, A, tp = _dpotrf_device_tp(96, 24, seed=11)
        return A, tp

    _set("device", "tpu_wave_batch", 0)
    try:
        digests = [_pump_digest(builder, s) for s in EXPLORER_SEEDS]
        ref = _python_digest(builder, EXPLORER_SEEDS[0])
        for d in digests:
            assert d == ref, "native seeded schedule diverged from Python"
    finally:
        _unset("device", "tpu_wave_batch")


def test_explorer_seeds_attention_native_vs_python_bit_identical():
    """Same 4-seed leg on the attention carry chain (the single-rank
    inner structure of ring attention — the pump is a one-rank engine):
    the online-softmax accumulation is order-sensitive along the chain,
    so a scheduler that reordered the carry would show up bitwise."""
    from parsec_tpu.ops.attention import build_flash_attention

    rng = np.random.default_rng(9)
    q = rng.standard_normal((1, 48, 2, 16)).astype(np.float32)
    k = rng.standard_normal((1, 48, 2, 16)).astype(np.float32)
    v = rng.standard_normal((1, 48, 2, 16)).astype(np.float32)

    made = []

    def builder():
        tp, assemble = build_flash_attention(
            q, k, v, causal=True, q_block=16, kv_block=16, use_cpu=False)
        made.append(assemble)
        return None, tp

    from parsec_tpu.dsl.native_exec import run_native

    _set("device", "tpu_wave_batch", 0)
    try:
        outs = []
        for s in EXPLORER_SEEDS:
            _, tp = builder()
            _set("sched", "rnd_seed", s)
            try:
                run_native(tp, native_device=True)
            finally:
                _unset("sched", "rnd_seed")
            outs.append(made[-1]())
        # Python-side reference schedule
        from parsec_tpu import Context

        _, tp = builder()
        _set("sched", "rnd_seed", EXPLORER_SEEDS[0])
        ctx = Context(nb_cores=2, scheduler="rnd")
        try:
            ctx.add_taskpool(tp)
            assert tp.wait(timeout=120)
        finally:
            ctx.fini()
            _unset("sched", "rnd_seed")
        ref = made[-1]()
        for out in outs:
            np.testing.assert_array_equal(out, ref)
    finally:
        _unset("device", "tpu_wave_batch")


def test_pump_seeded_orders_actually_differ():
    """The perturbation is real: different seeds produce different
    retire orders through the native queue (identity of results is
    meaningful only if the schedules explored are distinct)."""
    from parsec_tpu.dsl.native_exec import run_native

    orders = []
    for s in EXPLORER_SEEDS:
        S, A, tp = _dpotrf_device_tp(128, 16, seed=2)
        order = []
        cb = lambda es, p: order.append(p["task"])
        pins.subscribe(pins.NATIVE_TASK_DONE, cb)
        _set("sched", "rnd_seed", s)
        try:
            run_native(tp, native_device=True)
        finally:
            _unset("sched", "rnd_seed")
            pins.unsubscribe(pins.NATIVE_TASK_DONE, cb)
        assert len(order) == 120
        orders.append(tuple(order))
    assert len(set(orders)) >= 2, "seeds did not perturb the native queue"


# ---------------------------------------------------------------------------
# native ready-queue mirror: identical pop order to the Python disciplines
# ---------------------------------------------------------------------------

class _QT:
    """Bare scheduler-level task stub."""

    def __init__(self, k, priority=0, pool=None):
        self.k = k
        self.priority = priority
        self.taskpool = pool


class _QPool:
    def __init__(self, tenant, weight):
        self.tenant = tenant
        self.tenant_weight = weight


class _QCtx:
    nb_workers = 1


def _drain(s):
    out = []
    while True:
        t = s.select(None)
        if t is None:
            return [x.k for x in out]
        out.append(t)


def _spq_order(mirror, tasks_fn):
    from parsec_tpu.core.sched.spq import SchedSPQ

    if mirror:
        _set("sched", "native_queue", 1)
    try:
        s = SchedSPQ()
        s.install(_QCtx())
        assert (s._nq is not None) == mirror
        for batch, dist in tasks_fn():
            s.schedule(None, batch, distance=dist)
        out = _drain(s)
        s.remove(None)
        return out
    finally:
        if mirror:
            _unset("sched", "native_queue")


def test_spq_native_mirror_pop_parity():
    def mk():
        rng = np.random.default_rng(0)
        prios = rng.integers(0, 5, size=24).tolist()
        ts = [_QT(i, priority=int(p)) for i, p in enumerate(prios)]
        return [(ts[:12], 0), (ts[12:], 2)]

    assert _spq_order(False, mk) == _spq_order(True, mk)


def test_wdrr_native_mirror_pop_parity():
    from parsec_tpu.core.sched.wdrr import SchedWDRR

    def run(mirror):
        if mirror:
            _set("sched", "native_queue", 1)
        try:
            s = SchedWDRR()
            s.install(_QCtx())
            assert (s._nq is not None) == mirror
            a, b = _QPool("a", 1), _QPool("b", 2)
            rng = np.random.default_rng(1)
            ts = [_QT(i, priority=int(rng.integers(0, 4)),
                      pool=(a if i % 2 else b)) for i in range(20)]
            s.schedule(None, ts[:10])
            first = [s.select(None).k for _ in range(5)]
            s.schedule(None, ts[10:])  # interleaved push mid-drain
            rest = _drain(s)
            s.remove(None)
            return first + rest
        finally:
            if mirror:
                _unset("sched", "native_queue")

    assert run(False) == run(True)


# ---------------------------------------------------------------------------
# hb-check over the batched event drain
# ---------------------------------------------------------------------------

def test_pump_hbcheck_orders_native_run():
    """The drain republishes the native lifecycle into the PINS sites:
    hb-check sees dep decrements (tuple-tagged native tracker), publish
    and retire events, chains them, and reports a clean run."""
    from parsec_tpu.analysis.hb import HBRecorder
    from parsec_tpu.dsl.native_exec import run_native

    S, A, tp = _dpotrf_device_tp(96, 24, seed=4)
    with HBRecorder(stacks=False) as rec:
        ran = run_native(tp, native_device=True)
    assert ran == 20
    kinds = {}
    trackers = set()
    for ev in rec.events:
        kinds[ev.kind] = kinds.get(ev.kind, 0) + 1
        if ev.kind == "dep_dec":
            trackers.add(ev.obj[0])
    assert kinds.get("task_done") == 20
    assert kinds.get("task_publish", 0) >= 20  # roots synthesized too
    assert kinds.get("dep_dec", 0) > 0
    assert any(isinstance(t, tuple) and t[0] == "native" for t in trackers)
    assert rec.analyze() == []


# ---------------------------------------------------------------------------
# serve fairness pin under native pop (PR 9 floor ported to run_native)
# ---------------------------------------------------------------------------

def _device_chain_tp(name, n=12):
    """A 12-task sequential device chain — the latency-sensitive small
    tenant (device-bodied: the pump serves all-device classes only)."""
    from parsec_tpu.data import LocalCollection
    from parsec_tpu.dsl.ptg import PTG, INOUT

    dc = LocalCollection(f"S{name}", shape=(1,),
                         init=lambda k: np.zeros(4, dtype=np.float32))
    ptg = PTG(f"small_{name}")
    step = ptg.task_class("step", k="0 .. N-1")
    step.affinity("S(0)")
    step.flow("X", INOUT, "<- (k == 0) ? S(0) : X step(k-1)",
              "-> (k < N-1) ? X step(k+1) : S(0)")
    step.body(tpu=lambda X, k: X + 1.0)
    return ptg.taskpool(N=n, S=dc), dc


def test_serve_fairness_small_tenant_not_starved_under_native_pop():
    """While a 5984-task device dpotrf pumps, co-scheduled small chains
    must finish within a bounded factor of their solo latency: the
    wdrr deficits live in the native SchedQ now, and the pop batches
    must still interleave tenants instead of draining the big backlog
    first.  The drain batch is capped so wdrr selection is binding, and
    wave batching is off so the measurement times scheduling, not
    per-wave-width executable compiles.  The retire POSITIONS are the
    compile-noise-immune fairness currency; the wall-clock bound rides
    on top with a floor absorbing machine noise."""
    from parsec_tpu.dsl.native_exec import NativeServeExecutor, run_native
    from parsec_tpu.ops.cholesky import cholesky_ptg
    from parsec_tpu.datadist import TiledMatrix

    def dpotrf_tp(n):
        S = _spd(n, seed=5)
        A = TiledMatrix(n, n, 32, 32, name=f"big{n}",
                        dtype=np.float64).from_array(S)
        return cholesky_ptg(use_tpu=True,
                            use_cpu=False).taskpool(NT=A.mt, A=A)

    _set("device", "tpu_wave_batch", 0)
    try:
        # warm the executable cache: the 128/32 dpotrf compiles the same
        # four tile kernels the 1024/32 run uses, and one chain warms
        # the step kernel — so the fairness window below measures
        # scheduling, not first-touch compiles
        run_native(dpotrf_tp(128), native_device=True)
        run_native(_device_chain_tp("warm")[0], native_device=True)

        # solo latency of one small chain through the pump (median of 3)
        solos = []
        for i in range(3):
            tp, _ = _device_chain_tp(f"solo{i}")
            t0 = time.perf_counter()
            run_native(tp, native_device=True)
            solos.append(time.perf_counter() - t0)
        solo = sorted(solos)[1]

        big_tp = dpotrf_tp(1024)
        smalls = [_device_chain_tp(f"c{i}")[0] for i in range(4)]
        _set("runtime", "native_drain", 64)
        try:
            sx = NativeServeExecutor([big_tp] + smalls)
            try:
                counts = sx.run()
                log = list(sx.retire_log)
            finally:
                sx.close()
        finally:
            _unset("runtime", "native_drain")
    finally:
        _unset("device", "tpu_wave_batch")
    assert counts == [5984] + [12] * 4
    # retire-position fairness: every small chain completes well inside
    # the big backlog (full starvation = its last retire at the tail)
    total = len(log)
    done_at, done_pos = {}, {}
    for tenant, pos, ts in log:
        done_at[tenant] = ts
        done_pos[tenant] = pos
    for i in range(1, 5):
        assert done_pos[i] < 0.4 * total, (
            f"tenant {i} finished at retire position {done_pos[i]}/{total}"
            " — native wdrr pop is draining the big backlog first")
    # wall-clock bound (PR 9 floor shape, ported to the pump)
    worst = max(done_at[i] for i in range(1, 5))
    bound = max(5 * solo, 0.75)
    assert worst <= bound, (
        f"small-tenant completion {worst:.4f}s vs solo {solo:.4f}s "
        f"(bound {bound:.4f}s): native wdrr pop is starving the small "
        f"tenants behind the 5984-task backlog")
    # and they genuinely ran BESIDE the big job, not after it
    assert worst < done_at[0]
    # per-tenant serve metrics populated by the batched retirement
    assert big_tp.nb_retired == 5984
    assert all(tp.nb_retired == 12 for tp in smalls)
