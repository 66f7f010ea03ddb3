"""The attach plan (``dsl/attach_plan.py``): a DAG is captured once per
shape, and a new ``NativeExecutor(native_device=True)`` over a taskpool
of a shape already seen only binds the stored plan to its tiles.

The bound graph is the captured graph: tasks, priorities, de-duplicated
edges, roots, ``_tpu_home``, ``_wbs``, scratch users and ``body_args``
layout are compared with a fresh capture; the key misses on everything
the graph is a function of; what the fingerprint cannot vouch for falls
back; a stored plan keeps nothing of the solve that made it alive.
Counts and bits on the CPU backend, never a time.
"""

import gc
import threading
import weakref

import numpy as np
import pytest

from parsec_tpu import native
from parsec_tpu.comm import InprocFabric
from parsec_tpu.core.lifecycle import AccessMode
from parsec_tpu.datadist import TiledMatrix, TwoDimBlockCyclic
from parsec_tpu.dsl import attach_plan
from parsec_tpu.dsl.graph import capture
from parsec_tpu.dsl.native_dist import NativeDistExecutor
from parsec_tpu.dsl.native_exec import NativeExecutor, NativeServeExecutor
from parsec_tpu.dsl.ptg import PTG
from parsec_tpu.ops import cholesky_ptg
from parsec_tpu.ops.qr import qr_ptg

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="needs the native core")
NB = 8
INOUT, IN = AccessMode.INOUT, AccessMode.IN


@pytest.fixture(autouse=True)
def _empty_store():
    attach_plan.clear()
    yield
    attach_plan.clear()


@pytest.fixture(scope="module")
def dev():
    d = NativeExecutor._make_device()
    yield d
    d.detach()


def _spd(nt, seed, nb=NB):
    n = nt * nb
    m = np.random.default_rng(seed).standard_normal((n, n))
    return (m @ m.T + n * np.eye(n)).astype(np.float32)


def _dpotrf(nt=4, seed=3, nb=NB, matrix=TiledMatrix, **ptg_kw):
    n = nt * nb
    A = matrix(n, n, nb, nb, name="A", dtype=np.float32) \
        .from_array(_spd(nt, seed, nb))
    return cholesky_ptg(use_tpu=True, use_cpu=False, **ptg_kw) \
        .taskpool(NT=A.mt, A=A), A


def _geqrf(nt=4, seed=3, **ptg_kw):
    n = nt * NB
    a = np.random.default_rng(seed).uniform(-0.5, 0.5, (n, n)) \
        .astype(np.float32)
    A = TiledMatrix(n, n, NB, NB, name="A", dtype=np.float32).from_array(a)
    return qr_ptg(use_tpu=True, use_cpu=False, **ptg_kw).taskpool(
        NT=A.mt, A=A, TILE_SHAPE=(NB, NB), TILE_DTYPE=np.float32,
        QSHAPE2=(np.float32, (2 * NB, 2 * NB))), A


POOLS = {"dpotrf": _dpotrf, "geqrf": _geqrf}


def _how(ex):
    return {k[len("attach_plan_"):]: v for k, v in ex.stats.items()
            if k.startswith("attach_plan_")}


HIT = {"hits": 1, "misses": 0, "uncacheable": 0}
MISS = {"hits": 0, "misses": 1, "uncacheable": 0}
UNCACHEABLE = {"hits": 0, "misses": 0, "uncacheable": 1}


def _plan_facts(plan):
    """A plan's content, comparable with ``==`` (the FusedPlan of a
    region by its digest)."""
    return {s: ([(r[0].digest,) + r[1:] for r in plan.fused]
                if s == "fused" else
                list(getattr(plan, s)) if s.startswith(("edge_", "native_"))
                else getattr(plan, s))
            for s in plan.__slots__ if s != "key"}


def _bound_facts(ex, A):
    """What a bind left behind, named by tile and not by object: per
    native id the task, its priority, its ``body_args`` layout,
    ``_tpu_home`` and ``_wbs``; the roots; the scratch users."""
    tile = {id(A.data_of(*k)): ("A",) + k for k in A.tiles()}

    def name(d):
        if d is None:
            return None
        return tile.get(id(d)) or ("new", d.key, d.shape, str(d.dtype))

    tasks, scratch = {}, {}
    for nid, t in ex._pump_index.items():
        args = []
        for kind, payload, mode in t.body_args:
            args.append((kind, name(payload) if kind == "data" else payload,
                         int(mode)))
            if kind == "data" and payload is not None \
                    and payload.scratch is not None:
                scratch[payload.key] = payload.scratch
        tasks[nid] = (t.task_class.name, t.locals, t.priority, args,
                      t._tpu_home, [(name(s), name(h)) for s, h in t._wbs])
    return {"tasks": tasks, "roots": ex._roots, "scratch": scratch,
            "pump": ex._pump, "n_native": ex._n_native}


def _factor(ex, A):
    ex.run()
    ex.close()
    return A.to_array()


# -- (a) a plan bound from the store is the captured graph ---------------

@pytest.mark.parametrize("which", sorted(POOLS))
def test_bound_from_store_equals_fresh_capture(which, dev):
    tp1, A1 = POOLS[which]()
    ex1 = NativeExecutor(tp1, native_device=True, device=dev)
    assert _how(ex1) == MISS
    stored = attach_plan.stored()[0]
    tp2, A2 = POOLS[which]()
    ex2 = NativeExecutor(tp2, native_device=True, device=dev)
    assert _how(ex2) == HIT and ex2.graph is stored
    # a capture of the second pool resolves to the stored plan ...
    fresh = attach_plan.build_plan(tp2, capture(tp2, ranks=[0]))
    assert _plan_facts(fresh) == _plan_facts(stored)
    # ... and both binds made the same native graph over their own tiles
    f1, f2 = _bound_facts(ex1, A1), _bound_facts(ex2, A2)
    assert f1 == f2 and f1["pump"]
    assert len(f1["tasks"]) == len(stored.tasks) == f1["n_native"]
    if which == "geqrf":
        assert f1["scratch"] and min(f1["scratch"].values()) >= 1
    else:
        assert not f1["scratch"]
    assert len(stored.edge_pred) == len(set(
        zip(stored.edge_pred, stored.edge_succ)))
    # the second solve's factor is bitwise the first's on the same seed
    assert np.array_equal(_factor(ex1, A1), _factor(ex2, A2))


@pytest.mark.parametrize("which", sorted(POOLS))
def test_hit_factors_other_data_correctly(which, dev):
    """The plan binds to the NEW pool's tiles: another seed's matrix
    gets its own factor, equal to that of an executor that captured."""
    first = NativeExecutor(POOLS[which](seed=3)[0], native_device=True,
                           device=dev)
    first.close()
    tp, A = POOLS[which](seed=11)
    ex = NativeExecutor(tp, native_device=True, device=dev)
    assert _how(ex) == HIT
    got = _factor(ex, A)
    attach_plan.clear()
    tp, A = POOLS[which](seed=11)
    ex = NativeExecutor(tp, native_device=True, device=dev)
    assert _how(ex) == MISS
    assert np.array_equal(got, _factor(ex, A))
    if which == "dpotrf":
        ref = np.linalg.cholesky(_spd(4, 11).astype(np.float64))
        assert np.max(np.abs(np.tril(got) - ref)) < 1e-3 * np.max(ref)


# -- (b) the key ----------------------------------------------------------

def _edited_dpotrf(nt=4):
    """The dpotrf PTG with one dependency string edited (a trsm no
    longer writes its tile home: another graph)."""
    n = nt * NB
    A = TiledMatrix(n, n, NB, NB, name="A", dtype=np.float32) \
        .from_array(_spd(nt, 3))
    ptg = cholesky_ptg(use_tpu=True, use_cpu=False)
    flow = next(f for f in ptg.classes["trsm"].flows if f.name == "C")
    flow.deps_out = [d for d in flow.deps_out if d.src != "-> A(m, k)"]
    assert len(flow.deps_out) == 3
    return ptg.taskpool(NT=A.mt, A=A), A


def _scalar_pool(alpha):
    A = TiledMatrix(4 * NB, NB, NB, NB, name="A", dtype=np.float32) \
        .from_array(np.ones((4 * NB, NB), np.float32))
    ptg = PTG("scale")
    step = ptg.task_class("step", k="0 .. NT-1")
    step.affinity("A(k, 0)")
    step.use_globals("ALPHA")
    step.flow("T", INOUT, "<- A(k, 0)", "-> A(k, 0)")
    step.body(tpu=_scale_body)
    return ptg.taskpool(NT=A.mt, A=A, ALPHA=alpha), A


def _scale_body(T, k, ALPHA):
    return T * ALPHA


KEY_CASES = {
    "same": (lambda: _dpotrf()[0], HIT),
    "another_seed": (lambda: _dpotrf(seed=99)[0], HIT),
    "NT": (lambda: _dpotrf(nt=5)[0], MISS),
    "tile_shape": (lambda: _dpotrf(nb=16)[0], MISS),
    "grid": (lambda: _dpotrf(matrix=lambda *a, **k: TwoDimBlockCyclic(
        *a, p=1, q=1, **k))[0], MISS),
    "bf16_updates": (lambda: _dpotrf(use_pallas=True,
                                     bf16_updates=True)[0], MISS),
    "edited_dependency": (lambda: _edited_dpotrf()[0], MISS),
}


@pytest.mark.parametrize("case", sorted(KEY_CASES))
def test_key_misses_on_what_the_graph_is_a_function_of(case, dev):
    NativeExecutor(_dpotrf()[0], native_device=True, device=dev).close()
    make, expect = KEY_CASES[case]
    ex = NativeExecutor(make(), native_device=True, device=dev)
    assert _how(ex) == expect
    assert len(attach_plan.stored()) == (1 if expect == HIT else 2)
    ex.close()


def test_bf16_updates_pool_without_pallas_is_its_own_shape(dev):
    """``use_pallas`` alone changes the update bodies: a MISS too, and
    two PTG objects from two calls of ``cholesky_ptg()`` HIT."""
    for kw, expect in (({}, MISS), ({}, HIT), ({"use_pallas": True}, MISS),
                       ({"use_pallas": True}, HIT)):
        ex = NativeExecutor(_dpotrf(**kw)[0], native_device=True,
                            device=dev)
        assert _how(ex) == expect, kw
        ex.close()


def test_changed_scalar_constant_misses_and_is_applied(dev):
    for alpha, expect in ((2.0, MISS), (2.0, HIT), (3.0, MISS)):
        tp, A = _scalar_pool(alpha)
        ex = NativeExecutor(tp, native_device=True, device=dev)
        assert _how(ex) == expect
        assert np.all(_factor(ex, A) == alpha)


def test_fusion_configuration_is_part_of_the_key(dev):
    ex = NativeExecutor(_dpotrf()[0], native_device=True, device=dev)
    ex.close()
    fused = NativeExecutor(_dpotrf()[0], native_device=True, device=dev,
                           fusion="chains")
    assert _how(fused) == MISS
    tp, A = _dpotrf()
    again = NativeExecutor(tp, native_device=True, device=dev,
                           fusion="chains")
    assert _how(again) == HIT
    assert [r.members for r in again._regions] == \
        [r.members for r in fused._regions]
    assert _bound_facts(fused, _dpotrf()[1])["n_native"] == \
        again._n_native <= len(again.graph.nodes)
    fused.close()
    ref = np.linalg.cholesky(_spd(4, 3).astype(np.float64))
    got = np.tril(_factor(again, A))
    assert np.max(np.abs(got - ref)) < 1e-3 * np.max(ref)


def test_store_is_a_small_lru(dev):
    for nt in range(2, 2 + attach_plan.PLAN_CACHE_SIZE + 2):
        NativeExecutor(_dpotrf(nt=nt)[0], native_device=True,
                       device=dev).close()
    kept = attach_plan.stored()
    assert len(kept) == attach_plan.PLAN_CACHE_SIZE
    # the oldest shapes went: NT=2 is captured again, the newest is not
    assert _how(NativeExecutor(_dpotrf(nt=2)[0], native_device=True,
                               device=dev)) == MISS
    newest = 2 + attach_plan.PLAN_CACHE_SIZE + 1
    assert _how(NativeExecutor(_dpotrf(nt=newest)[0], native_device=True,
                               device=dev)) == HIT


# -- (c) what the fingerprint cannot vouch for falls back -----------------

class _Opaque:
    """A constant of no known kind."""


def _call_pool():
    """A dependency expression with an inline call."""
    tp, A = _dpotrf()
    tp.ptg.classes["potrf"].priority("pick(NT, k)")
    tp.constants["pick"] = lambda nt, k: (nt - k) * 1000
    return tp, A


UNCACHEABLE_CASES = {
    "opaque_constant": lambda: _with_constant(_Opaque()),
    "mutable_constant": lambda: _with_constant([1, 2, 3]),
    "callable_constant": lambda: _with_constant(len),
    "subclassed_collection": lambda: _dpotrf(
        matrix=type("MyMatrix", (TiledMatrix,), {})),
    "inline_call": _call_pool,
}


def _with_constant(value):
    tp, A = _dpotrf()
    tp.constants["EXTRA"] = value
    return tp, A


@pytest.mark.parametrize("case", sorted(UNCACHEABLE_CASES))
def test_uncacheable_pool_falls_back_and_factors(case, dev):
    ref = np.linalg.cholesky(_spd(4, 3).astype(np.float64))
    for _ in range(2):  # never stored: the second is no hit either
        tp, A = UNCACHEABLE_CASES[case]()
        ex = NativeExecutor(tp, native_device=True, device=dev)
        assert _how(ex) == UNCACHEABLE
        assert not attach_plan.stored()
        got = np.tril(_factor(ex, A))
        assert np.max(np.abs(got - ref)) < 1e-3 * np.max(ref)


def test_handed_in_graph_is_bound_and_not_stored(dev):
    tp, A = _dpotrf()
    ex = NativeExecutor(tp, graph=capture(tp, ranks=[0]),
                        native_device=True, device=dev)
    assert _how(ex) == UNCACHEABLE and not attach_plan.stored()
    ref = np.linalg.cholesky(_spd(4, 3).astype(np.float64))
    assert np.max(np.abs(np.tril(_factor(ex, A)) - ref)) \
        < 1e-3 * np.max(ref)


def test_rebind_points_at_the_plan(dev):
    ex = NativeExecutor(_dpotrf()[0], native_device=True, device=dev)
    with pytest.raises(NotImplementedError, match="attach plan"):
        ex.rebind(_dpotrf()[0])
    ex.close()


# -- (d) a stored plan keeps nothing of a solve alive ---------------------

@pytest.mark.parametrize("which", sorted(POOLS))
def test_stored_plan_keeps_no_collection_alive(which, dev):
    tp, A = POOLS[which]()
    ex = NativeExecutor(tp, native_device=True, device=dev)
    ex.run()
    ex.close()
    refs = [weakref.ref(A), weakref.ref(A.data_of(0, 0)), weakref.ref(tp),
            weakref.ref(ex)]
    del tp, A, ex
    gc.collect()
    assert len(attach_plan.stored()) == 1
    assert [r() for r in refs] == [None] * len(refs)


# -- (e) a run does not mutate the plan -----------------------------------

@pytest.mark.parametrize("which", sorted(POOLS))
def test_plan_is_not_mutated_by_a_run(which, dev):
    tp, A = POOLS[which]()
    ex = NativeExecutor(tp, native_device=True, device=dev)
    plan = attach_plan.stored()[0]
    before = _plan_facts(plan)
    first = _factor(ex, A)
    tp, A = POOLS[which]()
    ex = NativeExecutor(tp, native_device=True, device=dev)
    assert _how(ex) == HIT and ex.graph is plan
    assert np.array_equal(first, _factor(ex, A))
    assert _plan_facts(plan) == before
    tp, _ = POOLS[which]()
    assert _plan_facts(attach_plan.build_plan(
        tp, capture(tp, ranks=[0]))) == before


# -- (f) the other executors ----------------------------------------------

def test_serve_tenants_of_one_shape_share_a_plan(dev):
    """Two tenants in one shared native graph: the first captures, the
    second binds the first's plan at its own native ids."""
    (tp1, A1), (tp2, A2) = _dpotrf(seed=3), _dpotrf(seed=11)
    sx = NativeServeExecutor([tp1, tp2], device=dev)
    assert {k: sx.stats[f"attach_plan_{k}"] for k in HIT} == \
        {"hits": 1, "misses": 1, "uncacheable": 0}
    a, b = sx.children
    assert a.graph is b.graph and b._native_base == a._n_native
    assert sorted(sx._pump_index) == list(range(2 * a._n_native))
    ntasks = len(a.graph.nodes)
    assert sx.run() == [ntasks, ntasks]
    assert sx.stats["trampoline_entries"] == 0 \
        and sx.stats["completion_callbacks"] == 0
    sx.close()
    for A, seed in ((A1, 3), (A2, 11)):
        ref = np.linalg.cholesky(_spd(4, seed).astype(np.float64))
        assert np.max(np.abs(np.tril(A.to_array()) - ref)) \
            < 1e-3 * np.max(ref)


def test_mixed_dag_binds_cpu_bodies_from_the_plan(dev):
    """A CPU-only class keeps the DAG out of the pump; its bodies are
    built from the same plan rows, hit or miss."""
    def pool():
        A = TiledMatrix(4 * NB, NB, NB, NB, name="A", dtype=np.float32) \
            .from_array(np.ones((4 * NB, NB), np.float32))
        ptg = PTG("mixed")
        dbl = ptg.task_class("dbl", k="0 .. NT-1")
        dbl.affinity("A(k, 0)")
        dbl.flow("T", INOUT, "<- A(k, 0)", "-> T inc(k)")
        dbl.body(tpu=_double)
        inc = ptg.task_class("inc", k="0 .. NT-1")
        inc.affinity("A(k, 0)")
        inc.flow("T", INOUT, "<- T dbl(k)", "-> A(k, 0)")
        inc.body(cpu=_increment)
        return ptg.taskpool(NT=A.mt, A=A), A

    for expect in (MISS, HIT):
        tp, A = pool()
        ex = NativeExecutor(tp, native_device=True, device=dev)
        assert _how(ex) == expect and not ex._pump
        assert len(ex._bodies) == ex._n_native == 8
        assert np.all(_factor(ex, A) == 3.0)
        assert ex.stats["trampoline_entries"] == 4  # the device class's


def _double(T, k):
    return T * 2


def _increment(T, k):
    T += 1


def test_numpy_path_and_dist_executor_stay_off_the_plan():
    """``native_device=False`` captures and builds as it always did
    (``rebind()`` amortizes there): no plan is looked up or stored."""
    tp, A = _dpotrf()
    tp = cholesky_ptg(use_tpu=False, use_cpu=True).taskpool(NT=A.mt, A=A)
    ex = NativeExecutor(tp)
    assert _how(ex) == {"hits": 0, "misses": 0, "uncacheable": 0}
    ex.run(nthreads=2)
    ex.close()

    nranks, n, nb = 2, 64, 16
    spd = _spd(n // nb, 17, nb).astype(np.float64)
    ces = InprocFabric(nranks).endpoints()
    mats, how, errors = {}, {}, []

    def worker(r):
        try:
            M = TwoDimBlockCyclic(n, n, nb, nb, p=1, q=2, myrank=r,
                                  name="A").from_array(spd)
            mats[r] = M
            dx = NativeDistExecutor(cholesky_ptg(
                use_tpu=False, use_cpu=True).taskpool(NT=M.mt, A=M), ces[r])
            how[r] = _how(dx)
            dx.run(nthreads=2)
        except Exception as e:  # pragma: no cover
            errors.append((r, e))

    ts = [threading.Thread(target=worker, args=(r,)) for r in range(nranks)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=60)
    assert not errors and not any(t.is_alive() for t in ts)
    assert how == {r: {"hits": 0, "misses": 0, "uncacheable": 0}
                   for r in range(nranks)}
    assert not attach_plan.stored()
    out = np.zeros((n, n))
    for M in mats.values():
        for (i, j) in M.local_tiles():
            out[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb] = \
                M.data_of(i, j).newest_copy().payload
    ref = np.linalg.cholesky(spd)
    assert np.max(np.abs(np.tril(out) - ref)) < 1e-10 * np.max(ref)


# -- the spans ------------------------------------------------------------

def test_attach_spans_and_the_plan_note(dev):
    from parsec_tpu.profiling import pins

    seen = []
    subs = [(f"{n}_{e}", (lambda es, p, n=n, e=e: seen.append((n, e, p))))
            for n in ("attach:build", "attach:partition", "attach:plan",
                      "attach:bind") for e in ("begin", "end")]
    for site, cb in subs:
        pins.subscribe(site, cb)
    try:
        for _ in range(2):
            NativeExecutor(_dpotrf()[0], native_device=True,
                           device=dev).close()
    finally:
        for site, cb in subs:
            pins.unsubscribe(site, cb)
    order = [(n, e) for n, e, _ in seen]
    miss = [("attach:build", "begin"), ("attach:partition", "begin"),
            ("attach:plan", "begin"), ("attach:plan", "end"),
            ("attach:partition", "end"), ("attach:bind", "begin"),
            ("attach:bind", "end"), ("attach:build", "end")]
    hit = [x for x in miss if x[0] != "attach:plan"]
    assert order == miss + hit
    notes = [p for n, e, p in seen if (n, e) == ("attach:build", "end")]
    assert [p["plan"] for p in notes] == ["miss", "hit"]
    assert all(p["tasks"] == 20 and p["regions"] == 0 for p in notes)
