"""When a tile is read next: the table an attach plan keeps for the
device module's victim order (``dsl/attach_plan.py`` ``_next_uses``,
``_pump_rank``; ``device/residency.py``; PR 33).

The table of a small plan is compared with a brute-force walk of its
rows; the rank it is made from IS the order the pump runs the graph in; a
plan hit builds nothing and hands every bind the stored arrays; a pool
without a stored plan, a ``Context``-path pool and a tile a CPU body
touches stay "unknown", and evict as they always did.  Counts on the CPU
backend, never a time.
"""

import collections

import numpy as np
import pytest

from parsec_tpu import native
from parsec_tpu.core.context import Context
from parsec_tpu.core.lifecycle import AccessMode
from parsec_tpu.datadist import TiledMatrix
from parsec_tpu.device.residency import NEVER, UNKNOWN
from parsec_tpu.dsl import attach_plan
from parsec_tpu.dsl.graph import capture
from parsec_tpu.dsl.native_exec import (NativeExecutor, NativeServeExecutor,
                                        _pump_window)
from parsec_tpu.dsl.ptg import PTG
from parsec_tpu.ops import cholesky_ptg
from parsec_tpu.ops.qr import qr_ptg
from parsec_tpu.ops.stencil import stencil_grid, stencil_taskpool
from parsec_tpu.utils import mca_param

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="needs the native core")
NB = 8
INOUT, IN, OUT = AccessMode.INOUT, AccessMode.IN, AccessMode.OUT


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


def _dpotrf(nt=5, seed=3, nb=NB, matrix=TiledMatrix, **ptg_kw):
    n = nt * nb
    A = matrix(n, n, nb, nb, name="A", dtype=np.float32) \
        .from_array(_spd(nt, seed, nb))
    return cholesky_ptg(use_tpu=True, use_cpu=False, **ptg_kw) \
        .taskpool(NT=A.mt, A=A), A


def _geqrf(nt=4, seed=3):
    n = nt * NB
    a = np.random.default_rng(seed).uniform(-0.5, 0.5, (n, n)) \
        .astype(np.float32)
    A = TiledMatrix(n, n, NB, NB, name="A", dtype=np.float32).from_array(a)
    return qr_ptg(use_tpu=True, use_cpu=False).taskpool(
        NT=A.mt, A=A, TILE_SHAPE=(NB, NB), TILE_DTYPE=np.float32,
        QSHAPE2=(np.float32, (2 * NB, 2 * NB))), A


def _stencil(mt=3, iters=3, seed=3):
    grid = np.random.default_rng(seed).uniform(
        0, 1, (mt * NB, mt * NB)).astype(np.float32)
    A = stencil_grid(grid, mt, mt)
    B = stencil_grid(np.zeros_like(grid), mt, mt, name="B")
    return stencil_taskpool(A, iters, B=B, use_tpu=True, use_cpu=False), B


POOLS = {"dpotrf": _dpotrf, "geqrf": _geqrf, "stencil": _stencil}


def _plan_of(tp, regions=(), window=attach_plan.PUMP_WINDOW):
    return attach_plan.build_plan(tp, capture(tp, ranks=[0]), regions,
                                  window)


def _rank(plan, window=attach_plan.PUMP_WINDOW):
    return attach_plan._pump_rank(
        list(plan.native_prio), list(plan.edge_pred), list(plan.edge_succ),
        plan.roots, *window)


def _brute_force(plan, tp, rank):
    """(native node, position) -> next use, by walking every other node's
    positions for every position: no sort, no per-slot table."""
    classes = tp.ptg.classes

    def positions(node):
        pos = plan.native[node]
        if pos < 0:
            fp, slots = plan.fused[~pos][:2]
            return [(s, m != int(OUT), True)
                    for s, m in zip(slots, fp.slot_modes)]
        ci, _locs, _prio, slots = plan.tasks[pos][:4]
        pc = classes[plan.classes[ci]]
        return [(s, (f.mode & INOUT) != OUT, plan.device_class[ci])
                for s, f in zip(slots, pc.flows)]

    every = [positions(n) for n in range(len(plan.native))]
    out = {}
    for node, mine in enumerate(every):
        for p, (slot, _reads, _dev) in enumerate(mine):
            if slot < 0:
                out[node, p] = UNKNOWN
                continue
            best = None     # (rank, the node reads the slot)
            host = False
            for other, theirs in enumerate(every):
                touching = [r for s, r, _d in theirs if s == slot]
                if not touching:
                    continue
                if not all(d for s, _r, d in theirs if s == slot):
                    host = True
                if rank[other] > rank[node] \
                        and (best is None or rank[other] < best[0]):
                    best = (rank[other], any(touching))
            out[node, p] = UNKNOWN if host else \
                NEVER if best is None or not best[1] else best[0]
    return out


# -- (a) the table is what a walk of the rows says -------------------------

@pytest.mark.parametrize("which", sorted(POOLS))
def test_table_equals_a_brute_force_walk_of_the_rows(which):
    tp, _A = POOLS[which]()
    plan = _plan_of(tp)
    rank = _rank(plan)
    assert sorted(rank) == list(range(len(plan.native)))
    want = _brute_force(plan, tp, rank)
    got = {}
    for node, at in enumerate(plan.next_at):
        pos = plan.native[node]
        for p in range(len(plan.tasks[pos][3])):
            got[node, p] = plan.next_use[at + p]
    assert got == want
    assert len(plan.next_use) == len(want)
    known = [u for u in want.values() if u != UNKNOWN]
    assert NEVER in known and any(u != NEVER for u in known)
    assert all(type(u) is int for u in plan.next_use)


def test_dpotrf_panel_tiles_die_with_their_step_and_trailing_tiles_live():
    """What age cannot say: after the last update of step ``k`` that
    reads it, ``A(m, k)`` is read NEVER again; the trailing tile
    ``A(m, n)`` a gemm of step ``k`` wrote is read by step ``k + 1``."""
    nt = 6
    tp, _A = _dpotrf(nt=nt)
    plan = _plan_of(tp)
    rank = _rank(plan)
    flows = {c: [f.name for f in tp.ptg.classes[c].flows]
             for c in plan.classes}

    def use(cls, locs, flow):
        node = plan.node_of[plan.nodes[cls, locs]]
        return plan.next_use[plan.next_at[node] + flows[cls].index(flow)]

    def rank_of(cls, locs):
        return rank[plan.node_of[plan.nodes[cls, locs]]]

    for k in range(nt - 2):
        for m in range(k + 2, nt):
            for n in range(k + 1, m):
                nxt = ("gemm", (k + 1, m, n)) if k + 1 < n \
                    else ("trsm", (n, m))
                assert use("gemm", (k, m, n), "A") == rank_of(*nxt)
    # the last reader of a panel tile, in rank order, says NEVER
    k = 1
    for m in range(k + 1, nt):
        readers = [("syrk", (k, m), "B")] \
            + [("gemm", (k, m, n), "B1") for n in range(k + 1, m)] \
            + [("gemm", (k, i, m), "B2") for i in range(m + 1, nt)]
        readers.sort(key=lambda r: rank_of(r[0], r[1]))
        uses = [use(*r) for r in readers]
        assert uses[-1] == NEVER
        assert uses[:-1] == [rank_of(r[0], r[1]) for r in readers[1:]]
    # the factor's tiles: nobody reads what the last potrf wrote
    assert use("potrf", (nt - 1,), "T") == NEVER


def _overwrite_pool():
    """``rd(k)`` reads ``A(k, 0)``; ``wr(k)``, after it, only overwrites
    that tile (an ``OUT`` flow, as the stencil's last sweep writes its
    result's tile)."""
    A = TiledMatrix(3 * NB, 2 * NB, NB, NB, name="A", dtype=np.float32) \
        .from_array(np.ones((3 * NB, 2 * NB), np.float32))
    ptg = PTG("overwrite")
    rd = ptg.task_class("rd", k="0 .. NT-1")
    rd.affinity("A(k, 1)")
    rd.flow("T", IN, "<- A(k, 0)")
    rd.flow("S", INOUT, "<- A(k, 1)", "-> S wr(k)")
    rd.body(tpu=_add)
    wr = ptg.task_class("wr", k="0 .. NT-1")
    wr.affinity("A(k, 0)")
    wr.flow("S", IN, "<- S rd(k)")
    wr.flow("W", OUT, "<- A(k, 0)", "-> A(k, 0)")
    wr.body(tpu=_times_five)
    return ptg.taskpool(NT=A.mt, A=A), A


def _add(T, S, k):
    return S + T


def _times_five(S, W, k):
    return S * 5


def test_a_tile_that_is_only_overwritten_next_is_dead(dev):
    """The next touch of ``A(k, 0)`` after its reader only overwrites it:
    the version on the device is dead, whatever the writer's rank."""
    tp, A = _overwrite_pool()
    ex = NativeExecutor(tp, native_device=True, device=dev)
    plan = ex.graph
    rank = _rank(plan, _pump_window(dev))
    for k in range(3):
        rd, wr = (plan.node_of[plan.nodes[c, (k,)]] for c in ("rd", "wr"))
        assert rank[wr] > rank[rd]
        assert plan.next_use[plan.next_at[rd]:plan.next_at[rd] + 2] \
            == (NEVER, rank[wr])
        assert plan.next_use[plan.next_at[wr]:plan.next_at[wr] + 2] \
            == (NEVER, NEVER)
    ex.run()
    ex.close()
    got = A.to_array()
    assert np.all(got[:, :NB] == 10.0) and np.all(got[:, NB:] == 2.0)


def test_two_names_of_one_collection_are_two_tiles_to_the_table():
    """In place (``B`` is ``A``) the stencil reads generation 0 as
    ``A(i, j)`` and writes the result as ``B(i, j)``: a plan keeps names,
    not collections, so the table has a slot for each — here both say
    NEVER at their last touch, which is also what one slot would say."""
    grid = np.random.default_rng(3).uniform(0, 1, (3 * NB, 3 * NB)) \
        .astype(np.float32)
    A = stencil_grid(grid, 3, 3)
    plan = _plan_of(stencil_taskpool(A, 3, use_tpu=True, use_cpu=False))
    rank = _rank(plan)
    for name, touches in (("A", 5), ("B", 1)):
        s = plan.tiles.index(("data", name, (1, 1)))
        uses = sorted(
            (rank[node], plan.next_use[at + p])
            for node, at in enumerate(plan.next_at)
            for p, sl in enumerate(plan.tasks[plan.native[node]][3])
            if sl == s)
        assert len(uses) == touches and uses[-1][1] == NEVER
        assert [u for _r, u in uses[:-1]] == [r for r, _u in uses[1:]]


# -- (b) the rank is the pump's order ---------------------------------------

@pytest.mark.parametrize("case", ["dpotrf", "dpotrf_small_batches", "geqrf",
                                  "stencil", "synchronous"])
def test_the_rank_is_the_order_the_pump_pops(case):
    """The pump's order on one device is a function of the graph, the
    batch and the window: the plan replays it (``_pump_rank``), and a
    solve pops exactly that."""
    params = {"dpotrf_small_batches": [("runtime", "native_drain", 6)],
              "synchronous": [("runtime", "stage_depth", 1),
                              ("runtime", "native_drain", 5)]}.get(case, [])
    for fw, name, value in params:
        mca_param.params.set(fw, name, value)
    try:
        tp, _A = {"dpotrf_small_batches": lambda: _dpotrf(nt=7),
                  "synchronous": lambda: _dpotrf(nt=6)}.get(
                      case, POOLS.get(case))()
        ex = NativeExecutor(tp, native_device=True)
        dev = ex.device
        window = _pump_window(dev)
        popped = []
        submit = dev.submit_batch

        def spy(tasks, es=None, **kw):
            popped.extend(t.native_id - ex._native_base for t in tasks)
            return submit(tasks, es, **kw)

        dev.submit_batch = spy
        plan = ex.graph
        assert ex.run() == len(plan.tasks)
        ex.close()
    finally:
        for fw, name, _v in params:
            mca_param.params.unset(fw, name)
    assert window == {"dpotrf_small_batches": (3, 2),
                      "synchronous": (5, 1)}.get(case, (128, 2))
    rank = _rank(plan, window)
    assert popped == sorted(range(len(rank)), key=rank.__getitem__)
    assert plan.key[-1] == window


def test_the_window_is_part_of_the_key(dev):
    ex = NativeExecutor(_dpotrf()[0], native_device=True, device=dev)
    assert ex.stats["attach_plan_misses"] == 1
    table = ex.graph.next_use
    ex.close()
    mca_param.params.set("runtime", "native_drain", 4)
    try:
        ex = NativeExecutor(_dpotrf()[0], native_device=True, device=dev)
        assert ex.stats["attach_plan_misses"] == 1
        assert ex.graph.next_use != table
        ex.close()
    finally:
        mca_param.params.unset("runtime", "native_drain")
    ex = NativeExecutor(_dpotrf()[0], native_device=True, device=dev)
    assert ex.stats["attach_plan_hits"] == 1 and ex.graph.next_use == table
    ex.close()


# -- (c) a hit builds nothing; a bind hands out an index --------------------

@pytest.mark.parametrize("which", sorted(POOLS))
def test_a_hit_builds_no_table_and_shares_the_stored_arrays(
        which, dev, monkeypatch):
    ex1 = NativeExecutor(POOLS[which]()[0], native_device=True, device=dev)
    assert ex1.stats["attach_plan_misses"] == 1
    stored = attach_plan.stored()[0]
    ex1.close()

    def never(*a, **k):
        raise AssertionError("a plan hit computed next uses again")

    monkeypatch.setattr(attach_plan, "_next_uses", never)
    monkeypatch.setattr(attach_plan, "_pump_rank", never)
    ex2 = NativeExecutor(POOLS[which]()[0], native_device=True, device=dev)
    assert ex2.stats["attach_plan_hits"] == 1
    assert ex2.graph is stored
    assert ex2._pool_shim.next_use is stored.next_use
    assert type(stored.next_use) is tuple and type(stored.next_at) is tuple
    for nid, task in ex2._pump_index.items():
        assert type(task._tpu_next) is int
        assert task._tpu_next == stored.next_at[nid - ex2._native_base]
        assert task.taskpool is ex2._pool_shim
    ex2.close()


def test_serve_tenants_index_one_table(dev):
    pools = [_dpotrf(seed=s)[0] for s in (3, 4)]
    sx = NativeServeExecutor(pools, device=dev)
    a, b = sx.children
    assert a._pool_shim.next_use is b._pool_shim.next_use \
        is attach_plan.stored()[0].next_use
    assert sx.run() == [len(a.graph.tasks)] * 2
    sx.close()


# -- (d) who stays unknown --------------------------------------------------

def _tiles_known(dev):
    return dict(dev._res._next)


def _budget_of(dev, tiles, nb=NB):
    dev.hbm_budget = tiles * nb * nb * 4


def test_an_uncacheable_pool_has_no_table_and_evicts_by_age():
    tp, A = _dpotrf(nt=6, matrix=type("MyMatrix", (TiledMatrix,), {}))
    ex = NativeExecutor(tp, native_device=True)
    assert ex.stats["attach_plan_uncacheable"] == 1
    assert ex.graph.next_use == () and ex.graph.next_at == ()
    assert all(t._tpu_next == -1 for t in ex._pump_index.values())
    dev = ex.device
    _budget_of(dev, 14)     # 21 lower tiles
    seen = []
    evict = dev._res._evict

    def spy(need):
        seen.append(_tiles_known(dev))
        return evict(need)

    dev._res._evict = spy
    assert ex.run() == len(ex.graph.tasks)
    ex.close()
    s = dev.stats
    assert s["evictions"] > 0 and seen and not any(seen)
    assert s["evict_next_use"] == s["evict_never_again"] == 0
    ref = np.linalg.cholesky(_spd(6, 3).astype(np.float64))
    assert np.max(np.abs(np.tril(A.to_array()) - ref)) < 1e-3 * np.max(ref)


def test_a_handed_in_graph_has_no_table(dev):
    tp, _A = _dpotrf()
    ex = NativeExecutor(tp, graph=capture(tp, ranks=[0]),
                        native_device=True, device=dev)
    assert ex.graph.next_use == ()
    ex.close()


def test_a_context_path_pool_says_nothing_and_evicts_by_age():
    nt, nb = 6, 32
    n = nt * nb
    spd = _spd(nt, 5, nb)
    A = TiledMatrix(n, n, nb, nb, name="A", dtype=np.float32).from_array(spd)
    tp = cholesky_ptg(use_tpu=True, use_cpu=False).taskpool(NT=nt, A=A)
    mca_param.params.set("device", "tpu_hbm_budget_mb", 1)
    try:
        ctx = Context(nb_cores=2)
    finally:
        mca_param.params.unset("device", "tpu_hbm_budget_mb")
    dev = next(d for d in ctx.devices if hasattr(d, "_res"))
    _budget_of(dev, 14, nb)
    try:
        ctx.add_taskpool(tp)
        assert ctx.wait(timeout=120)
        assert not dev._res._next
        dev.flush()
    finally:
        ctx.fini()
    s = dev.stats
    assert s["evictions"] > 0
    assert s["evict_next_use"] == s["evict_never_again"] == 0
    ref = np.linalg.cholesky(spd.astype(np.float64))
    assert np.max(np.abs(np.tril(A.to_array()) - ref)) < 1e-3 * np.max(ref)


def _mixed_pool():
    A = TiledMatrix(4 * NB, 2 * NB, NB, NB, name="A", dtype=np.float32) \
        .from_array(np.ones((4 * NB, 2 * NB), np.float32))
    ptg = PTG("mixed")
    dbl = ptg.task_class("dbl", k="0 .. NT-1")
    dbl.affinity("A(k, 0)")
    dbl.flow("T", INOUT, "<- A(k, 0)", "-> T inc(k)")
    dbl.flow("S", INOUT, "<- A(k, 1)", "-> S tri(k)")
    dbl.body(tpu=_double)
    inc = ptg.task_class("inc", k="0 .. NT-1")
    inc.affinity("A(k, 0)")
    inc.flow("T", INOUT, "<- T dbl(k)", "-> A(k, 0)")
    inc.body(cpu=_increment)
    tri = ptg.task_class("tri", k="0 .. NT-1")
    tri.affinity("A(k, 1)")
    tri.flow("S", INOUT, "<- S dbl(k)", "-> A(k, 1)")
    tri.body(tpu=_triple)
    return ptg.taskpool(NT=A.mt, A=A), A


def _double(T, S, k):
    return T * 2, S * 2


def _triple(S, k):
    return S * 3


def _increment(T, k):
    T += 1


def test_a_tile_a_cpu_body_touches_is_unknown_throughout(dev):
    """A CPU body never passes the staging walk: nothing would move the
    tile's next use past it, so nobody claims to know it."""
    tp, A = _mixed_pool()
    ex = NativeExecutor(tp, native_device=True, device=dev)
    plan = ex.graph
    assert plan.has_cpu_bodies
    slot_of = {t: s for s, t in enumerate(plan.tiles)}
    rank = _rank(plan, _pump_window(dev))
    for node, at in enumerate(plan.next_at):
        ci, locs, _prio, slots = plan.tasks[plan.native[node]][:4]
        k = locs[0]
        for p, s in enumerate(slots):
            use = plan.next_use[at + p]
            if s == slot_of["data", "A", (k, 0)]:
                assert use == UNKNOWN
            elif plan.classes[ci] == "dbl":
                assert use == rank[plan.node_of[plan.nodes["tri", (k,)]]]
            else:
                assert plan.classes[ci] == "tri" and use == NEVER
    ex.run()
    ex.close()
    got = A.to_array()
    assert np.all(got[:, :NB] == 3.0) and np.all(got[:, NB:] == 6.0)


# -- (e) fused regions follow the same table --------------------------------

def test_a_fused_region_has_a_row_of_its_program_arguments(dev):
    tp, A = _dpotrf(nt=5)
    ex = NativeExecutor(tp, native_device=True, device=dev, fusion="chains")
    plan = ex.graph
    if not plan.fused:
        pytest.skip("no region at this size")
    rank = _rank(plan, _pump_window(dev))
    want = _brute_force(plan, tp, rank)
    for node, at in enumerate(plan.next_at):
        pos = plan.native[node]
        width = len(plan.fused[~pos][1]) if pos < 0 \
            else len(plan.tasks[pos][3])
        for p in range(width):
            assert plan.next_use[at + p] == want[node, p]
        task = ex._pump_index[ex._native_base + node]
        assert task._tpu_next == at
        if pos < 0:
            assert len(task.body_args) == width
    assert len(plan.next_use) == len(want)
    ex.run()
    ex.close()
    ref = np.linalg.cholesky(_spd(5, 3).astype(np.float64))
    assert np.max(np.abs(np.tril(A.to_array()) - ref)) < 1e-3 * np.max(ref)


# -- (f) what the device module tells the residency --------------------------

def test_the_staging_walk_and_the_commit_record_the_tables_answers():
    """After every batch the residency's record of a resident tile is an
    answer the table gave for it: the largest any task staged so far gave
    (readers may run out of rank order inside a batch)."""
    tp, _A = _dpotrf(nt=6)
    ex = NativeExecutor(tp, native_device=True)
    dev, plan = ex.device, ex.graph
    answers = collections.defaultdict(set)   # data_id -> what tasks said
    for task in ex._pump_index.values():
        at = task._tpu_next
        for p, spec in enumerate(task.body_args):
            if spec[0] == "data" and spec[1] is not None:
                answers[spec[1].data_id].add(plan.next_use[at + p])
    checked = []
    submit = dev.submit_batch

    def spy(tasks, es=None, **kw):
        out = submit(tasks, es, **kw)
        known = _tiles_known(dev)
        assert known and all(u in answers[d] for d, u in known.items())
        checked.append(len(known))
        return out

    dev.submit_batch = spy
    assert ex.run() == len(plan.tasks)
    assert checked
    # the factor's tiles at the end: nobody reads them again
    assert set(_tiles_known(dev).values()) == {NEVER}
    assert len(_tiles_known(dev)) == 6 * 7 // 2
    ex.close()
    assert not _tiles_known(dev)
