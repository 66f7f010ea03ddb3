"""The hierarchical tile QR: the reduction tree alone (``ops/qr_tree.py``,
DPLASMA's ``dplasma_qrtree_t``), and the ONE tile-QR PTG over it
(``ops/qr.py``) on tall and square matrices, through ``Context`` and
through the pump, against the benchmark's plain reference (an unblocked
Householder QR in float32)."""

import numpy as np
import pytest

from benchmark.reference.sgeqrf_hqr_gram import householder_r
from parsec_tpu import Context, native
from parsec_tpu.datadist import TiledMatrix
from parsec_tpu.dsl import attach_plan
from parsec_tpu.dsl.graph import capture
from parsec_tpu.dsl.native_exec import NativeExecutor
from parsec_tpu.ops.qr import qr_ptg, run_qr
from parsec_tpu.ops.qr_tree import QRTree, flat_tree
from parsec_tpu.profiling import pins

needs_native = pytest.mark.skipif(not native.available(),
                                  reason="needs the native core")
NB = 16

#: (mt, nt, a): tall; M = N; MT not a multiple of a; a = 1; a >= MT
GRIDS = [(12, 3, 4), (6, 6, 2), (9, 4, 4), (7, 3, 1), (5, 2, 8), (16, 2, 2)]


# ---------------------------------------------------------------------------
# the tree alone
# ---------------------------------------------------------------------------

def _kills(t, k, p):
    """The rows ``p`` kills in panel ``k``, walked by ``nextpiv``."""
    out, m = [], t.nextpiv(k, p, t.mt)
    while m != t.mt:
        out.append(m)
        m = t.nextpiv(k, p, m)
    return out


@pytest.mark.parametrize("mt, nt, a", GRIDS)
def test_every_row_is_killed_once_by_a_pivot_that_is_alive(mt, nt, a):
    t = QRTree(mt, nt, a)
    for k in range(nt):
        heads = [t.getm(k, i) for i in range(t.getnbgeqrf(k))]
        assert heads[0] == k and heads == sorted(heads)
        assert [t.geti(k, m) for m in heads] == list(range(len(heads)))
        # the heads: row k, and every later row that starts a domain
        assert heads[1:] == [m for m in range(k + 1, mt) if m % t.a == 0]
        killed = [m for p in heads for m in _kills(t, k, p)]
        assert sorted(killed) == list(range(k + 1, mt))
        assert t.currpiv(k, k) == mt        # the root is killed by nobody
        for m in range(k + 1, mt):
            p = t.currpiv(k, m)
            assert p in heads and p < m and m in _kills(t, k, p)
            assert t.gettype(k, m) == (m in heads)
            # TS: inside the domain; TT: between heads
            if not t.gettype(k, m):
                assert m // t.a == p // t.a or p == k
            # the pivot is alive: it dies later than its victim, or never
            assert p == k or t.level(k, p) > t.level(k, m)
        for tt in (0, 1):
            rows = [t.getmkill(k, tt, i) for i in range(t.getnbkill(k, tt))]
            assert rows == [m for m in range(k + 1, mt)
                            if t.gettype(k, m) == tt]
            assert [t.getikill(k, m) for m in rows] == list(range(len(rows)))


@pytest.mark.parametrize("mt, nt, a", GRIDS)
def test_nextpiv_and_prevpiv_are_inverse_and_ts_comes_before_tt(mt, nt, a):
    t = QRTree(mt, nt, a)
    for k in range(nt):
        for i in range(t.getnbgeqrf(k)):
            p = t.getm(k, i)
            kills = _kills(t, k, p)
            back, m = [], t.prevpiv(k, p, p)
            while m != mt:
                back.append(m)
                m = t.prevpiv(k, p, m)
            assert back == kills[::-1]
            types = [t.gettype(k, m) for m in kills]
            assert types == sorted(types)
            # a killer makes its kills one step after another
            levels = [t.level(k, m) for m in kills]
            assert levels == sorted(set(levels)) and (not levels
                                                      or levels[0] >= 1)


def test_the_binary_tree_over_the_heads():
    t = QRTree(32, 1, 4)        # 8 heads: 0, 4, .., 28
    heads = [t.getm(0, i) for i in range(8)]
    assert heads == list(range(0, 32, 4))
    piv = {heads.index(m): heads.index(t.currpiv(0, m)) for m in heads[1:]}
    assert piv == {1: 0, 3: 2, 5: 4, 7: 6, 2: 0, 6: 4, 4: 0}
    # three TS steps in every domain, then three TT levels
    assert [t.level(0, m) for m in (1, 2, 3)] == [1, 2, 3]
    assert [t.level(0, heads[j]) for j in (1, 2, 4)] == [4, 5, 6]
    assert _kills(t, 0, 0) == [1, 2, 3, 4, 8, 16]


@pytest.mark.parametrize("mt, nt", [(5, 5), (7, 3)])
def test_the_flat_tree_is_the_chain(mt, nt):
    t = flat_tree(mt, nt)
    assert t.plan_fingerprint() == QRTree(mt, nt, mt + 5).plan_fingerprint()
    for k in range(nt):
        assert t.getnbgeqrf(k) == 1 and t.getnbkill(k, 1) == 0
        assert _kills(t, k, k) == list(range(k + 1, mt))
        assert [t.level(k, m) for m in range(k + 1, mt)] == \
            list(range(1, mt - k))


def test_a_tree_says_what_it_is_a_function_of_and_refuses_a_wide_grid():
    assert QRTree(8, 2, 4).plan_fingerprint() != \
        QRTree(8, 2, 2).plan_fingerprint()
    assert QRTree(8, 2, 4).plan_fingerprint() == \
        QRTree(8, 2, 4).plan_fingerprint()
    with pytest.raises(ValueError, match="mt >= nt"):
        QRTree(2, 3, 1)


def test_the_tables_are_built_at_the_first_question_under_attach_tree():
    seen = []
    pins.subscribe("attach:tree_begin", lambda es, p: seen.append(p))
    try:
        t = QRTree(6, 2, 2)
        assert not seen
        t.getnbgeqrf(0)
        t.currpiv(1, 3)
    finally:
        pins.clear()
    assert len(seen) == 1 and seen[0]["mt"] == 6


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------

def _matrix(mt, nt, seed):
    a = np.random.default_rng(seed).uniform(-0.5, 0.5, (mt * NB, nt * NB)) \
        .astype(np.float32)
    return a, TiledMatrix(mt * NB, nt * NB, NB, NB, name="A",
                          dtype=np.float32).from_array(a)


def _taskpool(A, tree, **kw):
    return qr_ptg(tree, use_tpu=True, use_cpu=False, **kw).taskpool(
        NT=A.nt, A=A, TILE_SHAPE=(NB, NB), TILE_DTYPE=np.float32,
        QSHAPE2=(np.float32, (2 * NB, 2 * NB)))


def _canonical(r):
    r = np.asarray(r, np.float64)
    return r * np.where(np.diagonal(r) < 0, -1.0, 1.0)[:, None]


def _check(A, a, tol=3e-5):
    """R up to the signs of its rows, zeros everywhere else."""
    R = A.to_array()
    n = a.shape[1]
    want = np.asarray(householder_r(a))
    assert np.max(np.abs(np.tril(R[:n], -1))) == 0.0
    assert not R[n:].any()
    assert np.max(np.abs(_canonical(R[:n]) - _canonical(want))) \
        <= tol * np.max(np.abs(want))


@pytest.mark.parametrize("mt, nt, a", GRIDS)
def test_context_gives_the_reference_r(mt, nt, a):
    a0, A = _matrix(mt, nt, seed=mt + nt)
    with Context(nb_cores=2) as ctx:
        run_qr(ctx, A, tree=QRTree(mt, nt, a), use_tpu=False)
    _check(A, a0)


@needs_native
@pytest.mark.parametrize("mt, nt, a", GRIDS)
def test_the_pump_gives_the_reference_r(mt, nt, a):
    a0, A = _matrix(mt, nt, seed=3 * mt + nt)
    ex = NativeExecutor(_taskpool(A, QRTree(mt, nt, a)), native_device=True)
    dev = ex.device
    ran = ex.run()
    ex.close()
    assert ran == len(ex.graph.nodes) == dev.stats["executed_tasks"]
    assert ex.stats["pumped_tasks"] == ran
    assert ex.stats["trampoline_entries"] == 0
    _check(A, a0)
    # what crossed the host is the matrix, once each way; no Q block
    assert dev.stats["bytes_in"] == dev.stats["bytes_out"] == a0.nbytes
    assert dev.stats["scratch_bytes_in"] == dev.stats["scratch_bytes_out"] \
        == 0
    assert dev.stats["scratch_tiles_born"] == dev.stats["scratch_tiles_freed"]


@needs_native
def test_a_tall_matrix_without_a_tree_takes_the_flat_one():
    a0, A = _matrix(6, 2, seed=4)
    tp = _taskpool(A, None)
    assert tp.constants["MT"] == 6
    assert tp.constants["TREE"].plan_fingerprint() == \
        flat_tree(6, 2).plan_fingerprint()
    classes = {cls for (cls, _k) in capture(tp, ranks=[0]).nodes}
    assert classes == {"geqrt", "unmqr", "tsqrt", "tsmqr"}
    ex = NativeExecutor(tp, native_device=True)
    ex.run()
    ex.close()
    _check(A, a0)


@needs_native
def test_the_second_solve_of_a_tree_binds_the_stored_plan():
    attach_plan.clear()
    dev, hows = None, []
    pins.subscribe("attach:build_end", lambda es, p: hows.append(p["plan"]))
    built = []
    pins.subscribe("attach:tree_end", lambda es, p: built.append(p))
    try:
        for a in (4, 4, 2, 4):
            a0, A = _matrix(8, 2, seed=9)
            ex = NativeExecutor(_taskpool(A, QRTree(8, 2, a)),
                                native_device=True, device=dev)
            dev = ex.device
            ex.run()
            ex.close()
            _check(A, a0)
    finally:
        pins.clear()
        attach_plan.clear()
    # another domain size is another DAG; the same one is a hit, and a
    # hit asks the tree nothing: its tables are never built
    assert hows == ["miss", "hit", "miss", "hit"]
    assert len(built) == 2


@needs_native
def test_scratch_bytes_peak_is_the_width_of_the_dag():
    tile = 4 * NB * NB

    def peak(mt, nt, a):
        _a0, A = _matrix(mt, nt, seed=1)
        ex = NativeExecutor(_taskpool(A, QRTree(mt, nt, a)),
                            native_device=True)
        dev = ex.device
        ex.run()
        ex.close()
        assert dev.stats["scratch_tiles_born"] == \
            dev.stats["scratch_tiles_freed"] > 0
        return dev.stats["scratch_bytes_peak"]
    # one panel: every kill's block is alive until the updates... of which
    # there are none with one tile column, so each dies with its kill
    assert peak(8, 1, 2) == 4 * tile
    # two tile columns: the blocks of panel 0's kills (4 geqrt of one
    # tile, 4 + 3 kills of four) wait for their updates
    wide = peak(8, 2, 2)
    assert 4 * tile < wide <= (4 + 4 * 7) * tile
    # the flat tree's panel is a chain: fewer alive at once
    assert peak(8, 2, 8) <= wide


def test_a_method_of_a_constant_in_an_argument_and_a_range_through_a_definition():
    """``C1 t(k, TREE.nextpiv(k, p, m), n)``: nested parentheses and
    commas inside one argument; ``i = 0 .. TREE.n(k)-1``, ``m =
    TREE.m(k, i)``: a parameter over an irregular set."""
    from parsec_tpu.core.lifecycle import AccessMode
    from parsec_tpu.dsl.ptg import PTG

    class Rows:
        rows = {0: [0, 3, 4], 1: [2]}

        def n(self, k):
            return len(self.rows[k])

        def m(self, k, i):
            return self.rows[k][i]

        def after(self, k, m, none):
            later = [r for r in self.rows[k] if r > m]
            return later[0] if later else none

        def i(self, k, m):
            return self.rows[k].index(m)

        def plan_fingerprint(self):
            return ("Rows",)

    seen = []
    ptg = PTG("rows")
    ptg.default("TREE", lambda c: Rows())
    t = ptg.task_class("t", k="0 .. 1", i="0 .. TREE.n(k)-1")
    t.define("m", "TREE.m(k, i)")
    t.define("nxt", "TREE.after(k, m, 99)")
    t.flow("X", AccessMode.INOUT,
           "<- (i == 0) ? NEW : X t(k, TREE.i(k, m) - 1)",
           "-> (nxt != 99) ? X t(k, TREE.i(k, TREE.after(k, m, 99)))")
    t.body(cpu=lambda X, k, i, m, **_: seen.append((k, i, m)))
    tp = ptg.taskpool(TILE_SHAPE=(2,))
    g = capture(tp, ranks=[0])
    assert set(g.nodes) == {("t", (0, 0)), ("t", (0, 1)), ("t", (0, 2)),
                            ("t", (1, 0))}
    assert g.nodes[("t", (0, 1))].out_edges == [("X", ("t", (0, 2)), "X")]
    assert ptg.verify({}) == []
    with Context(nb_cores=1) as ctx:
        ctx.add_taskpool(tp)
        assert tp.wait(timeout=60)
    assert sorted(seen) == [(0, 0, 0), (0, 1, 3), (0, 2, 4), (1, 0, 2)]
    # the plan's key takes the object's own fingerprint...
    key = attach_plan.plan_key(ptg.taskpool(TILE_SHAPE=(2,)), (0,),
                               ("off",), (128, 2))
    assert any("Rows" in repr(part) for part in key)
    # ... and refuses one that gives none, or a call of anything else
    del Rows.plan_fingerprint
    with pytest.raises(attach_plan.Uncacheable, match="TREE"):
        attach_plan.plan_key(ptg.taskpool(TILE_SHAPE=(2,)), (0,),
                             ("off",), (128, 2))
