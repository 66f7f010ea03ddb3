"""A PTG task on the ``Context`` route says which of its read-write
inputs it alone consumes (``Task._tpu_donate``, from the classes' own
dependencies: ``PTGTaskpool._donate_rule`` / ``_sole_reader``): the
version a producer WROTE and handed to this task and to nobody else, or
the first version of a collection no flow reads from memory without
writing.  The device module lets the task's program write the output over
such a tile where it stands (``tile_args_donated``).  Counts and values
on the CPU backend, a case each; never a time."""

import numpy as np
import pytest

from parsec_tpu import Context
from parsec_tpu.dsl.ptg import IN, INOUT, PTG, PTGTaskpool

from test_multirank import run_ranks
from test_ptg_last_versions import N, _chain, _ones, _tpu_of


def _value(dc, *key):
    return np.asarray(dc.data_of(*key).newest_copy().payload)


def _said(tp):
    """``{(class, key): _tpu_donate}`` of every task of ``tp``, filled
    as each leaves ``prepare_input``."""
    said = {}
    for name, tc in tp._built.items():
        def wrapped(es, task, _inner=tc.prepare_input, _name=name):
            rc = _inner(es, task)
            said[(_name, task.locals)] = task._tpu_donate
            return rc
        tc.prepare_input = wrapped
    return said


def _run(tp):
    """One pool through ``Context.add_taskpool``: what its tasks said,
    and the device's counters once everything it owes is home."""
    said = _said(tp)
    c = Context(nb_cores=2)
    try:
        dev = _tpu_of(c)
        c.add_taskpool(tp)
        assert tp.wait(timeout=120)
        dev.flush()
    finally:
        c.fini()
    return said, dev.stats


def _dpotrf(n, nb, dist=None):
    from parsec_tpu.datadist import TiledMatrix
    from parsec_tpu.ops.cholesky import cholesky_ptg

    M = np.random.default_rng(5).standard_normal((n, n))
    S = M @ M.T + n * np.eye(n)
    A = (dist or TiledMatrix(n, n, nb, nb, name="A")).from_array(S)
    tp = cholesky_ptg(use_tpu=True, use_cpu=False).taskpool(NT=A.mt, A=A)
    return tp, A, S


def case_dpotrf_rule():
    """The dpotrf's four classes: the one read-write flow of every task
    is donated, first versions included (every ``<- A(m, n)`` of the PTG
    is on a flow that writes); the position is the flow's index."""
    tp, A, _S = _dpotrf(128, 16)
    classes = tp.ptg.classes
    flow = {"potrf": "T", "trsm": "C", "syrk": "A", "gemm": "A"}
    at = {name: next(f.index for f in classes[name].flows
                     if f.name == fname) for name, fname in flow.items()}
    assert at == {"potrf": 0, "trsm": 1, "syrk": 0, "gemm": 0}
    for name, pc in classes.items():
        rule = tp._donate_rule(pc)
        # (one branch reads the collection's tile, the other a producer)
        assert sorted(type(how).__name__ for how in rule.values()) \
            == ["bool", "tuple"], name
    said, stats = _run(tp)
    assert len(said) == stats["executed_tasks"] == 120
    assert {k: v for k, v in said.items() if v != (at[k[0]],)} == {}
    assert stats["tile_args_donated"] == 120
    assert stats["donation_refused"] == 0
    assert stats["commits_donate_unknown"] == 0


def _fan(readers):
    """``w(0)`` writes a version of ``A(0)`` and hands it to ``u(0)``,
    which rewrites it, and to the read-only ``r(1 .. NR)``, which add it
    into tiles of their own; ``u`` waits for every reader."""
    A, B = _ones("A"), _ones("B")
    ptg = PTG("fan")
    w = ptg.task_class("w", k="0 .. 0")
    w.affinity("A(0)")
    w.flow("T", INOUT, "<- A(0)", "-> T u(0)", "-> T r(1 .. NR)")
    w.body(tpu=lambda T, k: T + 1.0)
    r = ptg.task_class("r", j="1 .. NR")
    r.affinity("B(j)")
    r.flow("T", IN, "<- T w(0)")
    r.flow("X", INOUT, "<- B(j)", "-> B(j)")
    r.ctl("c", "-> c u(0)")
    r.body(tpu=lambda T, X, j: X + T)
    u = ptg.task_class("u", k="0 .. 0")
    u.affinity("A(0)")
    u.flow("T", INOUT, "<- T w(0)", "-> A(0)")
    u.ctl("c", "<- c r(1 .. NR)")
    u.body(tpu=lambda T, k: T * 3.0)
    return ptg.taskpool(A=A, B=B, NR=readers), A, B


def _fan_case(readers):
    tp, A, B = _fan(readers)
    said, stats = _run(tp)
    # w and r take first versions (no flow reads A or B read-only from
    # memory); u's input is its alone only where nobody else was handed it
    assert said[("w", (0,))] == (0,)
    assert said[("u", (0,))] == ((0,) if readers == 0 else ())
    for j in range(1, readers + 1):
        assert said[("r", (j,))] == (1,)
        np.testing.assert_array_equal(_value(B, j), np.full((N, N), 3.0))
    assert stats["tile_args_donated"] == 1 + readers + (readers == 0)
    assert stats["donation_refused"] == 0
    np.testing.assert_array_equal(_value(A, 0), np.full((N, N), 6.0))


def case_sole_reader_of_an_empty_range():
    """``-> T r(1 .. NR)`` with nobody in the range hands the version to
    nobody: ``u`` is its only consumer."""
    _fan_case(0)


def case_second_reader_is_read_only():
    """A version with TWO readers, one of them read-only: the writer
    among them may not write over it."""
    _fan_case(1)


def case_range_of_two_readers():
    """A ranged dependency counts every instance of its range."""
    _fan_case(2)


def case_collection_read_from_memory():
    """A PTG that reads a tile of the collection read-only from memory
    anywhere keeps the collection's first versions; the versions its
    tasks write and hand on are donated as ever."""
    A, B = _ones("A"), _ones("B")
    ptg = _chain(4, "-> (k == 3) ? A(0) : T step(k+1)")
    look = ptg.task_class("look", k="0 .. 0")
    look.affinity("B(0)")
    look.flow("X", IN, "<- A(1)")
    look.flow("Y", INOUT, "<- B(0)", "-> B(0)")
    look.body(tpu=lambda X, Y, k: Y + X)
    tp = ptg.taskpool(A=A, B=B)
    said, stats = _run(tp)
    assert said == {("step", (0,)): (), ("step", (1,)): (0,),
                    ("step", (2,)): (0,), ("step", (3,)): (0,),
                    ("look", (0,)): (1,)}
    assert stats["tile_args_donated"] == 4
    assert stats["commits_donate_unknown"] == 0
    np.testing.assert_array_equal(_value(A, 0), np.full((N, N), 5.0))
    np.testing.assert_array_equal(_value(B, 0), np.full((N, N), 2.0))


def case_two_names_of_one_collection():
    """The collection is what is read, not its name in the pool."""
    A = _ones("A")
    ptg = _chain(2, "-> (k == 1) ? A(0) : T step(k+1)")
    look = ptg.task_class("look", k="0 .. 0")
    look.affinity("A(1)")
    look.flow("X", IN, "<- ALIAS(2)")
    look.flow("Y", INOUT, "<- A(1)", "-> A(1)")
    look.body(tpu=lambda X, Y, k: Y + X)
    said, _stats = _run(ptg.taskpool(A=A, ALIAS=A))
    assert said == {("step", (0,)): (), ("step", (1,)): (0,),
                    ("look", (0,)): ()}


def case_dynamic_guard():
    """A producer's guard that reads an array of the pool (state a body
    may write) has one value when the version is handed on and perhaps
    another at the consumer's ``prepare_input``: the class cannot know,
    ``_tpu_donate`` stays None and nothing is donated."""
    dc = _ones()
    route = np.ones(5, dtype=int)
    tp = _chain(5, "-> (k < 4 && route[k] == 1) ? T step(k+1)",
                "-> (k == 4 || route[k] != 1) ? A(0)").taskpool(
        A=dc, route=route)
    assert tp._donate_rule(tp.ptg.classes["step"]) is None
    said, stats = _run(tp)
    assert set(said.values()) == {None}
    assert stats["commits_donate_unknown"] == 5
    assert stats["tile_args_donated"] == 0
    np.testing.assert_array_equal(_value(dc, 0), np.full((N, N), 6.0))


def case_version_landed_in_another_tile():
    """A producer that also lands its version in a collection tile
    OTHER than the flow's own has handed it to somebody else; into the
    flow's own tile it has not."""
    def pool(where):
        A = _ones("A")
        ptg = _chain(3, "-> (k < 2) ? T step(k+1)",
                     f"-> (k == 1) ? A({where})", "-> (k == 2) ? A(0)")
        return ptg.taskpool(A=A), A

    tp, A = pool(1)
    said, _ = _run(tp)
    assert [said[("step", (k,))] for k in range(3)] == [(0,), (0,), ()]
    np.testing.assert_array_equal(_value(A, 1), np.full((N, N), 3.0))
    np.testing.assert_array_equal(_value(A, 0), np.full((N, N), 4.0))
    tp, A = pool(0)
    said, _ = _run(tp)
    assert [said[("step", (k,))] for k in range(3)] == [(0,)] * 3
    np.testing.assert_array_equal(_value(A, 0), np.full((N, N), 4.0))


def case_forwarded_version_and_new_tile():
    """A flow the producer only READ forwards a version whose other
    readers share it, and a ``NEW`` tile is no version anybody handed
    over: neither is donated."""
    A, B = _ones("A"), _ones("B")
    ptg = PTG("forward")
    p = ptg.task_class("p", k="0 .. 0")
    p.affinity("A(0)")
    p.flow("T", INOUT, "<- A(0)", "-> T q(0)")
    p.body(tpu=lambda T, k: T + 1.0)
    q = ptg.task_class("q", k="0 .. 0")
    q.affinity("B(0)")
    q.flow("T", IN, "<- T p(0)", "-> T s(0)")
    q.flow("S", INOUT, "<- NEW", "-> S s(0)")
    q.body(tpu=lambda T, S, k: T * 2.0)
    s = ptg.task_class("s", k="0 .. 0")
    s.affinity("B(0)")
    s.flow("T", INOUT, "<- T q(0)", "-> A(0)")
    s.flow("S", INOUT, "<- S q(0)", "-> B(0)")
    s.body(tpu=lambda T, S, k: (T + S, S + 1.0))
    tp = ptg.taskpool(A=A, B=B, TILE_SHAPE=(N, N), TILE_DTYPE=np.float64)
    said, stats = _run(tp)
    assert said == {("p", (0,)): (0,), ("q", (0,)): (), ("s", (0,)): (1,)}
    assert stats["tile_args_donated"] == 2
    np.testing.assert_array_equal(_value(A, 0), np.full((N, N), 6.0))
    np.testing.assert_array_equal(_value(B, 0), np.full((N, N), 5.0))


def case_context_nt8_bitwise():
    """The dpotrf through ``Context`` at NT = 8: every read-write flow
    donated, none refused, the lower matrix home once (PR 47), and the
    factor the functional one's bit for bit."""
    tp, A, S = _dpotrf(256, 32)
    said, stats = _run(tp)
    assert stats["executed_tasks"] == 120
    assert stats["tile_args_donated"] == sum(len(v) for v in said.values()) \
        == 120
    assert stats["donation_refused"] == 0
    lower = A.mt * (A.mt + 1) // 2
    assert stats["bytes_out"] == lower * 32 * 32 * 8
    assert stats["commits_home_unknown"] == 0
    donated = np.tril(A.to_array())

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(PTGTaskpool, "_donate_rule", lambda self, pc: None)
        tp, A, _S = _dpotrf(256, 32)
        said, stats = _run(tp)
    assert set(said.values()) == {None}
    assert stats["tile_args_donated"] == 0
    assert stats["commits_donate_unknown"] == 120
    assert stats["bytes_out"] == lower * 32 * 32 * 8
    assert np.array_equal(donated, np.tril(A.to_array()))
    np.testing.assert_allclose(donated @ donated.T, S, rtol=1e-9, atol=1e-9)


def case_four_ranks():
    """Four in-process ranks, each on its own device: a peer may hold an
    array uncopied, the device donates nothing there and the rule is not
    worked out (``_tpu_donate`` None); the factor is right."""
    from parsec_tpu.datadist import TwoDimBlockCyclic

    n, nb = 128, 16
    mats, saids = {}, {}
    S = None

    def build(rank, ctx):
        nonlocal S
        tp, A, S = _dpotrf(n, nb, TwoDimBlockCyclic(
            n, n, nb, nb, p=2, q=2, myrank=rank, name="A"))
        mats[rank], saids[rank] = A, _said(tp)
        return tp

    ctxs = run_ranks(4, build, timeout=180)
    out = np.zeros((n, n))
    for c in ctxs:
        A, stats = mats[c.rank], _tpu_of(c).stats
        assert set(saids[c.rank].values()) == {None}
        assert stats["tile_args_donated"] == 0
        assert stats["donation_refused"] == 0
        assert stats["commits_donate_unknown"] == stats["executed_tasks"] > 0
        for (i, j) in A.local_tiles():
            if i >= j:
                out[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb] = \
                    _value(A, i, j)
    np.testing.assert_allclose(np.tril(out), np.linalg.cholesky(S),
                               rtol=1e-9, atol=1e-9)


CASES = {f.__name__[5:]: f for f in (
    case_dpotrf_rule, case_sole_reader_of_an_empty_range,
    case_second_reader_is_read_only, case_range_of_two_readers,
    case_collection_read_from_memory, case_two_names_of_one_collection,
    case_dynamic_guard, case_version_landed_in_another_tile,
    case_forwarded_version_and_new_tile, case_context_nt8_bitwise,
    case_four_ranks)}


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_ptg_task_says_which_inputs_are_its_alone(case):
    CASES[case]()
