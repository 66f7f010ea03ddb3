"""A PTG task on the ``Context`` route says which of its outputs are
last versions (``Task._tpu_home``, from its own output dependencies:
``PTGTaskpool._home_rule``): a version nobody overwrites is the tile's
last, only those reach the device module's write-back committer, and
every other stays on the device, dirty, until somebody reads it.  A
count on the CPU backend, a case each; never a time."""

import numpy as np
import pytest

from parsec_tpu import Context
from parsec_tpu.core.lifecycle import DEV_TPU
from parsec_tpu.data import LocalCollection
from parsec_tpu.dsl.ptg import INOUT, PTG
from parsec_tpu.utils import mca_param

from test_multirank import run_ranks

N = 16
TILE = N * N * 8


def _tpu_of(ctx):
    for d in ctx.devices:
        if d.device_type == DEV_TPU:
            return d
    pytest.skip("no jax device available")


def _ones(name="A", **kw):
    return LocalCollection(name, shape=(N, N),
                           init=lambda k: np.ones((N, N)), **kw)


def _host(dc, *key):
    """The HOST copy of a tile as it stands: what a reader that goes
    past ``newest_copy()`` would see."""
    return np.asarray(dc.data_of(*key).get_copy(0).payload)


def _run(tp, flush=True):
    """One pool through ``Context.add_taskpool``; the device's counters
    once everything it owes is home."""
    c = Context(nb_cores=2)
    try:
        dev = _tpu_of(c)
        c.add_taskpool(tp)
        assert tp.wait(timeout=120)
        if flush:
            dev.flush()
    finally:
        c.fini()
    return dev.stats


def _homes(tp, name):
    """``_tpu_home`` of every task of a class, by its key."""
    pc = tp.ptg.classes[name]
    always, guarded = tp._home_rule(pc)
    return {loc: tp._last_versions(always, guarded,
                                   pc.env_of(loc, tp.constants))
            if guarded else always
            for loc in pc.param_space(tp.constants)}


def _dpotrf(n, nb, dtype=np.float64):
    from parsec_tpu.datadist import TiledMatrix
    from parsec_tpu.ops.cholesky import cholesky_ptg

    M = np.random.default_rng(5).standard_normal((n, n))
    S = M @ M.T + n * np.eye(n)
    A = TiledMatrix(n, n, nb, nb, name="A", dtype=dtype).from_array(S)
    tp = cholesky_ptg(use_tpu=True, use_cpu=False).taskpool(NT=A.mt, A=A)
    return tp, A, S


def _chain(links, *out_deps):
    """``step(k)`` rewrites ``A(0)`` ``links`` times, each link adding
    one; ``out_deps``: how a link hands the tile on."""
    ptg = PTG("chain")
    t = ptg.task_class("step", k=f"0 .. {links - 1}")
    t.affinity("A(0)")
    t.flow("T", INOUT, "<- (k == 0) ? A(0) : T step(k-1)", *out_deps)
    t.body(tpu=lambda T, k: T + 1.0)
    return ptg


def case_watermark_1mb():
    """Tiles of 128 KiB against a watermark of 1 MB: the committer
    drains several times inside the solve, so its dedup saves few of the
    superseded versions — and none is handed to it any more: the lower
    matrix goes home exactly once, every copy started at hand-over."""
    mca_param.params.set("runtime", "wb_window_mb", 1)
    try:
        tp, A, S = _dpotrf(768, 128)
        stats = _run(tp)
    finally:
        mca_param.params.unset("runtime", "wb_window_mb")
    lower = A.mt * (A.mt + 1) // 2
    assert lower * 128 * 128 * 8 > 2 << 20
    assert stats["bytes_out"] == lower * 128 * 128 * 8
    assert stats["wb_started_early"] == stats["wb_early_hits"] == lower
    L = np.tril(A.to_array())
    np.testing.assert_allclose(L @ L.T, S, rtol=1e-9, atol=1e-9)


def case_every_task_knows():
    """The dpotrf's four classes: potrf and trsm are home whatever the
    key, syrk and gemm hand their tile to a writer on either branch; no
    task is committed with the question open."""
    tp, A, _S = _dpotrf(96, 24)
    classes = tp.ptg.classes
    assert tp._home_rule(classes["potrf"]) == ((0,), ())
    assert tp._home_rule(classes["trsm"]) == ((1,), ())
    for name in ("syrk", "gemm"):
        always, guarded = tp._home_rule(classes[name])
        assert always == () and len(guarded) == 1
        assert set(_homes(tp, name).values()) == {()}
    stats = _run(tp)
    assert stats["commits_home_unknown"] == 0
    assert stats["executed_tasks"] == 20


def case_no_terminal_dep():
    """A PTG that never spells ``-> A(k)``: nobody overwrites what its
    tasks write, so every version is a last one and every host tile is
    current after ``flush()``."""
    dc = _ones()
    ptg = PTG("bare")
    t = ptg.task_class("t", k="0 .. 3")
    t.affinity("A(k)")
    t.flow("X", INOUT, "<- A(k)")
    t.body(tpu=lambda X, k: X + k + 1.0)
    tp = ptg.taskpool(A=dc)
    assert tp._home_rule(ptg.classes["t"]) == ((0,), ())
    stats = _run(tp)
    assert stats["commits_home_unknown"] == 0
    assert stats["bytes_out"] == 4 * TILE
    for k in range(4):
        np.testing.assert_array_equal(_host(dc, k), np.full((N, N), k + 2.0))


def case_guarded_end_of_a_chain():
    """``-> (k == NT-1) ? A(0) : T step(k+1)``: the guard decides, a
    task at a time; exactly the last link goes home."""
    dc = _ones()
    tp = _chain(5, "-> (k == NT-1) ? A(0) : T step(k+1)").taskpool(
        A=dc, NT=5)
    assert _homes(tp, "step") == {(k,): (0,) if k == 4 else ()
                                  for k in range(5)}
    stats = _run(tp)
    assert stats["commits_home_unknown"] == 0
    assert stats["bytes_out"] == TILE
    assert stats["wb_started_early"] == stats["wb_early_hits"] == 1
    np.testing.assert_array_equal(_host(dc, 0), np.full((N, N), 6.0))


def case_collection_on_every_link():
    """A chain that names its collection on EVERY link (the likelihood
    PTG's sums): a link that a writer takes on is still not the last,
    one version goes home."""
    dc = _ones()
    tp = _chain(5, "-> A(0)", "-> (k < 4) ? T step(k+1)").taskpool(A=dc)
    assert _homes(tp, "step") == {(k,): (0,) if k == 4 else ()
                                  for k in range(5)}
    stats = _run(tp)
    assert stats["bytes_out"] == TILE
    assert stats["wb_started_early"] == 1
    np.testing.assert_array_equal(_host(dc, 0), np.full((N, N), 6.0))


def _ranged(nb):
    """``a(0)`` hands its tile to the writers ``b(1 .. NB)``."""
    dc = _ones()
    ptg = PTG("ranged")
    a = ptg.task_class("a", k="0 .. 0")
    a.affinity("A(0)")
    a.flow("T", INOUT, "<- A(0)", "-> T b(1 .. NB)")
    a.body(tpu=lambda T, k: T + 1.0)
    b = ptg.task_class("b", j="1 .. NB")
    b.affinity("A(0)")
    b.flow("T", INOUT, "<- T a(0)", "-> A(0)")
    b.body(tpu=lambda T, j: T * 3.0)
    return ptg.taskpool(A=dc, NB=nb), dc


def case_empty_range_of_writers():
    """A ranged dependency on a writer whose range is empty hands the
    tile to nobody: the version is the last."""
    tp, dc = _ranged(0)
    assert _homes(tp, "a") == {(0,): (0,)}
    stats = _run(tp)
    assert stats["bytes_out"] == TILE
    np.testing.assert_array_equal(_host(dc, 0), np.full((N, N), 2.0))


def case_ranged_writer():
    """... and with somebody in the range it is not: the writer's is."""
    tp, dc = _ranged(1)
    assert _homes(tp, "a") == {(0,): ()}
    assert _homes(tp, "b") == {(1,): (0,)}
    stats = _run(tp)
    assert stats["bytes_out"] == TILE
    np.testing.assert_array_equal(_host(dc, 0), np.full((N, N), 6.0))


def case_fused_pool():
    """A fused supertask is not a task of any one class: it keeps None,
    and its outputs go to the committer version by version as before."""
    mca_param.params.set("runtime", "fusion", "auto")
    try:
        tp, A, S = _dpotrf(128, 32)
        stats = _run(tp)
    finally:
        mca_param.params.unset("runtime", "fusion")
    assert tp._fusion is not None and stats["fused_submits"] > 0
    assert stats["commits_home_unknown"] > 0
    L = np.tril(A.to_array())
    np.testing.assert_allclose(L @ L.T, S, rtol=1e-9, atol=1e-9)


def case_dynamic_guard():
    """A guard that reads an array of the pool (``route[k]``: state a
    body may write) has one value at ``prepare_input`` and perhaps
    another at release: the class cannot know and says None."""
    dc = _ones()
    route = np.ones(5, dtype=int)
    tp = _chain(5, "-> (k < 4 && route[k] == 1) ? T step(k+1)",
                "-> (k == 4 || route[k] != 1) ? A(0)").taskpool(
        A=dc, route=route)
    assert tp._home_rule(tp.ptg.classes["step"]) == (None, ())
    stats = _run(tp)
    assert stats["commits_home_unknown"] == 5
    assert stats["wb_started_early"] == 0
    np.testing.assert_array_equal(_host(dc, 0), np.full((N, N), 6.0))


def case_two_ranks():
    """Two in-process ranks, each on its own device: what a rank's
    potrf / trsm write goes home once, there; a version whose next
    writer is on the other rank does not."""
    from parsec_tpu.datadist import TwoDimBlockCyclic
    from parsec_tpu.ops import cholesky_ptg

    n, nb = 96, 16
    M = np.random.default_rng(9).standard_normal((n, n))
    S = M @ M.T + n * np.eye(n)
    mats = {}

    def build(rank, ctx):
        A = TwoDimBlockCyclic(n, n, nb, nb, p=1, q=2, myrank=rank, name="A")
        mats[rank] = A.from_array(S)
        return cholesky_ptg(use_tpu=True, use_cpu=False).taskpool(
            NT=A.mt, A=A)

    ctxs = run_ranks(2, build, timeout=180)
    out = np.zeros((n, n))
    for c in ctxs:
        A, stats = mats[c.rank], _tpu_of(c).stats
        mine = [(i, j) for (i, j) in A.local_tiles() if i >= j]
        assert stats["commits_home_unknown"] == 0
        assert stats["bytes_out"] == len(mine) * nb * nb * 8
        assert stats["wb_started_early"] == len(mine)
        for (i, j) in mine:
            out[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb] = _host(A, i, j)
    np.testing.assert_allclose(np.tril(out), np.linalg.cholesky(S),
                               rtol=1e-9, atol=1e-9)


def case_remote_writer():
    """A chain whose links alternate between two ranks: every link but
    the last is overwritten on the OTHER rank, stays where it was
    written and goes over the wire from there; the last one's write-back
    reaches the tile's owner."""
    links = 6
    colls = {}

    def build(rank, ctx):
        dc = colls[rank] = _ones("A", nodes=2, myrank=rank)
        dc.rank_of = lambda *key: key[0] % 2
        ptg = PTG("pingpong")
        t = ptg.task_class("step", k=f"0 .. {links - 1}")
        t.affinity("A(k)")
        t.flow("T", INOUT, "<- (k == 0) ? A(0) : T step(k-1)",
               f"-> (k == {links - 1}) ? A(0) : T step(k+1)")
        t.body(tpu=lambda T, k: T + 1.0)
        return ptg.taskpool(A=dc)

    ctxs = run_ranks(2, build, timeout=120)
    stats = [_tpu_of(c).stats for c in ctxs]
    assert [s["executed_tasks"] for s in stats] == [3, 3]
    assert sum(s["commits_home_unknown"] for s in stats) == 0
    # (the last link runs on rank 1 and is the only version anybody
    # sends home; rank 0 owns the tile and gets it by write-back)
    assert [s["wb_started_early"] for s in stats] == [0, 1]
    np.testing.assert_array_equal(
        np.asarray(colls[0].data_of(0).newest_copy().payload),
        np.full((N, N), links + 1.0))


def case_cpu_reader_of_a_dirty_tile():
    """A device task leaves its version on the device, dirty (a CPU
    task will overwrite it): the CPU body pulls it on demand and sees
    the new value."""
    dc = _ones()
    seen = []
    ptg = PTG("mixed")
    d = ptg.task_class("d", k="0 .. 2")
    d.affinity("A(k)")
    d.flow("X", INOUT, "<- A(k)", "-> X c(k)")
    d.body(tpu=lambda X, k: X + 4.0)
    c = ptg.task_class("c", k="0 .. 2")
    c.affinity("A(k)")
    c.flow("X", INOUT, "<- X d(k)", "-> A(k)")

    def on_cpu(X, k):
        seen.append(float(X[0, 0]))
        X *= 2.0

    c.body(cpu=on_cpu)
    tp = ptg.taskpool(A=dc)
    assert _homes(tp, "d") == {(k,): () for k in range(3)}
    stats = _run(tp)
    assert stats["commits_home_unknown"] == 0
    assert stats["wb_started_early"] == 0
    assert seen == [5.0] * 3
    for k in range(3):
        np.testing.assert_array_equal(_host(dc, k), np.full((N, N), 10.0))


CASES = {f.__name__[5:]: f for f in (
    case_watermark_1mb, case_every_task_knows, case_no_terminal_dep,
    case_guarded_end_of_a_chain, case_collection_on_every_link,
    case_empty_range_of_writers, case_ranged_writer, case_fused_pool,
    case_dynamic_guard, case_two_ranks, case_remote_writer,
    case_cpu_reader_of_a_dirty_tile)}


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_ptg_task_says_which_outputs_are_last_versions(case):
    CASES[case]()
