"""A wave program writes a read-write tile where it stands.

A task's read-write flow whose INPUT version has no other consumer
(``Task._tpu_donate``: the attach plan's ``_donations``, a DTD
insertion's exclusive writer) is donated to the program that runs the
task, where the staging walk finds nobody else holding the array
(``TpuDevice._not_sole``).  Held here, on the CPU backend at tiny sizes:
the factors are the functional path's bit for bit; the counter reads what
the captured graph predicts; a second reader of the version, a copy home
queued or on its way, a pin that is somebody else's, several ranks — each
leaves the tile un-donated and the result right; a donated program that
raises fails its pool and is not retried; the old array is deleted and
nothing of the device module still holds it.
"""

import collections
import gc
import json
import os
import threading
import weakref

import numpy as np
import pytest

from parsec_tpu import Context, DEV_TPU, native
from parsec_tpu.core.lifecycle import AccessMode
from parsec_tpu.core.task import Chore, TaskClass
from parsec_tpu.data import data_create
from parsec_tpu.datadist import TiledMatrix
from parsec_tpu.device.tpu import TpuDevice
from parsec_tpu.device.value_args import FlowPlan
from parsec_tpu.dsl import DTDTaskpool, attach_plan
from parsec_tpu.dsl.graph import capture
from parsec_tpu.dsl.native_exec import NativeExecutor, _NativeDeviceTask
from parsec_tpu.dsl.ptg import PTG
from parsec_tpu.ops import cholesky_dtd, cholesky_ptg, mle
from parsec_tpu.ops.qr import qr_ptg

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="needs the native core")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NB = 16
INOUT, IN, OUT = AccessMode.INOUT, AccessMode.IN, AccessMode.OUT
F32 = np.dtype(np.float32)


@pytest.fixture(autouse=True)
def _fresh_plans():
    attach_plan.clear()
    yield
    attach_plan.clear()


@pytest.fixture
def ctx():
    c = Context(nb_cores=1)
    yield c
    c.fini()


def tpu_dev(ctx):
    return next(d for d in ctx.devices if d.device_type == DEV_TPU)


# -- the pump: bit for bit, and the counts the graph predicts ----------------

def _spd(nt, seed=0):
    n = nt * NB
    a = np.random.default_rng(seed).standard_normal((n, n))
    return (a @ a.T + n * np.eye(n)).astype(np.float32)


def _general(nt, seed=1):
    n = nt * NB
    return np.random.default_rng(seed).uniform(-0.5, 0.5, (n, n)) \
        .astype(np.float32)


def _tiled(a):
    n = a.shape[0]
    return TiledMatrix(n, n, NB, NB, name="A",
                       dtype=np.float32).from_array(a.copy())


def _dpotrf(a):
    A = _tiled(a)
    return cholesky_ptg(use_tpu=True, use_cpu=False).taskpool(
        NT=A.mt, A=A), A.to_array


def _geqrf(a):
    A = _tiled(a)
    return qr_ptg(use_tpu=True, use_cpu=False).taskpool(
        NT=A.mt, A=A, TILE_SHAPE=(NB, NB), TILE_DTYPE=np.float32,
        QSHAPE2=(np.float32, (2 * NB, 2 * NB))), A.to_array


def _likelihood(_a):
    """One mixed-precision likelihood evaluation (``ops/mle.py``) at
    n = 512, nb = 64, two float32 diagonals: the factor as it is stored,
    y, and the two sums."""
    from benchmark.reference import smle_matern_rows as ref

    with open(os.path.join(
            ROOT, "benchmark/configs/smle_matern_mp_nb2048_1chip.json")) as f:
        config = json.load(f)
    n, nb, band = 512, 64, 2
    p = ref.make_problem(2147483999, dict(config, n=n, nb=nb, band_f32=band),
                         None, None)
    ref.prepare(p)
    cols = mle.mle_collections(n, nb, band, p["x"], p["z"], p["theta"](0))

    def result():
        A = cols["A"]
        tiles = [np.asarray(A.data_of(*k).newest_copy().payload, np.float32)
                 for k in sorted(A.tiles())]
        return np.concatenate(
            [t.ravel() for t in tiles] + [cols["Y"].to_array().ravel(),
                                          np.array(mle.loglik_parts(
                                              cols["SC"]), np.float64)])
    return mle.mle_taskpool(**cols, band_f32=band), result


def _pump(tp, functional=False):
    """One solve through the pump; ``functional``: the device names no
    donated flow (the parent's programs).  Returns its counters."""
    ex = NativeExecutor(tp, native_device=True)
    dev = ex.device
    if functional:
        dev._may_donate = False
    assert ex.run() == len(ex.graph.nodes)
    ex.close()
    return dict(dev.stats)


def _predicted(tp):
    """What the captured graph says a solve donates, worked out apart
    from ``attach_plan._donations``: a task's read-write flow counts when
    its source (a producer's written flow, or the collection's tile) has
    this one reader and the producer does not send that version home."""
    g = capture(tp)
    classes = tp.ptg.classes

    def mode(cname, fname):
        return int(next(f for f in classes[cname].flows
                        if f.name == fname).mode)
    first = collections.Counter(
        src for node in g.nodes.values()
        for src in node.flow_sources.values()
        if src is not None and src[0] == "data")
    n = 0
    for tid, node in g.nodes.items():
        for f in classes[tid[0]].flows:
            src = node.flow_sources.get(f.name)
            if int(f.mode) & int(INOUT) != int(INOUT) or src is None \
                    or src[0] == "new":
                continue
            if src[0] == "data":
                n += first[src] == 1
                continue
            _, ptid, pflow = src
            prod = g.nodes[ptid]
            edges = [e for e in prod.out_edges if e[0] == pflow]
            rewritten = any(mode(s[0], sf) & int(OUT)
                            for (_pf, s, sf) in edges)
            home = any(w[0] == pflow for w in prod.write_backs) \
                and not rewritten
            n += len(edges) == 1 and not home \
                and bool(mode(ptid[0], pflow) & int(OUT))
    return n


@pytest.mark.parametrize("make,matrix,outputs,new", [
    (_dpotrf, _spd, 120, 0),
    # geqrt 8 x 2 + tsqrt 28 x 3 + unmqr 28 + tsmqr 140 x 2 outputs, of
    # them the 8 + 28 Q blocks NEW
    (_geqrf, _general, 408, 36),
    (_likelihood, lambda nt: None, None, None),
], ids=["dpotrf_nt8", "geqrf_nt8", "likelihood_nt8"])
def test_the_donated_factor_is_the_functional_one_bit_for_bit(
        make, matrix, outputs, new):
    a = matrix(8)
    tp, result = make(a)
    want = _predicted(tp)
    stats = _pump(tp)
    donated = result()
    assert stats["tile_args_donated"] == want > 0
    if outputs is not None:
        assert want == outputs - new
    assert stats["donation_refused"] == 0
    attach_plan.clear()
    tp, result = make(a)
    plain = _pump(tp, functional=True)
    assert plain["tile_args_donated"] == plain["donation_refused"] == 0
    assert np.array_equal(donated, result())
    # the same programs, task for task: donation splits no wave and adds
    # no signature
    assert stats["wave_submits"] == plain["wave_submits"]
    assert stats["wave_signatures"] == plain["wave_signatures"]
    assert stats["executed_tasks"] == plain["executed_tasks"]


def test_the_host_tiles_a_donated_first_version_came_from_stand():
    """A tile's first version is staged from its host copy; the program
    that is given the staged array must not write through to the memory
    the host copy keeps (the CPU backend's zero-copy puts)."""
    a = _spd(4)
    tp, result = _dpotrf(a)
    A = tp.constants["A"]
    hosts = {k: A.data_of(*k).get_copy(0).payload for k in A.tiles()}
    before = {k: np.array(v) for k, v in hosts.items()}
    ex = NativeExecutor(tp, native_device=True)
    assert ex.run() == len(ex.graph.nodes)
    assert ex.device.stats["tile_args_donated"] == 20
    ex.close()
    # (the factor comes home as NEW host values: the first versions'
    # arrays are nobody's to write)
    for k, v in hosts.items():
        assert np.array_equal(v, before[k]), k
    L = np.tril(result())
    np.testing.assert_allclose(L @ L.T, a, rtol=2e-5, atol=2e-3)


# -- a planted graph: a second reader of the version -------------------------

def _planted_ptg():
    """P writes a tile; R reads that version and W rewrites it: W is not
    its only consumer.  (R is ordered before W by a control flow, as a
    PTG over tiles has to order a reader before whoever overwrites what
    it reads: a tile has ONE current version on a device.)"""
    ptg = PTG("planted")
    p = ptg.task_class("P", k="0 .. N-1")
    p.affinity("A(k, 0)")
    p.flow("X", INOUT, "<- A(k, 0)", "-> X W(k)", "-> X R(k)")
    p.body(tpu=lambda X, **_: X + 1.0)
    w = ptg.task_class("W", k="0 .. N-1")
    w.affinity("A(k, 0)")
    w.flow("X", INOUT, "<- X P(k)", "-> A(k, 0)")
    w.ctl("after", "<- read R(k)")
    w.body(tpu=lambda X, **_: X * 2.0)
    r = ptg.task_class("R", k="0 .. N-1")
    r.affinity("B(k, 0)")
    r.flow("X", IN, "<- X P(k)")
    r.flow("Y", INOUT, "<- B(k, 0)", "-> B(k, 0)")
    r.ctl("read", "-> after W(k)")
    r.body(tpu=lambda X, Y, **_: Y + X)
    return ptg


def test_a_version_with_a_second_reader_is_not_donated():
    n = 6
    a = np.arange(n * NB * NB, dtype=np.float32).reshape(n * NB, NB)
    b = np.ones((n * NB, NB), np.float32)
    A = TiledMatrix(n * NB, NB, NB, NB, name="A",
                    dtype=np.float32).from_array(a.copy())
    B = TiledMatrix(n * NB, NB, NB, NB, name="B",
                    dtype=np.float32).from_array(b.copy())
    tp = _planted_ptg().taskpool(N=n, A=A, B=B)
    ex = NativeExecutor(tp, native_device=True)
    donate = {(t.task_class.name, t._tpu_donate)
              for t in ex._pump_index.values()}
    # P's input is the collection's tile, read by nobody else; W's is
    # P's output, which R reads too; R's Y is B's tile
    assert donate == {("P", (0,)), ("W", ()), ("R", (1,))}
    assert ex.run() == 3 * n
    stats = dict(ex.device.stats)
    ex.close()
    assert stats["tile_args_donated"] == 2 * n
    assert stats["donation_refused"] == 0
    assert np.array_equal(A.to_array(), (a + 1.0) * 2.0)
    assert np.array_equal(B.to_array(), b + (a + 1.0))


# -- the staging walk's own checks, on hand-made tasks -----------------------

class _Pool:
    """What the device module reads of a task's pool."""

    taskpool_id = 0
    name = "stub"
    context = None
    next_use = ()

    def __init__(self):
        self.failed = False
        self.fail_reason = None

    def _force_fail(self):
        was, self.failed = self.failed, True
        return not was

    def task_done(self, t=None):
        pass


def _inc(x):
    return x + 1.0


def _wave(n, donate=(0,), body=_inc, home=()):
    """``n`` ready tasks of one class, each rewriting a tile of its own
    that is resident on nobody's device yet."""
    pool, tclass = _Pool(), TaskClass("inc")
    chore = Chore(DEV_TPU, hook=lambda es, t: None)
    chore.body_fn = body
    tasks = []
    for i in range(n):
        t = _NativeDeviceTask(pool, tclass, (i,), 0)
        t.selected_chore = chore
        t.body_args = [("data", data_create(
            ("don", i), payload=np.full((8, 8), float(i), np.float32)),
            INOUT)]
        t._tpu_donate, t._tpu_home = donate, home
        t.on_complete = lambda task: None
        tasks.append(t)
    return pool, tasks


def _again(tasks, donate=(0,)):
    """The same tiles under new ready tasks (a task goes out once)."""
    out = []
    for t in tasks:
        n = _NativeDeviceTask(t.taskpool, t.task_class, t.locals, 0)
        n.selected_chore = t.selected_chore
        n.body_args = list(t.body_args)
        n._tpu_donate, n._tpu_home = donate, t._tpu_home
        n.on_complete = t.on_complete
        out.append(n)
    return out


def _arrays(dev, tasks):
    return [t.body_args[0][1].get_copy(dev.data_index).payload
            for t in tasks]


def _values(dev, tasks):
    return [float(np.asarray(a)[0, 0]) for a in _arrays(dev, tasks)]


def test_the_old_array_is_deleted_and_nobody_holds_it(ctx):
    dev = tpu_dev(ctx)
    pool, first = _wave(4)
    dev._submit_wave(first, None, complete=False)
    # the first versions came from the host: staged, donated, rewritten
    assert dev.stats["tile_args_donated"] == 4
    old = _arrays(dev, first)
    refs = [weakref.ref(a) for a in old]
    dev._submit_wave(_again(first), None, complete=False)
    assert dev.stats["tile_args_donated"] == 8
    assert dev.stats["donation_refused"] == 0
    assert all(a.is_deleted() for a in old)
    assert not any(a.is_deleted() for a in _arrays(dev, first))
    assert _values(dev, first) == [2.0, 3.0, 4.0, 5.0]
    del old
    gc.collect()
    # no residency entry, copy, staged list or cache entry kept the array
    assert [r() for r in refs] == [None] * 4
    # one entry, one set of donated positions, and it is bound
    calls = dev.stats["calls_bound"]
    dev._submit_wave(_again(first), None, complete=False)
    assert dev.stats["calls_bound"] == calls + 1
    assert not pool.failed


def _queued_with_the_committer(dev, tasks):
    """The tiles' newest versions are in a drain that does not end: the
    committer's write-back blocks until it is let."""
    let, begun = threading.Event(), threading.Event()
    real = dev._wb.writeback_batch

    def slow(*a, **kw):
        begun.set()
        assert let.wait(timeout=60)
        return real(*a, **kw)
    dev._wb.writeback_batch = slow
    com = dev._wb_committer()
    com.enqueue_all([t.body_args[0][1] for t in tasks], last=True)
    assert begun.wait(timeout=60)

    def undo():
        let.set()
        dev.flush()
        dev._wb.writeback_batch = real
    return undo


def _an_evictions_victim(dev, tasks):
    """The tiles are victims of the lane's eviction, on their way home
    with the residency lock free; the walk takes them back."""
    res = dev._res
    let, begun = threading.Event(), threading.Event()
    real = res._writeback

    def slow(victims):
        begun.set()
        assert let.wait(timeout=60)
        return real(victims)
    res._writeback = slow
    budget = res.budget
    res.budget = res.used
    lane = threading.Thread(target=res.make_room, args=(res.used,),
                            daemon=True)
    lane.start()
    assert begun.wait(timeout=60)
    res.budget = budget
    assert res.going_home == {t.body_args[0][1].data_id for t in tasks}

    def undo():
        let.set()
        lane.join(timeout=60)
        assert not lane.is_alive() and not res.going_home
        res._writeback = real
        # taken back by the walk: none of them left
        assert dev.stats["evict_cancelled"] == len(tasks)
        assert dev.stats["evictions"] == 0
    return undo


def _pinned_by_somebody_else(dev, tasks):
    datas = [t.body_args[0][1] for t in tasks]
    with dev._res.lock:
        for d in datas:
            dev._res.pin(d)
    return lambda: dev._res.unpin(datas)


def _the_host_copy_is_the_same_array(dev, tasks):
    for t in tasks:
        d = t.body_args[0][1]
        d.attach_copy(0, d.get_copy(dev.data_index).payload)
    return lambda: None


@pytest.mark.parametrize("held", [
    _queued_with_the_committer, _an_evictions_victim,
    _pinned_by_somebody_else, _the_host_copy_is_the_same_array],
    ids=["copy_home_queued", "evictions_victim_taken_back",
         "pinned_by_another", "another_copy_holds_the_array"])
def test_a_tile_somebody_else_holds_is_passed_undonated(ctx, held):
    dev = tpu_dev(ctx)
    pool, first = _wave(4)
    dev._submit_wave(first, None, complete=False)
    assert dev.stats["tile_args_donated"] == 4
    old = _arrays(dev, first)
    undo = held(dev, first)
    try:
        dev._submit_wave(_again(first), None, complete=False)
        # the tasks went out under the functional program: nothing was
        # consumed, everything was computed
        assert dev.stats["donation_refused"] == 4
        assert dev.stats["tile_args_donated"] == 4
        assert not any(a.is_deleted() for a in old)
        assert [float(np.asarray(a)[0, 0]) for a in old] \
            == [1.0, 2.0, 3.0, 4.0]
        assert _values(dev, first) == [2.0, 3.0, 4.0, 5.0]
    finally:
        undo()
    assert not pool.failed
    # and once nobody holds them, the same tiles are donated again
    dev._submit_wave(_again(first), None, complete=False)
    assert dev.stats["donation_refused"] == 4
    assert dev.stats["tile_args_donated"] == 8
    assert _values(dev, first) == [3.0, 4.0, 5.0, 6.0]


def test_one_held_tile_takes_its_task_out_of_the_chunk_alone(ctx):
    """Eight tasks, the tile of one pinned by somebody else: seven go out
    donated (4 + 2 + 1), the one under the functional program."""
    dev = tpu_dev(ctx)
    pool, first = _wave(8)
    dev._submit_wave(first, None, complete=False)
    data = first[5].body_args[0][1]
    with dev._res.lock:
        dev._res.pin(data)
    programs = dev.stats["wave_submits"]
    dev._submit_wave(_again(first), None, complete=False)
    dev._res.unpin([data])
    assert dev.stats["donation_refused"] == 1
    assert dev.stats["tile_args_donated"] == 8 + 7
    assert dev.stats["wave_submits"] == programs + 4
    assert _values(dev, first) == [float(i) + 2.0 for i in range(8)]
    assert not pool.failed


def test_the_same_tile_twice_in_a_program_is_not_donated(ctx):
    dev = tpu_dev(ctx)
    pool, tasks = _wave(2, body=lambda x, y: x + y)
    shared = tasks[0].body_args[0][1]
    for t in tasks:
        t.body_args = [t.body_args[0], ("data", shared, IN)]
    dev._submit_wave(tasks, None, complete=False)
    # task 0 rewrites the tile that both read: it goes out alone,
    # functional; task 1's own tile is read by nobody else
    assert dev.stats["donation_refused"] == 1
    assert dev.stats["tile_args_donated"] == 1
    assert _values(dev, tasks) == [0.0, 1.0]
    assert not pool.failed


def test_a_donated_program_that_raises_fails_its_pool_unretried(ctx):
    dev = tpu_dev(ctx)
    pool, first = _wave(4)
    dev._submit_wave(first, None, complete=False)
    tasks = _again(first)
    real, alone = dev._dispatch, []

    def broken(local_key, entry, flat):
        raise RuntimeError("the program died under the call")
    dev._dispatch = broken
    dev._submit_one = lambda t, *a, **kw: alone.append(t)
    try:
        dev._submit_units([("wave", tasks)], None, False)
    finally:
        dev._dispatch = real
        del dev._submit_one
    assert pool.failed and "donated tiles" in pool.fail_reason
    assert alone == []                      # no task was run again
    assert dev.stats["wave_fallbacks"] == 0
    assert dev.stats["submit_retries"] == 0
    assert all(t._tpu_completed and t._tpu_effects for t in tasks)
    # a chunk that donates nothing still falls back task by task
    pool2, plain = _wave(4, donate=None)
    dev._dispatch = broken
    dev._submit_one = lambda t, *a, **kw: alone.append(t)
    try:
        dev._submit_units([("wave", plain)], None, False)
    finally:
        dev._dispatch = real
        del dev._submit_one
    assert alone == plain and dev.stats["wave_fallbacks"] == 1
    assert not pool2.failed


def test_a_task_that_goes_out_alone_donates_as_a_wave_does(ctx):
    dev = tpu_dev(ctx)
    pool, (task,) = _wave(1)
    dev._submit_one(task, None, complete=False)
    old = _arrays(dev, [task])
    (again,) = _again([task])
    dev._submit_one(again, None, complete=False)
    assert dev.stats["tile_args_donated"] == 2
    assert old[0].is_deleted() and _values(dev, [task]) == [2.0]
    assert dev.stats["task_commits"] == 2 and not pool.failed


@pytest.mark.parametrize("why", ["several_ranks", "deferred_completion",
                                 "nobody_said"])
def test_who_donates_nothing(ctx, why):
    """Several ranks (a peer may hold the array uncopied), completion
    deferred to the chip's events (a donated tile's copy would be a
    deleted array until its commit), and a task whose builder said
    nothing (``_tpu_donate`` None: a hand-built task, a PTG class whose
    dependencies read more than the class can know)."""
    from parsec_tpu.utils import mca_param

    other = None
    if why == "several_ranks":
        other = Context(nb_cores=1)
        other.nranks = 2
        dev = TpuDevice(other, 7)
    elif why == "deferred_completion":
        mca_param.params.set("device", "tpu_eager_complete", 0)
        try:
            dev = TpuDevice(ctx, 7)
        finally:
            mca_param.params.unset("device", "tpu_eager_complete")
    else:
        dev = tpu_dev(ctx)
    try:
        pool, first = _wave(4, donate=None if why == "nobody_said" else (0,))
        assert dev._signature_of(first[0])[1].donates == ()
        if why == "deferred_completion":
            return  # (its commits wait for a scheduling core's completion)
        dev._submit_wave(first, None, complete=False)
        old = _arrays(dev, first)
        dev._submit_wave(_again(first, first[0]._tpu_donate), None,
                         complete=False)
        assert dev.stats["tile_args_donated"] == 0
        assert dev.stats["donation_refused"] == 0
        assert not any(a.is_deleted() for a in old)
        assert _values(dev, first) == [2.0, 3.0, 4.0, 5.0]
        assert not pool.failed
    finally:
        if other is not None:
            other.fini()


def test_the_flow_plan_names_what_may_be_donated():
    tile = ((8, 8), F32, INOUT)
    # position 1 is read-only, position 3 a value: named or not, no
    plan = FlowPlan((tile, ((8, 8), F32, IN), tile, int), (0, 1, 2, 3))
    assert plan.donates == ((0, 0, 0), (2, 2, 1))
    assert FlowPlan((tile,), ()).donates == ()
    # nothing to stage, nothing to donate: a write-only flow, an unborn
    # scratch tile, a tile whose shape nobody says
    assert FlowPlan((((8, 8), F32, OUT),), (0,)).donates == ()
    assert FlowPlan((("unborn", (8, 8), F32, INOUT),), (0,)).donates == ()
    assert FlowPlan(((None, None, INOUT),), (0,)).donates == ()
    # "ctl" takes a position of body_args and no argument
    assert FlowPlan(("ctl", tile), (1,)).donates == ((1, 0, 0),)


def test_an_output_of_another_shape_leaves_its_input_alone(ctx):
    """A read-write flow whose body returns another dtype: XLA could
    alias the input to nothing, so it is not given away."""
    import jax.numpy as jnp

    dev = tpu_dev(ctx)
    pool, tasks = _wave(2, body=lambda x: (x + 1.0).astype(jnp.bfloat16))
    dev._submit_wave(tasks, None, complete=False)
    assert dev.stats["tile_args_donated"] == 0
    assert dev.stats["donation_refused"] == 0
    assert [np.asarray(a).dtype.name for a in _arrays(dev, tasks)] \
        == ["bfloat16"] * 2
    assert not pool.failed


# -- the other DSL: an insertion's exclusive writer --------------------------

def test_an_inserted_writer_donates_the_version_it_overwrites():
    M = _spd(8, seed=3)

    def factor(donating):
        c = Context(nb_cores=1)
        try:
            dev = tpu_dev(c)
            dev._may_donate = donating
            A = _tiled(M)
            tp = DTDTaskpool(c)
            cholesky_dtd(tp, A, use_tpu=True, use_cpu=False)
            assert tp.wait(timeout=300)
            tp.flush_all(A)
            tp.close()
            return np.tril(A.to_array()), dict(dev.stats), tp.counters()
        finally:
            c.fini()
    L, stats, counters = factor(True)
    # every one of the 120 tasks rewrites ONE tile, and an insertion
    # orders a writer behind every reader of what it overwrites
    assert stats["tile_args_donated"] == 120
    assert stats["donation_refused"] == 0 and counters["dtd_renames"] == 0
    L0, plain, _ = factor(False)
    assert plain["tile_args_donated"] == 0
    assert np.array_equal(L, L0)
