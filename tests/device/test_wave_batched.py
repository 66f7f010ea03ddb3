"""A wave of a body that says it is a sequential loop is ONE batched kernel.

The three Householder kill bodies of ``ops/qr.py`` name the form that
runs a wave of them in lockstep (``_batched``); ``TpuDevice._launch``
builds the wave's program around that form where it would have unrolled
the tasks' bodies, and unrolls every other body as before.  Held here, on
the CPU backend at small sizes, on hand-made ready tasks that carry the
PTG's own wrapped bodies: a wave gives, task for task, what the same
tasks give one at a time, with donation on; the outputs come back in the
unrolled program's order and the span notes the same ``don``; an
undeclared body's program has the key and the HLO it always had; the
batched and the unrolled program of one body never share a key; the
counters say what went through; a form that raises is loud; and the tile
a kill leaves as zeros is landed at home without a copy from the chip.
"""

import numpy as np
import pytest

import jax

from parsec_tpu import Context, DEV_TPU, native
from parsec_tpu.core.lifecycle import AccessMode
from parsec_tpu.core.task import Chore, TaskClass
from parsec_tpu.data import data_create
from parsec_tpu.datadist import TiledMatrix
from parsec_tpu.device import scratch
from parsec_tpu.dsl.native_exec import _NativeDeviceTask
from parsec_tpu.ops import qr
from parsec_tpu.profiling import pins

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="needs the native core")
NB = 24
INOUT = AccessMode.INOUT
KILLS = ("geqrt", "tsqrt", "ttqrt")


@pytest.fixture(autouse=True)
def _clean_pins():
    pins.clear()
    yield
    pins.clear()


@pytest.fixture
def ctx():
    c = Context(nb_cores=1)
    yield c
    c.fini()


def _wrapped_bodies():
    A = TiledMatrix(2 * NB, 2 * NB, NB, NB, name="A", dtype=np.float32)
    tp = qr.qr_ptg(use_tpu=True, use_cpu=False).taskpool(
        NT=A.nt, A=A, TILE_SHAPE=(NB, NB), TILE_DTYPE=np.float32,
        QSHAPE2=(np.float32, (2 * NB, 2 * NB)))
    return {name: next(c.body_fn for c in tc.chores
                       if c.device_type == DEV_TPU)
            for name, tc in tp._built.items()}


@pytest.fixture(scope="module")
def bodies():
    """The device bodies as the PTG wraps them (positional arguments,
    ``_batched`` forwarded), by class."""
    return _wrapped_bodies()


def tpu_dev(ctx):
    return next(d for d in ctx.devices if d.device_type == DEV_TPU)


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


def _unrolled(body):
    """The same body without its declaration."""
    def plain(*pos):
        return body(*pos)
    plain.__name__ = body.__name__
    return plain


def _tiles(cls, n, seed):
    """The tile arguments of ``n`` kills: for a ``geqrt`` a square, for
    the TS / TT kills a triangle and a square / a triangle."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        t = [rng.uniform(-0.5, 0.5, (NB, NB)).astype(np.float32)
             for _ in range(1 if cls == "geqrt" else 2)]
        if cls != "geqrt":
            t[0] = np.triu(t[0])
        if cls == "ttqrt":
            t[1] = np.triu(t[1])
        out.append(t)
    return out


def _kills(cls, body, tiles, tag, donate=True):
    """Ready tasks of one kill class over ``tiles``: the flows the PTG
    gives them (the tiles read-write, then the ``NEW`` Q block), the
    tiles' input versions theirs alone."""
    pool, tclass = _Pool(), TaskClass(cls)
    chore = Chore(DEV_TPU, hook=lambda es, t: None)
    chore.body_fn = body
    q = (NB, NB) if cls == "geqrt" else (2 * NB, 2 * NB)
    tasks = []
    for i, mine in enumerate(tiles):
        t = _NativeDeviceTask(pool, tclass, (i,), 0)
        t.selected_chore = chore
        t.body_args = [("data", data_create((tag, cls, i, k),
                                            payload=x.copy()), INOUT)
                       for k, x in enumerate(mine)]
        t.body_args.append(
            ("data", scratch.new((tag, cls, i, "Q"), q, np.float32), INOUT))
        t._tpu_donate = tuple(range(len(mine))) if donate else ()
        t._tpu_home = ()
        t.on_complete = lambda task: None
        tasks.append(t)
    return pool, tasks


def _results(dev, tasks):
    return [[np.asarray(d.get_copy(dev.data_index).payload)
             for (_k, d, _m) in t.body_args] for t in tasks]


@pytest.mark.parametrize("n", [2, 8, 64])
@pytest.mark.parametrize("cls", KILLS)
def test_a_wave_of_kills_is_the_same_kills_one_at_a_time(ctx, bodies, cls,
                                                         n):
    dev = tpu_dev(ctx)
    tiles = _tiles(cls, n, seed=n)
    pool, wave = _kills(cls, bodies[cls], tiles, "wave")
    dev._submit_wave(wave, None, complete=False)
    assert dev.stats["wave_tasks_batched"] == dev.stats["wave_tasks"] == n
    assert dev.stats["tile_args_donated"] == n * len(tiles[0])
    assert dev.stats["donation_refused"] == 0
    _p, alone = _kills(cls, bodies[cls], tiles, "alone")
    for t in alone:
        dev._submit_one(t, None, complete=False)
    assert dev.stats["wave_tasks"] == n and not pool.failed
    for mine, got, want in zip(tiles, _results(dev, wave),
                               _results(dev, alone)):
        r, q = got[0], got[-1]
        assert np.max(np.abs(r - want[0])) <= 1e-5 * np.max(np.abs(want[0]))
        assert not np.tril(r, -1).any()
        assert np.max(np.abs(q.T @ q - np.eye(len(q)))) <= 1e-5
        stack = np.vstack(mine)
        assert np.max(np.abs(q[:, :NB] @ r - stack)) <= 1e-5
        if cls != "geqrt":
            assert not got[1].any()  # the killed tile: exact zeros


def _spans(fn):
    """The ``dev:wave`` spans' notes of what ``fn`` runs."""
    seen = []

    def note(es, p):
        seen.append(dict(p))
    pins.subscribe("dev:wave_end", note)
    try:
        fn()
    finally:
        pins.unsubscribe("dev:wave_end", note)
    return seen


@pytest.mark.parametrize("cls", KILLS)
def test_the_outputs_come_back_in_the_unrolled_programs_order(ctx, bodies,
                                                              cls):
    """... and the span's ``don`` is the unrolled program's: the R and B
    outputs have their inputs' shape and dtype."""
    dev = tpu_dev(ctx)
    n = 4
    tiles = _tiles(cls, n, seed=3)
    notes, got = {}, {}
    for how, body in (("batched", bodies[cls]),
                      ("unrolled", _unrolled(bodies[cls]))):
        _pool, tasks = _kills(cls, body, tiles, how)
        notes[how], = _spans(
            lambda: dev._submit_wave(tasks, None, complete=False))
        got[how] = _results(dev, tasks)
    assert notes["batched"]["batched"] == n
    assert notes["unrolled"]["batched"] == 0
    for key in ("n", "don", "outs", "tdrop", "vdrop", "rep"):
        assert notes["batched"][key] == notes["unrolled"][key], key
    assert notes["batched"]["don"] == n * len(tiles[0])
    # task for task and flow for flow what the unrolled program committed
    for b, u in zip(got["batched"], got["unrolled"]):
        for x, y in zip(b, u):
            assert x.shape == y.shape and x.dtype == y.dtype
            assert np.max(np.abs(x - y)) <= 1e-5
    assert dev.stats["wave_tasks_batched"] == n
    assert dev.stats["wave_tasks"] == 2 * n


def _programs(dev, fn):
    """``(content key, function, donated positions)`` of the device
    programs ``fn`` makes the device build."""
    built = []
    real = dev._ccache.jit

    def jit(f, key=None, donate_argnums=()):
        built.append((key, f, tuple(donate_argnums)))
        return real(f, key=key, donate_argnums=donate_argnums)
    dev._ccache.jit = jit
    try:
        fn()
    finally:
        dev._ccache.jit = real
    return built


@pytest.mark.parametrize("cls,n", [("tsmqr", 4), ("unmqr", 2),
                                   ("tsmqr", 16)])
def test_an_undeclared_bodys_program_is_the_one_it_always_was(ctx, bodies,
                                                              cls, n):
    """The wave program of a body without ``_batched``: the content key
    as it has been spelled since PR 25, and the HLO of the tasks' bodies
    unrolled."""
    dev = tpu_dev(ctx)
    body = bodies[cls]
    assert getattr(body, "_batched", None) is None
    rng = np.random.default_rng(0)
    shapes = [(NB, NB), (NB, NB)] if cls == "unmqr" \
        else [(2 * NB, 2 * NB), (NB, NB), (NB, NB)]
    pool, tclass = _Pool(), TaskClass(cls)
    chore = Chore(DEV_TPU, hook=lambda es, t: None)
    chore.body_fn = body
    tasks = []
    for i in range(n):
        t = _NativeDeviceTask(pool, tclass, (i,), 0)
        t.selected_chore = chore
        t.body_args = [
            ("data", data_create(("und", cls, n, i, k), payload=rng.uniform(
                -1, 1, s).astype(np.float32)),
             AccessMode.IN if k == 0 else INOUT)
            for k, s in enumerate(shapes)]
        t._tpu_donate, t._tpu_home = (), ()
        t.on_complete = lambda task: None
        tasks.append(t)
    (key, fn, donate), = _programs(
        dev, lambda: dev._submit_wave(tasks, None, complete=False))
    nargs, nout = len(shapes), len(shapes) - 1
    assert key == ("wave", cls, dev._content_fp(body), nargs, nout, n)
    assert donate == () and fn.__name__ == f"_wave_{cls}"
    assert dev.stats["wave_tasks_batched"] == 0

    def parents(*flat):
        outs = []
        for t in range(n):
            o = body(*flat[t * nargs:(t + 1) * nargs])
            outs.extend(o if isinstance(o, (tuple, list)) else (o,))
        return tuple(outs)
    parents.__name__ = fn.__name__
    flat = [jax.ShapeDtypeStruct(s, np.float32)
            for _ in range(n) for s in shapes]
    assert jax.jit(fn).lower(*flat).as_text() \
        == jax.jit(parents).lower(*flat).as_text()


@pytest.mark.parametrize("cls", KILLS)
def test_the_batched_and_the_unrolled_program_never_share_a_key(
        ctx, bodies, cls, monkeypatch):
    """ONE body (the same object, the same ``_jit_key``), one class, one
    width: with its declaration and, taken off it, without."""
    dev = tpu_dev(ctx)
    n = 4
    tiles = _tiles(cls, n, seed=5)
    body = bodies[cls]
    form_fp = dev._content_fp(body._batched)
    keys = {}
    for how in ("batched", "unrolled"):
        if how == "unrolled":
            monkeypatch.delattr(body, "_batched")
        _pool, tasks = _kills(cls, body, tiles, how)
        (keys[how], fn, _don), = _programs(
            dev, lambda: dev._submit_wave(tasks, None, complete=False))
        assert fn.__name__ == f"_wave_{cls}"
    assert dev.stats["wave_tasks_batched"] == n
    assert dev.stats["wave_tasks"] == 2 * n
    assert keys["batched"] != keys["unrolled"]
    # the form's own code is in the key (another form, another program),
    # and everything else is what the two share
    assert tuple(k for k in keys["batched"] if k not in keys["unrolled"]) \
        == ("batched", form_fp)
    assert tuple(k for k in keys["batched"] if k in keys["unrolled"]) \
        == keys["unrolled"]


def test_the_next_taskpools_wave_is_a_bound_call_of_the_same_entry(ctx,
                                                                   bodies):
    """A PTG wraps body and form anew for every taskpool; the program's
    entry is found again all the same (``_jit_key``), and its call is the
    executable the first solve bound."""
    dev = tpu_dev(ctx)
    tiles = _tiles("tsqrt", 4, seed=6)
    _p, first = _kills("tsqrt", bodies["tsqrt"], tiles, "tp1")
    dev._submit_wave(first, None, complete=False)
    entries, signed = len(dev._jit_cache), dev.stats["calls_signed"]
    again = _wrapped_bodies()["tsqrt"]
    assert again is not bodies["tsqrt"]
    assert again._batched is not bodies["tsqrt"]._batched
    _p, second = _kills("tsqrt", again, tiles, "tp2")
    built = _programs(
        dev, lambda: dev._submit_wave(second, None, complete=False))
    assert built == [] and len(dev._jit_cache) == entries
    assert dev.stats["calls_signed"] == signed
    assert dev.stats["calls_bound"] == 1
    assert dev.stats["wave_tasks_batched"] == 8


def test_a_kill_that_goes_out_alone_runs_the_same_kernel(ctx, bodies):
    """Its program is the body's own (no wave, nothing counted as
    batched), and the body is the form over a stack of one: bit for bit
    what the form gives for that tile."""
    dev = tpu_dev(ctx)
    tiles = _tiles("tsqrt", 1, 9)
    _pool, tasks = _kills("tsqrt", bodies["tsqrt"], tiles, "one")
    seen = []
    pins.subscribe("dev:submit_one_end", lambda es, p: seen.append(dict(p)))
    (key, _fn, _don), = _programs(
        dev, lambda: dev._submit_one(tasks[0], None, complete=False))
    assert "batched" not in key
    assert dev.stats["wave_tasks_batched"] == 0
    assert "wave_tasks" not in dev.stats
    assert seen and "batched" not in seen[0]
    want = qr.tsqrt_tpu._batched(*[x[None] for x in tiles[0]])
    for got, ref in zip(_results(dev, tasks)[0], want):
        np.testing.assert_array_equal(got, np.asarray(ref[0]))


def test_the_counters_say_what_went_through(ctx, bodies):
    """Three waves: 8 kills batched, 8 kills of a body that names no form
    unrolled, 3 kills as a chunk of two and a chunk of one (the form over
    a stack of one, like any other width)."""
    dev = tpu_dev(ctx)
    _p, kills = _kills("tsqrt", bodies["tsqrt"], _tiles("tsqrt", 8, 1), "c8")
    _p, odd = _kills("ttqrt", bodies["ttqrt"], _tiles("ttqrt", 3, 2), "c3")
    _p, plain = _kills("tsqrt", _unrolled(bodies["tsqrt"]),
                       _tiles("tsqrt", 8, 3), "u8")
    notes = _spans(lambda: [dev._submit_wave(w, None, complete=False)
                            for w in (kills, plain, odd)])
    assert [(s["cls"], s["n"], s["batched"]) for s in notes] == [
        ("tsqrt", 8, 8), ("tsqrt", 8, 0), ("ttqrt", 2, 2), ("ttqrt", 1, 1)]
    assert dev.stats["wave_tasks"] == 19
    assert dev.stats["wave_tasks_batched"] == 11
    assert dev.stats["wave_fallbacks"] == 0


def test_a_form_that_raises_is_loud_and_counted(ctx, bodies):
    """The form's trace fails: a wave that donates nothing raises out of
    ``_submit_wave`` before any task has side effects; the manager then
    warns, counts a ``wave_fallbacks`` (which the drivers hold at 0) and
    runs the tasks one by one under their bodies.  A wave that was given
    donated tiles fails its pool."""
    dev = tpu_dev(ctx)

    def broken(*pos):
        return bodies["tsqrt"](*pos)
    broken._batched = lambda *pos: (_ for _ in ()).throw(
        ValueError("no such form"))
    tiles = _tiles("tsqrt", 4, 7)
    pool, tasks = _kills("tsqrt", broken, tiles, "nodon", donate=False)
    with pytest.raises(ValueError, match="no such form"):
        dev._submit_wave(tasks, None, complete=False)
    assert not pool.failed and "wave_tasks" not in dev.stats
    assert not any(getattr(t, "_tpu_completed", False) for t in tasks)
    dev._submit_units([("wave", tasks)], None, complete=False)
    assert dev.stats["wave_fallbacks"] == 1
    assert dev.stats["wave_tasks_batched"] == 0
    for got in _results(dev, tasks):
        assert not got[1].any() and np.abs(got[0]).max() > 0
    pool, tasks = _kills("tsqrt", broken, tiles, "don")
    dev._submit_wave(tasks, None, complete=False)
    assert pool.failed and dev.stats["wave_tasks_batched"] == 0


# -- a tile its body says is zeros goes home without a copy from the chip ------

@pytest.mark.parametrize("how", ["declared", "undeclared", "not_known_last"])
def test_a_killed_tile_is_landed_at_home_as_zeros(ctx, bodies, how):
    """``tsqrt`` says its second output is exact zeros (``_zeros``): where
    the task knows that version to be the tile's last (``_tpu_home``),
    zeros are landed at home at the version the commit gave it, counted
    like any tile written home, and the committer never sees the tile.  A
    body that says nothing, or a task that does not know its last
    versions, sends the tile home through the committer as ever."""
    dev = tpu_dev(ctx)
    n = 4
    body = bodies["tsqrt"] if how != "undeclared" \
        else _unrolled(bodies["tsqrt"])
    assert getattr(body, "_zeros", ()) == (() if how == "undeclared"
                                          else (1,))
    _pool, tasks = _kills("tsqrt", body, _tiles("tsqrt", n, 11), how)
    for t in tasks:
        t._tpu_home = None if how == "not_known_last" else (1,)
    seen = []
    com = dev._wb_committer()
    real = com.enqueue_all
    com.enqueue_all = lambda datas, *a, **kw: (
        seen.extend(datas), real(datas, *a, **kw))[1]
    try:
        dev._submit_wave(tasks, None, complete=False)
        dev.flush()
    finally:
        com.enqueue_all = real
    killed = [t.body_args[1][1] for t in tasks]
    landed = n if how == "declared" else 0
    assert dev.stats["wb_zeros_landed"] == landed
    assert dev.stats["bytes_out"] >= n * NB * NB * 4
    for d in killed:
        home, there = d.get_copy(0), d.get_copy(dev.data_index)
        assert home.version == there.version
        assert home.payload.shape == (NB, NB) and not home.payload.any()
        assert home.payload.flags.writeable
        assert not np.asarray(there.payload).any()
        assert (d in seen) == (how != "declared")
