"""A chunk's byte bound counts what its program brings onto the device.

``TpuDevice._submit_wave`` cuts a wave into chunks whose tiles stay under
``Residency.chunk_limit``.  A task costs at most its signature's
``FlowPlan.nbytes`` (every tile read, every tile written); where a wave
at that price is cut, each task is counted for itself, and a tile read
and not written that was BORN on the device (a scratch tile, a tile of a
``device_born`` collection) and is there still costs nothing.  Held
here, on the CPU backend on hand-made ready tasks: the five kinds of wave
of the rule, each with the ``dev:wave`` spans' ``cut`` / ``counted``
notes and the two counters; and what must count as it always did, so
that no program reaches a cell whose warm-up never asked for it: a home
tile that is resident, an output that is donated
(``benchmark/drivers/dtd.py`` warms chunks of 32 .. 1 of a 63-task wave
and nothing wider).  And the other half of the account: a scratch tile
let go with its last reader stays charged until the chip has run that
reader's program (``Residency.release(after=)``), so that a pump that
leads the chip waits for room instead of holding memory charged to
nobody.
"""

import numpy as np
import pytest

from parsec_tpu import Context, DEV_TPU, native
from parsec_tpu.core.lifecycle import AccessMode
from parsec_tpu.core.task import Chore, TaskClass
from parsec_tpu.data import data_create
from parsec_tpu.datadist import TiledMatrix
from parsec_tpu.device import scratch
from parsec_tpu.dsl.native_exec import _NativeDeviceTask
from parsec_tpu.profiling import pins

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="needs the native core")
NB = 8
TILE = NB * NB * 4
IN, INOUT = AccessMode.IN, AccessMode.INOUT
#: a task of :func:`five_point` reads five tiles and writes a new one:
#: six tiles at most; thirteen tiles of room are two such tasks, and
#: eight whose reads cost nothing
LIMIT = 13 * TILE


@pytest.fixture(autouse=True)
def _clean_pins():
    pins.clear()
    yield
    pins.clear()


@pytest.fixture
def dev():
    c = Context(nb_cores=1)
    d = next(d for d in c.devices if d.device_type == DEV_TPU)
    d.hbm_budget = 16 * LIMIT
    assert d._res.chunk_limit == LIMIT
    yield d
    c.fini()


class _Pool:
    """What the device module reads of a task's pool."""

    taskpool_id = 0
    name = "stub"
    context = None
    next_use = ()
    failed = False
    fail_reason = None

    def _force_fail(self):
        was, self.failed = self.failed, True
        return not was

    def task_done(self, t=None):
        pass


def five_point(up, down, left, right, centre, out):
    return 0.2 * (up + down + left + right + centre)


def update(c, a, b):
    return c - a @ b


def _tasks(cls, body, args_of, n, donate=()):
    pool, tclass = _Pool(), TaskClass(cls)
    chore = Chore(DEV_TPU, hook=lambda es, t: None)
    chore.body_fn = body
    tasks = []
    for i in range(n):
        t = _NativeDeviceTask(pool, tclass, (i,), 0)
        t.selected_chore = chore
        t.body_args = [("data", d, mode) for d, mode in args_of(i)]
        t._tpu_donate, t._tpu_home = donate, ()
        t.on_complete = lambda task: None
        tasks.append(t)
    return pool, tasks


def _home(tag, n, value=1.0):
    return [data_create((tag, i),
                        payload=np.full((NB, NB), value, np.float32))
            for i in range(n)]


def _sweep(tag, reads, outs, n=16):
    """``n`` five-point tasks: task ``i`` reads ``reads[i .. i + 4]``
    (round the end) and writes ``outs[i]``, a tile nobody has written."""
    return _tasks(
        "sweep", five_point,
        lambda i: [(reads[(i + k) % len(reads)], IN) for k in range(5)]
        + [(outs[i], INOUT)], n)


def _unborn(tag, n, users):
    made = [scratch.new((tag, i), (NB, NB), np.float32) for i in range(n)]
    for d in made:
        scratch.add_users(d, users)
    return made


def _born(dev, tag, n=16, kept=False):
    """``n`` tiles written by a wave on ``dev`` and alive there: scratch
    tiles with five readers to come, or (``kept``) the tiles of a
    device-born collection."""
    if kept:
        A = TiledMatrix(n * NB, NB, NB, NB, name=tag, dtype=np.float32,
                        device_born=True)
        outs = [A.data_of(i, 0) for i in range(n)]
    else:
        outs = _unborn(tag, n, users=6)  # the writer and five readers
    pool, wave = _sweep(tag, _home((tag, "in"), n), outs, n)
    dev._submit_wave(wave, None, complete=False)
    assert not pool.failed
    assert all(d.current_copy(dev.data_index) is not None for d in outs)
    return outs


def _run(dev, tasks):
    """The ``dev:wave`` spans' notes of ``tasks`` as one wave, and what
    the two counters moved by."""
    seen = []

    def note(es, p):
        seen.append(dict(p))
    before = dict(dev.stats)
    pins.subscribe("dev:wave_end", note)
    try:
        dev._submit_wave(tasks, None, complete=False)
    finally:
        pins.unsubscribe("dev:wave_end", note)
    return seen, {k: dev.stats[k] - before.get(k, 0)
                  for k in ("chunks_cut_by_bytes", "chunk_bytes_born_here",
                            "wave_submits", "wave_tasks")}


def _spill(dev, datas):
    """What an eviction under pressure does to a scratch tile with users
    left: its only copy goes to the host."""
    res = dev._res
    budget, res.budget = res.budget, TILE
    try:
        assert not res.reserve(TILE * len(datas))  # everything left
    finally:
        res.budget = budget
    assert all(d.current_copy(dev.data_index) is None for d in datas)
    assert dev.stats["scratch_bytes_out"] == TILE * len(datas)


@pytest.mark.parametrize("reads,width,born", [
    ("home", 2, 0), ("scratch", 8, 5), ("kept", 8, 5), ("spilled", 2, 0)])
def test_a_tile_born_here_costs_the_chunk_nothing(dev, reads, width, born):
    """Sixteen five-point tasks where the most a task can cost allows
    two: two a program over tiles with a home, eight over scratch tiles
    that were born on the device or over the tiles of a device-born
    collection, two again once an eviction spilled them."""
    if reads == "home":
        tiles = _home("h", 16)
    else:
        tiles = _born(dev, reads, kept=reads == "kept")
        if reads == "spilled":
            _spill(dev, tiles)
    # (a reader to come keeps each result for the look at it below)
    pool, wave = _sweep("next", tiles, _unborn("out", 16, users=2))
    seen, moved = _run(dev, wave)
    assert not pool.failed
    assert [s["n"] for s in seen] == [width] * (16 // width)
    # the last chunk takes the tasks that are left, whatever the bound
    assert [s["cut"] for s in seen] \
        == ["bytes"] * (16 // width - 1) + ["tasks"]
    assert [s["counted"] for s in seen] \
        == [width * (6 - born) * TILE] * len(seen)
    assert all(s["counted"] <= LIMIT for s in seen)
    assert moved == {"chunks_cut_by_bytes": 16 // width - 1,
                     "chunk_bytes_born_here": 16 * born * TILE,
                     "wave_submits": 16 // width, "wave_tasks": 16}
    if reads == "spilled":  # the walk staged them back in
        assert dev.stats["scratch_bytes_in"] == 16 * TILE
    for i, out in enumerate(wave):
        got = np.asarray(out.body_args[-1][1].get_copy(
            dev.data_index).payload)
        assert np.allclose(got, 1.0), i


def test_a_mixed_wave_is_cut_by_what_each_chunks_own_tasks_cost(dev):
    """Four tasks over home tiles, then twelve over tiles born here, in
    ONE wave (one signature): not the first task's price for all."""
    born, home = _born(dev, "gen"), _home("h", 16)
    outs = _unborn("out", 16, users=1)
    pool, wave = _tasks(
        "sweep", five_point,
        lambda i: [((home if i < 4 else born)[(i + k) % 16], IN)
                   for k in range(5)] + [(outs[i], INOUT)], 16)
    seen, moved = _run(dev, wave)
    assert not pool.failed
    # 2 x 6 tiles; 2 x 6 again (two more would be 14 > 13); 8 x 1; 4 x 1
    assert [(s["n"], s["cut"], s["counted"] // TILE) for s in seen] \
        == [(2, "bytes", 12), (2, "bytes", 12), (8, "tasks", 8),
            (4, "tasks", 4)]
    assert moved["chunks_cut_by_bytes"] == 2
    assert moved["chunk_bytes_born_here"] == 12 * 5 * TILE


def test_a_read_write_tile_born_here_still_counts_twice(dev):
    """Sixteen updates ``C -= A @ B`` over scratch tiles born here: A
    and B cost nothing, C is read AND written and counts as
    ``FlowPlan.nbytes`` counts it, twice: the input is what a donation
    gives back, and the count knows of none."""
    born = _born(dev, "gen", n=48)
    for d in born:  # (two more waves read or write each)
        scratch.add_users(d, 2)
    c, a, b = born[:16], born[16:32], born[32:]
    for donate in ((), (0,)):
        pool, wave = _tasks(
            "gemm", update,
            lambda i: [(c[i], INOUT), (a[i], IN), (b[i], IN)], 16, donate)
        seen, moved = _run(dev, wave)
        assert not pool.failed
        # four tiles a task at most: three tasks, so two; two tiles once
        # A and B are free: six tasks, so four
        assert [(s["n"], s["cut"], s["counted"]) for s in seen] \
            == [(4, "bytes", 8 * TILE)] * 3 + [(4, "tasks", 8 * TILE)]
        assert moved["chunk_bytes_born_here"] == 16 * 2 * TILE
        assert [s["don"] for s in seen] == [4 * len(donate)] * 4


def test_a_wave_that_fits_at_the_most_it_can_cost_is_not_looked_into(dev):
    born = _born(dev, "gen")
    pool, wave = _sweep("next", born, _unborn("out", 2, users=1), n=2)
    seen, moved = _run(dev, wave)
    assert [(s["n"], s["cut"], s["counted"]) for s in seen] \
        == [(2, "tasks", 12 * TILE)]
    assert moved["chunk_bytes_born_here"] == moved["chunks_cut_by_bytes"] == 0


@pytest.mark.parametrize("resident", [False, True])
@pytest.mark.parametrize("donate", [(), (0,)])
def test_a_home_tile_and_a_donated_output_count_as_before(dev, resident,
                                                          donate):
    """63 independent updates (C read and written, A and B read: four
    tiles a task) at the DTD cell's ratio of tile to budget (3,426 tiles
    of room: 53 tasks a chunk, so 32): chunks of 32, 16, 8, 4, 2, 1 —
    what ``benchmark/drivers/dtd.py`` warms — whether or not the tiles
    are on the device already and whether or not C's input is donated."""
    dev.hbm_budget = 3426 * TILE
    c, a, b = _home("c", 63, 4.0), _home("a", 63), _home("b", 63, 0.5)
    if resident:
        for d in c + a + b:
            dev._h2d.one(d)
            assert d.current_copy(dev.data_index) is not None
    pool, wave = _tasks(
        "gemm", update,
        lambda i: [(c[i], INOUT), (a[i], IN), (b[i], IN)], 63, donate)
    seen, moved = _run(dev, wave)
    assert not pool.failed
    assert [s["n"] for s in seen] == [32, 16, 8, 4, 2, 1]
    assert [s["cut"] for s in seen] == ["tasks"] * 6
    assert [s["don"] for s in seen] == [s["n"] * len(donate) for s in seen]
    assert moved["chunks_cut_by_bytes"] == 0
    assert moved["chunk_bytes_born_here"] == 0
    # one task more and the bound cuts: 64 = 32 + 32
    c2 = _home("c2", 64, 4.0)
    pool, wave = _tasks(
        "gemm", update,
        lambda i: [(c2[i], INOUT), (a[i % 63], IN), (b[i % 63], IN)], 64,
        donate)
    seen, moved = _run(dev, wave)
    assert [(s["n"], s["cut"]) for s in seen] \
        == [(32, "bytes"), (32, "tasks")]
    assert moved["chunks_cut_by_bytes"] == 1


# -- a tile let go stays charged until the chip has let it go ----------------

class _Program:
    """What ``Residency`` asks of an output of a device program."""

    def __init__(self, ready=False, deleted=False):
        self.ready, self.deleted, self.waited = ready, deleted, 0

    def is_deleted(self):
        return self.deleted

    def is_ready(self):
        return self.ready

    def block_until_ready(self):
        self.waited += 1
        self.ready = True


def _charged(res, tag, n):
    tiles = _unborn(tag, n, users=1)
    for d in tiles:
        d.attach_copy(res.index, np.zeros((NB, NB), np.float32))
        assert res.account(d, TILE)
        res.touch(d, dirty=True)
    return tiles


@pytest.mark.parametrize("zone", [False, True])
def test_a_scratch_tile_let_go_is_charged_until_its_program_has_run(zone):
    """Eight tiles of room, six let go behind three programs in flight:
    nothing is free until the chip says so; room that is asked for is
    waited for, oldest program first and no further than needed, before
    anybody is evicted; what a later program wrote over can no longer be
    asked and counts as gone."""
    from parsec_tpu.device.residency import Residency

    stats = {}
    home = []
    res = Residency(1, 8 * TILE, stats, lambda v: home.extend(v) or 0,
                    zone=zone)
    tiles = _charged(res, "gen", 6)
    stays = _charged(res, "stays", 2)
    first, second, third = _Program(), _Program(), _Program()
    for d, after in zip(tiles, (first, first, second, second, third, third)):
        res.release(d, after)
        assert d.get_copy(1) is None
    assert res._in_use() == 8 * TILE and len(res._limbo) == 3
    res.settle()  # nothing has run: nothing is free
    assert res._in_use() == 8 * TILE and stats["lead_waits"] == 0
    # one tile of room: the oldest program is waited for, it alone
    assert res.reserve(TILE)
    assert (first.waited, second.waited, stats["lead_waits"]) == (1, 0, 1)
    assert res._in_use() == 6 * TILE
    # the chip got on meanwhile: a commit lets go of what it let go of
    second.ready = True
    res.settle()
    assert res._in_use() == 4 * TILE and second.waited == 0
    # a program's output that a later one writes in place is not asked
    third.deleted = True
    res.settle()
    assert res._in_use() == 2 * TILE and not res._limbo
    assert third.waited == 0 and stats["lead_waits"] == 1
    # and nobody was evicted for any of it
    assert stats["evictions"] == 0 and not home
    assert all(d.get_copy(1) is not None for d in stays)
    # with nothing left to wait for, room is made as ever
    assert res.reserve(7 * TILE)
    assert stats["evictions"] == 1 and stats["lead_waits"] == 1
    # without a program to ask, a tile let go is free at once
    res.release(stays[1])
    assert res._in_use() == 0
    # and a detach forgets what is still charged
    last = _charged(res, "last", 1)[0]
    res.release(last, _Program())
    assert res._in_use() == TILE
    res.clear()
    assert res._in_use() == 0 and not res._limbo
    if zone:
        res.zone.close()


def test_a_spent_clean_tile_goes_before_anybody_waits():
    """Room while tiles let go are still charged: first what the chip
    has let go of, then the clean tiles nobody reads again (they cost no
    copy), and only then a wait; a clean tile with a reader to come and
    a dirty one stay through all of it."""
    from parsec_tpu.device.residency import NEVER, Residency

    stats = {}
    home = []
    res = Residency(1, 8 * TILE, stats, lambda v: home.extend(v) or 0)
    gone = _charged(res, "gen", 2)
    spent, read_again, live = [
        data_create((tag, 0), payload=np.ones((NB, NB), np.float32))
        for tag in ("spent", "again", "live")]
    for d, dirty in ((spent, False), (read_again, False), (live, True)):
        d.attach_copy(1, np.ones((NB, NB), np.float32))
        assert res.account(d, TILE)
        res.touch(d, dirty=dirty)
    res.next_uses({spent.data_id: NEVER, read_again.data_id: 7})
    running = _Program()
    for d in gone:
        res.release(d, running)
    assert res._in_use() == 5 * TILE
    res.wait_for(3 * TILE)  # (there is room)
    assert stats["evictions"] == stats["lead_waits"] == 0
    res.wait_for(4 * TILE)  # one tile short: the spent one goes
    assert (stats["evictions"], stats["lead_waits"]) == (1, 0)
    assert spent.get_copy(1) is None and not home
    res.wait_for(6 * TILE)  # two more: the chip's, waited for
    assert (stats["evictions"], stats["lead_waits"]) == (1, 1)
    assert res._in_use() == 2 * TILE
    res.wait_for(8 * TILE)  # nothing left to wait for: nobody leaves
    assert stats["evictions"] == 1
    assert read_again.get_copy(1) is not None
    assert live.get_copy(1) is not None


def test_a_wave_lets_go_of_what_it_read_last_behind_its_programs(dev):
    """The scratch tiles of a wave, let go by the wave that read them
    last: each behind the program of its last reader, and free once
    those have run."""
    born = _born(dev, "gen")  # (five readers each, all in the next wave)
    before = dev.hbm_used
    pool, wave = _sweep("next", born, _unborn("out", 16, users=2))
    dev._submit_wave(wave, None, complete=False)
    assert not pool.failed
    assert dev.stats["scratch_tiles_freed"] == 16
    assert all(d.current_copy(dev.data_index) is None for d in born)
    for out in dev._res._limbo:
        out[0].block_until_ready()
    dev._res.settle()
    # sixteen let go, sixteen born
    assert not dev._res._limbo and dev.hbm_used == before
    assert dev.stats["lead_waits"] == 0


# -- the cells whose program set must not move ------------------------------

def _spd(n):
    rng = np.random.default_rng(0)
    a = rng.random((n, n), dtype=np.float32) - np.float32(0.5)
    return ((a + a.T) / 2
            + np.float32(0.75 * np.sqrt(n)) * np.eye(n, dtype=np.float32))


def _through_the_pump(ctx, dev, A):
    from parsec_tpu.dsl.native_exec import NativeExecutor
    from parsec_tpu.ops import cholesky_ptg

    tp = cholesky_ptg(use_tpu=True, use_cpu=False).taskpool(NT=A.mt, A=A)
    ex = NativeExecutor(tp, native_device=True, device=dev)
    ex.run()
    ex.close()


def _through_context(ctx, dev, A):
    from parsec_tpu.ops import cholesky_ptg

    tp = cholesky_ptg(use_tpu=True, use_cpu=False).taskpool(NT=A.mt, A=A)
    ctx.add_taskpool(tp)
    assert tp.wait(timeout=300)
    dev.flush()


def _inserted(ctx, dev, A):
    from parsec_tpu.dsl import DTDTaskpool
    from parsec_tpu.ops import cholesky_dtd

    tp = DTDTaskpool(ctx)
    cholesky_dtd(tp, A, use_tpu=True, use_cpu=False)
    assert tp.wait(timeout=300)
    tp.flush_all(A)
    tp.close()


def _widths(cls, widest, first=None, stride=0):
    """``(class, width, donated positions)`` of the wave programs of
    ``cls`` of every power of two up to ``widest``; every task donates
    its argument ``first``, ``stride`` arguments a task."""
    out, n = [], 1
    while n <= widest:
        out.append((cls, n, () if first is None else tuple(
            first + stride * t for t in range(n))))
        n *= 2
    return out


#: the tile Cholesky at tiny tiles, the budget at each cell's ratio to a
#: tile (14.37 GB over 1, 4 and 16 MiB), one worker so that the order
#: does not depend on time: how it is run, NT, the budget in tiles, the
#: device programs of a solve and its wave programs' (class, width,
#: donated positions), AS THE PARENT OF PR 45 BUILT THEM (recorded from
#: that tree; the chip's cells read 126 / 123 / 628 at NT = 40 / 2,274
#: programs a solve)
CELLS = {
    "tile_pump_n8192": (
        _through_the_pump, 16, 13704, 123,
        _widths("gemm", 64, 0, 3) + _widths("syrk", 8, 0, 2)
        + _widths("trsm", 8, 1, 2)),
    # (since PR 49 a ``Context`` PTG task names its donated input as a
    # pumped one does: the pump cell's positions; widths and the number
    # of programs as before)
    "tile_ctx_n8192": (
        _through_context, 16, 13704, 123,
        _widths("gemm", 64, 0, 3) + _widths("syrk", 8, 0, 2)
        + _widths("trsm", 8, 1, 2)),
    "dtd_potrf_nb1024": (
        _inserted, 24, 3426, 404,
        _widths("gemm", 32, 0, 3) + _widths("syrk", 16, 0, 2)
        + _widths("trsm", 16, 1, 2)),
    "ooc_pump_n90112": (
        _through_the_pump, 44, 856, 2271,
        _widths("gemm", 8, 0, 3) + _widths("syrk", 16, 0, 2)
        + _widths("trsm", 16, 1, 2)),
}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_program_set_of_a_cell_without_born_tiles_did_not_move(cell):
    """No task of a tile Cholesky reads a tile that was born on the
    device: widths, donated positions and the number of programs are the
    parent's, and where the bound cuts (the DTD gemm at 32, the
    out-of-core gemm at 8) it counted every byte."""
    run, nt, tiles, programs, want = CELLS[cell]
    n = nt * NB
    ctx = Context(nb_cores=1)
    try:
        dev = next(d for d in ctx.devices if d.device_type == DEV_TPU)
        dev.hbm_budget = tiles * TILE
        built, real = [], dev._ccache.jit

        def jit(f, key=None, donate_argnums=()):
            if key and key[0] == "wave":
                built.append((key[1], key[5], tuple(donate_argnums)))
            return real(f, key=key, donate_argnums=donate_argnums)
        dev._ccache.jit = jit
        M = _spd(n)
        A = TiledMatrix(n, n, NB, NB, name="A",
                        dtype=np.float32).from_array(M.copy())
        run(ctx, dev, A)
        stats = dict(dev.stats)
        L = np.tril(A.to_array())
    finally:
        ctx.fini()
    assert np.abs(L @ L.T - M).max() < 1e-4
    assert sorted(built) == sorted(want)
    assert stats["wave_submits"] + stats["executed_tasks"] \
        - stats["wave_tasks"] == programs
    assert stats["chunk_bytes_born_here"] == 0
    assert (stats["chunks_cut_by_bytes"] > 0) \
        == (cell in ("dtd_potrf_nb1024", "ooc_pump_n90112"))
