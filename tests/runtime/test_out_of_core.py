"""A matrix larger than the device's budget through the pump (PR 30).

Tile dpotrf with 2.4 MB of lower tiles against a budget of 1 MB
(``device_tpu_hbm_budget_mb`` 1): every task runs on the device, tiles
are evicted (dirty ones written home in batches), staged in again, and
every tile at home is its last version.  Under a budget smaller than one
task's tiles the pool fails loudly: room for one tile of a chunk is not
made at the expense of its neighbour.  ``Residency.reserve`` with nothing
evictable says so; a chunk is bounded by bytes; a copy home goes through
an alias that becomes the home tile, and never silently another way.
"""

import collections
import contextlib
import sys
import threading
import time

import numpy as np
import pytest

from parsec_tpu import native
from parsec_tpu.data import data_create
from parsec_tpu.datadist import TiledMatrix
from parsec_tpu.device.residency import NEVER, UNKNOWN, Residency
from parsec_tpu.dsl.native_exec import NativeExecutor
from parsec_tpu.ops import cholesky_ptg
from parsec_tpu.utils import mca_param

needs_native = pytest.mark.skipif(not native.available(),
                                  reason="needs the native core")
NT, NB = 8, 128


def _spd(n, seed):
    m = np.random.default_rng(seed).standard_normal((n, n))
    return (m @ m.T + n * np.eye(n)).astype(np.float32)


def _solve(spd, nb, budget_mb=None, budget=None):
    """One dpotrf through the pump; returns (tasks run or the error,
    device stats, the matrix)."""
    n = spd.shape[0]
    A = TiledMatrix(n, n, nb, nb, name="A", dtype=np.float32).from_array(spd)
    tp = cholesky_ptg(use_tpu=True, use_cpu=False).taskpool(NT=A.mt, A=A)
    if budget_mb is not None:
        mca_param.params.set("device", "tpu_hbm_budget_mb", budget_mb)
    try:
        ex = NativeExecutor(tp, native_device=True)
    finally:
        if budget_mb is not None:
            mca_param.params.unset("device", "tpu_hbm_budget_mb")
    dev = ex.device
    if budget is not None:
        dev.hbm_budget = budget
    try:
        ran = ex.run()
    except RuntimeError as e:
        ran = e
    finally:
        ex.close()
    return ran, dev, A


@needs_native
def test_dpotrf_larger_than_the_budget_evicts_restages_and_is_right():
    n = NT * NB
    spd = _spd(n, 3)
    ran, dev, A = _solve(spd, NB, budget_mb=1)
    assert dev.hbm_budget == 1 << 20 < NT * (NT + 1) // 2 * NB * NB * 4
    assert ran == NT + NT * (NT - 1) + NT * (NT - 1) * (NT - 2) // 6 == 120
    s = dev.stats
    assert s["executed_tasks"] == 120
    assert s["wave_fallbacks"] == s["submit_retries"] == 0
    assert s["evictions"] > 0 and s["restaged_tiles"] > 0
    assert s["evict_dirty"] > 0 and s["evict_batches"] > 0
    assert s["evict_bytes_home"] == s["evict_dirty"] * NB * NB * 4
    assert s["evict_clean"] + s["evict_dirty"] == s["evictions"]
    assert s["reserve_gave_up"] == s["unaccounted_tiles"] == 0
    assert s["bytes_in"] > NT * (NT + 1) // 2 * NB * NB * 4
    # every tile at home is its LAST version: an intermediate one that an
    # eviction wrote home and nothing superseded would miss its updates
    want = np.linalg.cholesky(spd.astype(np.float64))
    got = np.tril(A.to_array().astype(np.float64))
    assert np.max(np.abs(got - want)) / np.max(np.abs(want)) < 2e-6
    for i in range(NT):
        for j in range(i + 1):
            d = A.data_of(i, j)
            assert d.newest_copy() is d.get_copy(0) or \
                d.get_copy(0).version == d.newest_copy().version


@needs_native
def test_a_budget_smaller_than_one_tasks_tiles_fails_the_pool_loudly():
    """Two tiles of room for a gemm that reads three: the chunk cannot
    split below one task, so the solve fails — it does not evict the
    task's own tiles and go on past the budget."""
    spd = _spd(4 * NB, 5)
    ran, dev, _A = _solve(spd, NB, budget=2 * NB * NB * 4)
    assert isinstance(ran, RuntimeError)
    assert "out of memory" in str(ran) and "no room on the device" in str(ran)
    assert dev.stats["reserve_gave_up"] > 0
    assert dev.stats["wave_fallbacks"] == dev.stats["submit_retries"] == 0
    assert dev.stats["executed_tasks"] < 20


@needs_native
def test_a_wave_chunk_is_bounded_by_bytes():
    """A sixteenth of the budget a device program: with room for 64
    tiles a gemm task (three tiles read, one written) rides alone and a
    trsm (two and one) too; the solve is right all the same."""
    n = NT * NB
    spd = _spd(n, 7)
    ran, dev, A = _solve(spd, NB, budget=64 * NB * NB * 4)
    assert ran == 120
    assert dev.stats["wave_tasks"] == dev.stats["wave_submits"] > 0
    want = np.linalg.cholesky(spd.astype(np.float64))
    got = np.tril(A.to_array().astype(np.float64))
    assert np.max(np.abs(got - want)) / np.max(np.abs(want)) < 2e-6


def _resident(res, key, nbytes, dirty=False):
    d = data_create(key, payload=np.zeros(nbytes // 8))
    d.attach_copy(res.index, np.zeros(nbytes // 8))
    with res.lock:
        assert res.account(d, nbytes)
        res.touch(d, dirty=dirty)
    return d


def test_reserve_with_nothing_evictable_says_so():
    stats = collections.Counter()
    home = []
    res = Residency(1, 4096, stats, lambda victims: home.extend(victims) or 0)
    a, b = _resident(res, "a", 2048), _resident(res, "b", 2048, dirty=True)
    with res.lock:
        res.pin(a)
        res.pin(b)
        assert not res.reserve(1024)
        assert stats["reserve_gave_up"] == 1 and stats["evictions"] == 0
        res.unpin([a])
        assert res.reserve(1024)   # the unpinned one goes, clean: no copy home
    assert stats["evictions"] == stats["evict_clean"] == 1 and not home
    assert a.get_copy(1) is None and b.get_copy(1) is not None
    with res.lock:
        res.unpin([b])
        assert res.reserve(4096)
    assert home == [b] and stats["evict_dirty"] == 1
    assert stats["evict_bytes_home"] == 2048 and stats["evict_batches"] == 2
    assert stats["reserve_gave_up"] == 1 and res.used == 0


def test_victims_go_home_as_one_batch_and_pinned_tiles_stay():
    stats = collections.Counter()
    batches = []
    res = Residency(1, 8 * 1024, stats,
                    lambda victims: batches.append(list(victims)) or 7)
    tiles = [_resident(res, k, 1024, dirty=True) for k in "abcdefgh"]
    with res.lock:
        res.pin(tiles[0])
        assert res.reserve(3 * 1024)
    assert [[d.key for d in b] for b in batches] == [["b", "c", "d"]]
    assert stats["evictions"] == stats["evict_dirty"] == 3
    assert stats["evict_batches"] == 1 and res.used == 5 * 1024
    assert tiles[0].get_copy(1) is not None


def test_victims_are_the_oldest_unpinned_clean_before_dirty():
    """Plain LRU (the chip's run of the cell under it: ``PERF.md`` §6):
    clean tiles leave before dirty ones, the least recently used first,
    and a re-touched tile is the newest of its list."""
    stats = collections.Counter()
    batches = []
    res = Residency(1, 6 * 1024, stats,
                    lambda victims: batches.append(list(victims)) or 0)
    c1, c2, c3 = (_resident(res, k, 1024) for k in ("c1", "c2", "c3"))
    d1, d2, d3 = (_resident(res, k, 1024, dirty=True)
                  for k in ("d1", "d2", "d3"))
    res.warm(c1)                    # used again: now the newest clean one
    with res.lock:
        res.pin(c2)
        assert res.reserve(3 * 1024)
    # c2 is pinned: c3, c1 (in that order), then the oldest dirty one
    assert [d.key for b in batches for d in b] == ["d1"]
    assert [t.get_copy(1) is None for t in (c1, c2, c3, d1, d2, d3)] == \
        [True, False, True, True, False, False]
    assert stats["evict_clean"] == 2 and stats["evict_dirty"] == 1
    assert stats["evict_batches"] == 1


# ---------------------------------------------------------------------------
# victims by next use (PR 33): who touches a tile may say when it is read
# next; without that the order above stands
# ---------------------------------------------------------------------------

def _victim_order(tiles, pinned=(), room=None):
    """``tiles``: ``(key, dirty, next use or None)`` in the order they
    were touched; returns the keys in the order eviction takes them all
    (or as many as ``room`` tiles of need take)."""
    stats = collections.Counter()
    order = []
    res = Residency(1, len(tiles) * 1024, stats,
                    lambda victims: order.append(None) or 0)
    datas = {}
    for key, dirty, use in tiles:
        d = datas[key] = _resident(res, key, 1024, dirty=dirty)
        if use is not None:
            with res.lock:
                res.next_uses({d.data_id: use})
    drop = res.drop

    def spy(data, **kw):
        order.append(data.key)
        return drop(data, **kw)

    res.drop = spy
    with res.lock:
        for key in pinned:
            res.pin(datas[key])
        left = len(tiles) - len(pinned)
        res.reserve((room if room is not None else left) * 1024)
    return [k for k in order if k is not None], stats


VICTIM_ORDERS = {
    # never read again first, whatever their age: clean before dirty
    "never_again_first": (
        [("soon", False, 3), ("dead_dirty", True, NEVER), ("far", True, 90),
         ("dead", False, NEVER)],
        (), ["dead", "dead_dirty", "far", "soon"]),
    # then the farthest next reader, clean or dirty, young or old
    "farthest_rank_first": (
        [("r5", False, 5), ("r70", True, 70), ("r20", False, 20),
         ("r71", False, 71), ("r6", True, 6)],
        (), ["r71", "r70", "r20", "r6", "r5"]),
    # a pinned tile is passed over, however far its next reader
    "pinned_passed_over": (
        [("a", False, NEVER), ("b", True, 99), ("c", False, 7),
         ("d", True, 50)],
        ("a", "b"), ["d", "c"]),
    # nobody said anything: today's order, to the tile
    "unknown_as_today": (
        [("c1", False, None), ("d1", True, None), ("c2", False, None),
         ("d2", True, None), ("c3", False, None)],
        (), ["c1", "c2", "c3", "d1", "d2"]),
    # equal ranks: oldest first, clean before dirty
    "ties_as_today": (
        [("d1", True, 8), ("c1", False, 8), ("d2", True, 8),
         ("c2", False, 8)],
        (), ["c1", "c2", "d1", "d2"]),
    # a mixed set: never, then unknown (clean before dirty, oldest
    # first), then known ones from the far end; the pinned one stays
    "mixed": (
        [("k9", True, 9), ("u_d", True, None), ("dead", True, NEVER),
         ("u_c2", False, None), ("k40", False, 40), ("pinned", False, NEVER),
         ("u_c1", False, None), ("k2", False, 2)],
        ("pinned",),
        ["dead", "u_c2", "u_c1", "u_d", "k40", "k9", "k2"]),
}


@pytest.mark.parametrize("case", sorted(VICTIM_ORDERS))
def test_the_victim_order(case):
    tiles, pinned, want = VICTIM_ORDERS[case]
    got, stats = _victim_order(tiles, pinned)
    assert got == want
    known = sum(1 for k, _d, use in tiles
                if use is not None and k not in pinned)
    never = sum(1 for k, _d, use in tiles if use == NEVER
                and k not in pinned)
    assert stats["evictions"] == len(want)
    assert stats["evict_next_use"] == known
    assert stats["evict_never_again"] == never
    assert stats["evict_clean"] + stats["evict_dirty"] == len(want)


def test_only_as_many_victims_as_the_room_takes():
    tiles, _pinned, want = VICTIM_ORDERS["mixed"]
    got, stats = _victim_order(tiles, ("pinned",), room=3)
    assert got == want[:3] and stats["evictions"] == 3
    assert stats["evict_next_use"] == stats["evict_never_again"] == 1


def test_a_next_use_is_only_raised_and_goes_with_the_copy():
    """Readers of a tile may be staged out of rank order: the answer of
    the one that ran LAST in rank order stands (an earlier reader's
    ``next`` has run already).  ``UNKNOWN`` says nothing; a drop, a
    release and a detach forget."""
    res = Residency(1, 8 * 1024, collections.Counter(), lambda victims: 0)
    a, b, c = (_resident(res, k, 1024) for k in "abc")
    with res.lock:
        res.next_uses({a.data_id: 20, b.data_id: NEVER})
        res.next_uses({a.data_id: 9, b.data_id: 4})     # out of order
        assert res._next == {a.data_id: 20, b.data_id: NEVER}
        res.touch(a, dirty=True)                         # says nothing
        assert res._next == {a.data_id: 20, b.data_id: NEVER}
        res.next_uses({c.data_id: 0})                    # rank 0 is a rank
        assert res._next[c.data_id] == 0
        res.next_uses({a.data_id: UNKNOWN})              # says nothing
        assert res._next[a.data_id] == 20
        res.drop(a)
        assert a.data_id not in res._next
        res.drop(b)
        assert b.data_id not in res._next
        res.release(c)
        assert not res._next
        res.next_uses({c.data_id: 5})
    res.clear()
    assert not res._next


def test_the_evict_span_says_how_many_victims_had_a_known_use():
    notes = []

    class _Span:
        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def note(self, **kw):
            notes.append(kw)

    res = Residency(1, 4 * 1024, collections.Counter(),
                    lambda victims: 11, span=lambda name, **info: _Span())
    for key, use in (("a", NEVER), ("b", 3), ("c", None), ("d", NEVER)):
        d = _resident(res, key, 1024, dirty=key == "b")
        if use is not None:
            with res.lock:
                res.next_uses({d.data_id: use})
    with res.lock:
        assert res.reserve(4 * 1024)
    assert notes == [{"victims": 4, "dirty": 1, "bytes_home": 1024,
                      "wait_us": 11, "known": 3, "never": 2,
                      "cancelled": 0}]


class _Forgetful(Residency):
    """The residency as it was: it is told, and forgets."""

    def next_uses(self, ranks):
        pass


def _ooc_solve(monkeypatch, residency, nt=10, nb=32, tiles=40, seed=11):
    from parsec_tpu.device import tpu
    from parsec_tpu.dsl import attach_plan

    monkeypatch.setattr(tpu, "Residency", residency)
    attach_plan.clear()
    spd = _spd(nt * nb, seed)
    ran, dev, A = _solve(spd, nb, budget=tiles * nb * nb * 4)
    attach_plan.clear()
    assert type(dev._res) is residency
    assert ran == nt + nt * (nt - 1) + nt * (nt - 1) * (nt - 2) // 6
    want = np.linalg.cholesky(spd.astype(np.float64))
    got = np.tril(A.to_array().astype(np.float64))
    assert np.max(np.abs(got - want)) / np.max(np.abs(want)) < 2e-6
    s = dev.stats
    assert s["reserve_gave_up"] == s["unaccounted_tiles"] == 0
    assert s["wave_fallbacks"] == s["submit_retries"] == 0
    assert s["evict_clean"] + s["evict_dirty"] == s["evictions"] > 0
    return s


@needs_native
def test_by_next_use_less_comes_in_and_fewer_tiles_are_evicted(monkeypatch):
    """55 lower tiles against room for 40: the same solve, both right,
    with the residency as it is and with one that forgets what it is
    told (the order of PR 30): fewer evictions, fewer bytes in, fewer
    victims written home."""
    new = dict(_ooc_solve(monkeypatch, Residency))
    old = dict(_ooc_solve(monkeypatch, _Forgetful))
    assert old["evict_next_use"] == old["evict_never_again"] == 0
    assert new["evict_next_use"] == new["evictions"]      # none unknown
    assert 0 < new["evict_never_again"] < new["evictions"]
    assert new["evictions"] < 0.7 * old["evictions"]
    assert new["bytes_in"] < 0.8 * old["bytes_in"]
    assert new["evict_bytes_home"] < old["evict_bytes_home"]
    assert new["restaged_tiles"] < old["restaged_tiles"]
    assert new["bytes_out"] < old["bytes_out"]


def test_the_chunk_limit_follows_the_budget():
    res = Residency(1, 85 * 1024, collections.Counter(), lambda victims: 0)
    assert res.chunk_limit == 85 * 64
    res.budget = 16 * 1024
    assert res.chunk_limit == 1024


# ---------------------------------------------------------------------------
# a copy home through an alias: no cached host value, no landing copy —
# and never silently the other way (``wb_alias_fallbacks``)
# ---------------------------------------------------------------------------

class _CopyingWriter:
    """``HostWriter(adopt=True)`` as it is off the CPU backend: a device
    array's host value is a COPY that nobody else can reach (read-only,
    its memory its own).  On the CPU backend it is a view of the device's
    memory, so the collect is doubled here."""

    def __new__(cls, stats):
        from parsec_tpu.device.staging import HostWriter

        class Writer(HostWriter):
            collected = []

            def d2h_batch(self, payloads):
                self.collected = list(payloads)
                hosts = [np.array(p) for p in payloads]
                for h in hosts:
                    h.flags.writeable = False
                self.hosts = hosts
                return hosts

        return Writer(1, stats, name="copying", adopt=True)


def _dirty_on_device(key, value, n=64):
    import jax.numpy as jnp

    d = data_create(key, payload=np.zeros(n, np.float32))
    c = d.attach_copy(1, jnp.full(n, value, jnp.float32))
    c.version = 2
    return d


@pytest.mark.parametrize("how", ["one", "batch", "started_early"])
def test_a_copy_home_goes_through_an_alias_that_becomes_the_home_tile(how):
    stats = collections.Counter()
    w = _CopyingWriter(stats)
    tiles = [_dirty_on_device(k, float(k)) for k in (1, 2, 3)]
    payloads = [d.get_copy(1).payload for d in tiles]
    if how == "one":
        for d in tiles:
            w.writeback(d)
            # the collect went through another array over the same buffer
            assert w.collected[0] is not d.get_copy(1).payload
            assert d.get_copy(0).payload is w.hosts[0]  # no landing copy
    else:
        early = [w.start(d) for d in tiles] if how == "started_early" else ()
        assert w.writeback_batch(tiles, early=early) == (3, 3)
        assert all(a is not p for a, p in zip(w.collected, payloads))
        assert all(d.get_copy(0).payload is h       # no landing copy
                   for d, h in zip(tiles, w.hosts))
        if early:
            assert [a for (_v, a) in early] == w.collected
            assert stats["wb_early_hits"] == 3
    for k, (d, p) in enumerate(zip(tiles, payloads), 1):
        home = d.get_copy(0)
        assert home.version == 2 and home.payload.flags.writeable
        np.testing.assert_array_equal(home.payload, np.full(64, float(k)))
        # the RESIDENT array made no copy: no host value cached beside it
        assert getattr(p, "_npy_value", None) is None
    assert stats["wb_alias_fallbacks"] == 0
    assert stats["bytes_out"] == 3 * 64 * 4


def test_a_refused_alias_is_counted_and_warned_once(monkeypatch):
    """JAX refusing the alias brings the cached second matrix back: the
    copy home still lands (data first), and the run says so."""
    import jax

    from parsec_tpu.utils import debug

    def refuse(*a, **k):
        raise TypeError("no such array")

    warned = []
    monkeypatch.setattr(jax, "make_array_from_single_device_arrays", refuse)
    monkeypatch.setattr(debug, "warning",
                        lambda msg, *a: warned.append(msg % a))
    stats = collections.Counter()
    w = _CopyingWriter(stats)
    tiles = [_dirty_on_device(k, float(k)) for k in (1, 2)]
    assert w.writeback_batch(tiles) == (2, 2)
    assert w.collected == [d.get_copy(1).payload for d in tiles]
    assert stats["wb_alias_fallbacks"] == 2 and len(warned) == 1
    assert "no alias" in warned[0]
    for k, d in enumerate(tiles, 1):
        np.testing.assert_array_equal(d.get_copy(0).payload,
                                      np.full(64, float(k)))
        assert d.get_copy(0).payload.flags.writeable  # commit copied it


def test_a_host_value_that_is_a_view_is_counted_not_adopted():
    """The CPU backend's own host values are views of the device's
    memory: with ``adopt`` on, each is copied at the landing and counted
    (which is why the device module switches ``adopt`` off there)."""
    from parsec_tpu.device.staging import HostWriter

    stats = collections.Counter()
    w = HostWriter(1, stats, name="cpu", adopt=True)
    d = _dirty_on_device("v", 4.0)
    w.writeback(d)
    home = d.get_copy(0).payload
    np.testing.assert_array_equal(home, np.full(64, 4.0))
    assert home.flags.writeable and home.flags.owndata
    assert stats["wb_alias_fallbacks"] == 1
    # and off, as the device module runs on this backend: no alias at all
    stats = collections.Counter()
    w = HostWriter(1, stats, name="cpu")
    d = _dirty_on_device("w", 5.0)
    assert w._alias(d.get_copy(1).payload) is d.get_copy(1).payload
    w.writeback(d)
    assert stats["wb_alias_fallbacks"] == 0
    np.testing.assert_array_equal(d.get_copy(0).payload, np.full(64, 5.0))


def test_a_consumed_array_is_no_alias_fallback():
    """An array a donating task consumed since the snapshot has nothing
    to bring home and nothing to alias: not a fallback."""
    import jax.numpy as jnp

    from parsec_tpu.device.staging import HostWriter

    stats = collections.Counter()
    w = HostWriter(1, stats, name="gone", adopt=True)
    x = jnp.ones(8)
    x.delete()
    assert w._alias(x) is x and stats["wb_alias_fallbacks"] == 0


# ---------------------------------------------------------------------------
# an eviction in two holds of the lock (PR 35): whoever comes WITHOUT the
# residency lock (the transfer lane) lets its victims go home with the
# lock free; whoever holds it around a walk evicts as ever
# ---------------------------------------------------------------------------

class _Notes:
    """A span double that keeps what ``dev:evict`` notes."""

    def __init__(self):
        self.notes = []

    def __call__(self, name, **info):
        assert name == "dev:evict"
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def note(self, **kw):
        self.notes.append(kw)


class _SlowHome:
    """A write-back double that blocks on an event: the victims' device
    copies land as their host copies (``land``) once it is let."""

    def __init__(self, land=True):
        self.land = land
        self.begun, self.let = threading.Event(), threading.Event()
        self.batches = []

    def __call__(self, victims):
        self.batches.append([v.key for v in victims])
        snaps = [(v, np.array(v.get_copy(1).payload), v.get_copy(1).version)
                 for v in victims]
        self.begun.set()
        assert self.let.wait(timeout=30)
        if self.land:
            for v, payload, version in snaps:
                v.attach_copy(0, payload).version = version
        return 5


def _owned(res, key, nbytes=1024, version=1):
    """A resident dirty tile whose copy on the device is the only valid
    one (``version`` ahead of the host copy's 0)."""
    d = _resident(res, key, nbytes, dirty=True)
    d.get_copy(res.index).version = version
    return d


def _lane(res, nbytes):
    """``make_room`` on a thread of its own, as the transfer lane calls
    it: without the lock."""
    t = threading.Thread(target=res.make_room, args=(nbytes,), daemon=True)
    t.start()
    return t


def _joined(t):
    t.join(timeout=30)
    assert not t.is_alive()


def test_the_lanes_victims_go_home_with_the_residency_lock_free():
    stats, home, span = collections.Counter(), _SlowHome(), _Notes()
    res = Residency(1, 4 * 1024, stats, home, span=span)
    a, b, c, d = (_owned(res, k) for k in "abcd")
    lane = _lane(res, 2 * 1024)
    assert home.begun.wait(timeout=30)
    # the victims are on their way home: anybody may take the lock, and
    # finds them accounted (the budget holds), attached and in no LRU
    assert res.lock.acquire(timeout=10)
    try:
        assert res.used == 4 * 1024 and list(res.dirty.values()) == [c, d]
        assert a.get_copy(1) is not None and b.get_copy(1) is not None
        assert stats["evictions"] == 0
    finally:
        res.lock.release()
    home.let.set()
    _joined(lane)
    # the room asked for is there on return; ONE batch, ONE span
    assert res.used + 2 * 1024 <= res.budget
    assert home.batches == [["a", "b"]]
    assert a.get_copy(1) is None and b.get_copy(1) is None
    assert a.get_copy(0).version == b.get_copy(0).version == 1
    assert stats["evictions"] == stats["evict_dirty"] == 2
    assert stats["evict_bytes_home"] == 2 * 1024
    assert stats["evict_batches"] == 1 and stats["evict_cancelled"] == 0
    assert span.notes == [{"victims": 2, "dirty": 2, "bytes_home": 2048,
                           "wait_us": 5, "known": 0, "never": 0,
                           "cancelled": 0}]


def _pin(res, d):
    res.pin(d)


def _restage(res, d):
    res.touch(d, dirty=True)   # what a staging walk does with a hit


def _rewrite(res, d):
    c = d.get_copy(1)          # what a commit does with an output
    c.payload = c.payload + 1.0
    d.version_bump(1)
    res.touch(d, dirty=True)


@pytest.mark.parametrize("meanwhile", [_pin, _restage, _rewrite],
                         ids=["pinned", "restaged", "rewritten"])
def test_a_victim_somebody_took_during_the_write_back_stays(meanwhile):
    stats, home, span = collections.Counter(), _SlowHome(), _Notes()
    res = Residency(1, 4 * 1024, stats, home, span=span)
    a, b, c, d = (_owned(res, k) for k in "abcd")
    lane = _lane(res, 2 * 1024)
    assert home.begun.wait(timeout=30)
    with res.lock:
        meanwhile(res, a)
    home.let.set()
    _joined(lane)
    mine = a.get_copy(1)
    assert mine is not None and mine.payload is not None
    assert res.accounted()[a.data_id] == 1024 and res.used == 3 * 1024
    assert b.get_copy(1) is None
    assert stats["evict_cancelled"] == 1
    assert stats["evictions"] == stats["evict_dirty"] == 1
    assert stats["evict_clean"] == 0 and stats["evict_bytes_home"] == 1024
    # (the span: what was chosen and what went over the link)
    assert span.notes[0]["victims"] == span.notes[0]["dirty"] == 2
    assert span.notes[0]["bytes_home"] == 2048
    assert span.notes[0]["cancelled"] == 1
    if meanwhile is _rewrite:
        # the landing that was overtaken is harmless: the device's
        # version is the newest, and it is a victim again later
        assert a.get_copy(0).version == 1 and mine.version == 2
        assert a.newest_copy() is mine
    # what is short the caller's reserve makes up under its hold, as
    # everybody else evicts: the next victim, not the one that stayed
    with res.lock:
        assert res.reserve(2 * 1024)
    assert stats["reserve_gave_up"] == 0 and res.used == 2 * 1024
    assert a.get_copy(1) is not None and c.get_copy(1) is None


def test_the_only_valid_copy_is_never_dropped_before_its_host_copy_stands():
    """A write-back that lands nothing (a committer that died, a copy
    that a donating task consumed): with the lock free meanwhile nobody
    vouches for the victims, so they stay, the oldest of their LRU."""
    stats, home = collections.Counter(), _SlowHome(land=False)
    res = Residency(1, 4 * 1024, stats, home)
    a, b, c, d = (_owned(res, k) for k in "abcd")
    home.let.set()
    res.make_room(2 * 1024)
    assert home.batches == [["a", "b"]]
    assert all(t.get_copy(1).payload is not None for t in (a, b))
    assert all(t.get_copy(0).version == 0 for t in (a, b))
    assert stats["evict_cancelled"] == 2 and stats["evictions"] == 0
    assert stats["evict_bytes_home"] == 0 and res.used == 4 * 1024
    assert list(res.dirty.values()) == [a, b, c, d] and not res.clean


def test_a_victim_evicted_by_a_walk_that_held_the_lock_is_not_counted_twice():
    """Staged, let go and evicted again under the pump's own hold while
    its first copy home was on its way: that eviction counted it."""
    stats, home = collections.Counter(), _SlowHome()
    res = Residency(1, 4 * 1024, stats, home)
    a, b, c, d = (_owned(res, k) for k in "abcd")
    lane = _lane(res, 1024)
    assert home.begun.wait(timeout=30)
    assert home.batches == [["a"]]
    with res.lock:
        res.touch(a, dirty=True)
        for t in (b, c, d):
            res.pin(t)
        home.let.set()            # (the second batch does not block)
        assert res.reserve(1024)  # under the lock: a, once more
        res.unpin([b, c, d])
    _joined(lane)
    assert home.batches == [["a"], ["a"]] and a.get_copy(1) is None
    assert stats["evictions"] == stats["evict_dirty"] == 1
    assert stats["evict_cancelled"] == 1 and stats["evict_batches"] == 2
    assert res.used == 3 * 1024


def test_a_caller_that_holds_the_lock_evicts_as_before():
    """The pump's own staging walk, a commit's ``settle()``, the
    synchronous regime: the lock is held from the choice of the victims
    to their drop (nobody else gets it), and they drop whatever the
    write-back did, as they always have."""
    stats, span = collections.Counter(), _Notes()
    tried = []

    def home(victims):
        t = threading.Thread(
            target=lambda: tried.append(res.lock.acquire(timeout=0.2)))
        t.start()
        _joined(t)
        return 3

    res = Residency(1, 4 * 1024, stats, home, span=span)
    a, b, c, d = (_owned(res, k) for k in "abcd")
    with res.lock:
        assert res.reserve(2 * 1024)
    assert tried == [False]
    assert a.get_copy(1) is None and b.get_copy(1) is None
    assert stats["evictions"] == stats["evict_dirty"] == 2
    assert stats["evict_cancelled"] == 0 and res.used == 2 * 1024
    assert span.notes == [{"victims": 2, "dirty": 2, "bytes_home": 2048,
                           "wait_us": 3, "known": 0, "never": 0,
                           "cancelled": 0}]


def _staging(budget, blocked=None):
    """A residency, the write-back halves and the stage-in of one CPU
    device, wired as the device module wires them; ``blocked``: an event
    the eviction's write-back waits for (its begin is ``begun``)."""
    import jax

    from parsec_tpu.device.staging import HostWriter, StageIn

    stats = collections.Counter()
    writer = HostWriter(1, stats, name="cpu")
    begun = threading.Event()

    def home(victims):
        begun.set()
        assert blocked is None or blocked.wait(timeout=30)
        writer.writeback_batch(victims)
        return 0

    res = Residency(1, budget, stats, home)
    h2d = StageIn(res, writer, jax.devices("cpu")[0], stats,
                  lambda name, **info: contextlib.nullcontext())
    return res, h2d, stats, begun


def _on_device(res, key, value, n=256):
    """A tile (``n`` float32) that a task wrote on the device: resident,
    dirty, one version ahead of its host copy."""
    import jax.numpy as jnp

    d = data_create(key, payload=np.zeros(n, np.float32))
    d.attach_copy(1, jnp.full(n, value, jnp.float32)).version = 1
    with res.lock:
        assert res.account(d, 4 * n)
        res.touch(d, dirty=True)
    return d


def test_the_lanes_batch_has_its_room_on_return_whatever_was_cancelled():
    """``StageIn.batch(unlocked=True)``: room for two tiles, one victim
    pinned by the pump while it went home: the hold that accounts the
    batch evicts the next one, and the budget holds."""
    let = threading.Event()
    res, h2d, stats, begun = _staging(4 * 1024, blocked=let)
    a, b, c, d = (_on_device(res, k, i + 1.0) for i, k in enumerate("abcd"))
    x, y = (data_create(k, payload=np.full(256, v, np.float32))
            for k, v in (("x", 8.0), ("y", 9.0)))
    keep, got = [], {}
    lane = threading.Thread(
        target=lambda: h2d.batch([x, y], got=got, keep=keep, unlocked=True),
        daemon=True)
    lane.start()
    assert begun.wait(timeout=30)
    with res.lock:                      # (free: the victims are going home)
        res.touch(a, dirty=True)
        res.pin(a)
    let.set()
    _joined(lane)
    assert keep == [x, y] and res.used == 4 * 1024 == res.budget
    assert set(res.accounted()) == {t.data_id for t in (a, d, x, y)}
    np.testing.assert_array_equal(np.asarray(got[x.data_id]), 8.0)
    assert stats["evict_cancelled"] == 1 and stats["evict_batches"] == 2
    assert stats["evictions"] == stats["evict_dirty"] == 2
    assert stats["reserve_gave_up"] == 0
    for t, v in ((b, 2.0), (c, 3.0)):   # gone, and home at their version
        assert t.get_copy(1) is None and t.get_copy(0).version == 1
        np.testing.assert_array_equal(t.get_copy(0).payload, v)
    assert a.get_copy(1).payload is not None


def test_nothing_is_lost_while_the_lane_evicts_beside_the_pump():
    """A time-bounded stress: one lane staging tiles in without the
    lock, four threads staging, pinning and rewriting tiles under it as
    the pump does, in a budget of a quarter of the tiles.  Every write
    is counted: a tile dropped while its newest version was the chip's
    alone, or put back over a newer one, would lose some."""
    import jax

    from parsec_tpu.device.staging import NoRoom

    n_tiles, n = 32, 256
    res, h2d, stats, _begun = _staging(8 * 4 * n)
    tiles = [data_create(k, payload=np.zeros(n, np.float32))
             for k in range(n_tiles)]
    writes = [0] * n_tiles
    stop = time.monotonic() + 2.0
    errors = []

    def lane(seed):
        rng = np.random.default_rng(seed)
        while time.monotonic() < stop:
            batch = [tiles[k] for k in rng.choice(n_tiles, 3, replace=False)]
            try:
                h2d.batch(batch, unlocked=True)
            except NoRoom:
                pass

    def pump(seed):
        rng = np.random.default_rng(seed)
        while time.monotonic() < stop:
            k = int(rng.integers(n_tiles))
            pinned = []
            try:
                with res.lock:
                    got = {}
                    h2d.batch([tiles[k]], got=got, keep=pinned)
                    c = tiles[k].get_copy(1)
                    assert c.payload is got[tiles[k].data_id]
                    tiles[k].transfer_ownership(1, 3)   # INOUT
                    c.payload = c.payload + 1.0
                    tiles[k].version_bump(1)
                    res.touch(tiles[k], dirty=True)
                    writes[k] += 1
                    res.settle()
            except NoRoom:
                pass
            finally:
                res.unpin(pinned)

    def guarded(fn, seed):
        try:
            fn(seed)
        except BaseException as e:
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=guarded, args=(lane, 0))] + \
            [threading.Thread(target=guarded, args=(pump, s))
             for s in range(1, 5)]
        for t in threads:
            t.start()
        for t in threads:
            _joined(t)
    finally:
        sys.setswitchinterval(old)
    assert not errors, errors
    assert sum(writes) > 0 and stats["evictions"] > 0
    assert res.used <= res.budget
    assert stats["evict_clean"] + stats["evict_dirty"] == stats["evictions"]
    for k, tile in enumerate(tiles):
        newest = tile.newest_copy()
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(newest.payload)),
            np.full(n, float(writes[k]), np.float32), err_msg=str(k))


def test_a_write_back_that_raises_leaves_its_victims_evictable():
    stats = collections.Counter()

    def home(victims):
        raise RuntimeError("the link is down")

    res = Residency(1, 4 * 1024, stats, home)
    tiles = [_owned(res, k) for k in "abcd"]
    with pytest.raises(RuntimeError, match="the link is down"):
        res.make_room(2 * 1024)
    assert list(res.dirty.values()) == tiles and res.used == 4 * 1024
    assert stats["evict_cancelled"] == 2 and stats["evictions"] == 0
