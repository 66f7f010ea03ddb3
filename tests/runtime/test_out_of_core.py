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

import numpy as np
import pytest

from parsec_tpu import native
from parsec_tpu.data import data_create
from parsec_tpu.datadist import TiledMatrix
from parsec_tpu.device.residency import Residency
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
