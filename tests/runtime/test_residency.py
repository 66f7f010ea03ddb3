"""``device/residency.py`` alone: the dual LRU, the accounting and
eviction of one device, with a fake write-back and plain numpy payloads —
no JAX program, no task, no device module.  Every case runs under both
accountings where the native core is built: the byte counter and the
native zone allocator."""

import collections

import numpy as np
import pytest

from parsec_tpu import native
from parsec_tpu.data import Coherency, data_create
from parsec_tpu.device.residency import Residency

DEV = 1            # the device's slot in ``Data.copies``
TILE = 1024        # bytes of one tile (a multiple of the zone's alignment)


@pytest.fixture(params=["counter", "zone"])
def make(request):
    """``make(tiles of room) -> (Residency, the victims its fake
    write-back saw)``; the write-back lands the device copy at home, as
    the device module's does."""
    zone = request.param == "zone"
    if zone and not native.available():
        pytest.skip("needs the native core")
    made = []

    def make(room):
        victims = []

        def writeback(batch):
            for data in batch:
                mine = data.get_copy(DEV)
                victims.append((data.key, mine.version))
                data.attach_copy(0, np.array(mine.payload)).version = \
                    mine.version
            return 0  # microseconds waited

        res = Residency(DEV, room * TILE, collections.Counter(), writeback,
                        zone=zone)
        assert (res.zone is not None) == zone
        made.append(res)
        return res, victims

    yield make
    for res in made:
        if res.zone is not None:
            res.zone.close()


def stage(res, key, *, dirty=False, version=0, nbytes=TILE):
    """What a stage-in (``dirty=False``) or a commit leaves: a device
    copy at ``version``, accounted, newest of its LRU."""
    d = data_create(key, payload=np.zeros(nbytes, np.uint8))
    c = d.attach_copy(DEV, np.full(nbytes, 7, np.uint8))
    c.version = version
    if dirty:
        c.coherency = Coherency.OWNED
    res.account(d, nbytes)
    res.touch(d, dirty=dirty)
    return d


def resident(res, *tiles):
    return [d.get_copy(DEV) is not None for d in tiles]


def test_clean_tiles_are_evicted_before_dirty_ones(make):
    res, victims = make(3)
    dirty = stage(res, "dirty", dirty=True, version=1)   # the oldest
    clean = [stage(res, ("clean", i)) for i in range(2)]
    new = [stage(res, ("new", i), dirty=True, version=1) for i in range(2)]
    # room for three: the two clean ones went, oldest first, not the
    # older dirty one — and a clean tile the host also holds costs no
    # write-back
    assert resident(res, dirty, *clean, *new) == [True, False, False,
                                                  True, True]
    assert victims == [] and res.stats["evictions"] == 2
    stage(res, "more")             # only dirty ones left: the oldest goes
    assert victims == [("dirty", 1)] and resident(res, dirty) == [False]
    assert dirty.get_copy(0).version == 1       # written back, then dropped
    assert res.used <= res.budget


def test_a_clean_copy_that_is_the_only_valid_one_is_written_back(make):
    res, victims = make(1)
    only = stage(res, "arrived")   # a device-native arrival: clean here,
    only.detach_copy(0)            # and the host holds nothing
    ahead = stage(res, "ahead", version=2)   # clean, newer than the host's
    assert victims == [("arrived", 0)]
    assert only.get_copy(DEV) is None and only.get_copy(0) is not None
    stage(res, "next")
    assert victims == [("arrived", 0), ("ahead", 2)]
    assert ahead.get_copy(0).version == 2


def test_the_allocatee_is_never_its_own_victim(make):
    res, victims = make(2)
    a, b = stage(res, "a"), stage(res, "b")
    # ``a`` grows to the whole budget: ``b`` goes, never ``a`` itself
    # (it is the oldest of the LRU when the room is made)
    a.get_copy(DEV).payload = np.zeros(2 * TILE, np.uint8)
    res.account(a, 2 * TILE)
    res.touch(a, dirty=False)
    assert resident(res, a, b) == [True, False]
    assert res.accounted() == {a.data_id: 2 * TILE}
    assert res.used == 2 * TILE and res.stats["evictions"] == 1


def test_accounting_the_same_bytes_again_evicts_nobody(make):
    res, victims = make(2)
    a, b = stage(res, "a"), stage(res, "b", dirty=True, version=1)
    before = (res.used, list(res.clean), list(res.dirty))
    for _ in range(3):     # a commit rebinds an output over its input
        res.account(b, TILE)
        res.account(a, TILE)
    assert (res.used, list(res.clean), list(res.dirty)) == before
    assert resident(res, a, b) == [True, True]
    assert victims == [] and res.stats["evictions"] == 0


def test_freeing_a_tile_that_was_never_accounted_does_not_underflow(make):
    res, _ = make(2)
    a = stage(res, "a")
    # attached from outside (a benchmark pre-placing a tile): in the LRU
    # through a touch, never accounted
    outside = data_create("outside", payload=np.zeros(TILE, np.uint8))
    outside.attach_copy(DEV, np.zeros(TILE, np.uint8))
    res.touch(outside, dirty=False)
    res.free(outside)
    res.drop(outside, evicted=False)
    assert res.used == TILE and res.accounted() == {a.data_id: TILE}
    res.free(a)
    res.free(a)
    assert res.used == 0 and res.accounted() == {}


def test_clear_leaves_nothing_charged_and_nothing_tracked(make):
    res, victims = make(4)
    tiles = [stage(res, i, dirty=i % 2 == 1, version=i % 2)
             for i in range(4)]
    assert res.used == 4 * TILE
    res.clear()
    assert res.used == 0 and res.accounted() == {}
    assert not res.clean and not res.dirty
    # the payloads stay with their Data; nothing was written back
    assert resident(res, *tiles) == [True] * 4 and victims == []
    stage(res, "again")            # and the device is usable again
    assert res.used == TILE


def test_a_budget_change_migrates_the_live_slots(make):
    res, victims = make(4)
    tiles = [stage(res, i) for i in range(3)]
    res.budget = 8 * TILE
    assert res.budget == 8 * TILE and res.used == 3 * TILE
    assert res.accounted() == {d.data_id: TILE for d in tiles}
    more = [stage(res, ("more", i)) for i in range(5)]
    assert resident(res, *tiles, *more) == [True] * 8
    assert res.stats["evictions"] == 0 and victims == []
    stage(res, "ninth")            # the new budget is the one that binds
    assert res.stats["evictions"] == 1
    assert resident(res, tiles[0]) == [False]


def test_settle_brings_the_use_under_the_budget(make):
    res, victims = make(4)
    tiles = [stage(res, i, dirty=True, version=1) for i in range(4)]
    res.budget = 2 * TILE          # commits grew residency past it
    res.settle()
    assert res.used <= res.budget
    if res.zone is None:
        # the counter: the oldest go, each written back first
        assert victims == [(0, 1), (1, 1)]
        assert resident(res, *tiles) == [False, False, True, True]
        assert all(d.get_copy(0).version == 1 for d in tiles[:2])
    else:
        # the zone evicts while it allocates: the slots that fit the new
        # budget migrated, the others fell out of the accounting
        assert victims == [] and len(res.accounted()) == 2
    stage(res, "next")             # either way the budget binds from here
    assert res.used <= res.budget and victims[0] == (0, 1)


def test_release_hands_a_tile_on_without_write_back_or_eviction(make):
    res, victims = make(2)
    a = stage(res, "a", dirty=True, version=3)
    res.warm(a)                    # (a resident tile: newest of its LRU)
    assert list(res.dirty) == [a.data_id]
    assert res.resident_bytes(a) == TILE
    res.release(a)
    assert a.get_copy(DEV) is None and a.get_copy(0).version == 0
    assert res.used == 0 and not res.dirty
    assert victims == [] and res.stats["evictions"] == 0
    assert res.resident_bytes(a) == 0
