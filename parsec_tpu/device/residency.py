"""Residency: which tiles hold a copy on one device, and what they are
charged against its memory budget.

The reference's ``gpu_mem_lru`` / ``gpu_mem_owned_lru`` and its
``zone_malloc`` slab (``device_gpu.h:240-243``) as one unit that knows
neither JAX programs nor tasks: a dual LRU of resident ``Data`` (clean,
and dirty = owned here), byte accounting against a budget, and eviction
— clean tiles first, then dirty ones, each written back first when the
device holds the only valid copy, by a callable handed in at
construction.  "Allocation" is accounting: PJRT owns the real placement.

Which accounting a device has is decided ONCE, in the constructor: the
native zone allocator (alignment and fragmentation modelled for real —
an allocation can fail under budget and evict) or a byte counter.
Callers see :meth:`~Residency.account` / :meth:`~Residency.free` /
:meth:`~Residency.settle` / :meth:`~Residency.clear` and never ask which.

``lock`` is the residency lock: LRU and accounting mutations are not
single-threaded once the transfer lane prestages wave N+1 while the
pump thread commits wave N.  RLock — the stage/evict/account paths
nest.  Order: the device's ``_lock`` -> ``lock`` -> ``Data.lock``; the
write-back committer takes only ``Data.lock``, so an eviction waiting
on it under ``lock`` cannot deadlock.
"""

from __future__ import annotations

import collections
import threading
from typing import Callable, Dict, MutableMapping

from ..data.data import Coherency, Data
from ..utils import mca_param


def native_zone(platform: str) -> bool:
    """Which accounting a device on ``platform`` gets: True for the
    native zone allocator (``device_tpu_native_zone``, the default,
    where the native core is built)."""
    if not mca_param.register(
            "device", "tpu_native_zone", 1,
            help="use the native zone allocator for HBM accounting"):
        return False
    from .. import native

    built = native.available()
    if not built and platform == "tpu":
        # on the CPU backend byte-counter accounting stands in (the
        # native-off CI leg); on a chip the configured allocator missing
        # is a broken installation
        raise RuntimeError(
            "device_tpu_native_zone=1 but the native core is "
            f"unavailable: {native.build_error()}")
    return built


class Residency:
    """The resident tiles of one device and their bytes."""

    def __init__(self, data_index: int, budget: int,
                 stats: MutableMapping[str, int],
                 writeback: Callable[[Data], None], zone: bool = False):
        """``data_index``: the device's slot in ``Data.copies``;
        ``writeback(victim)``: bring a victim's device copy home before
        it drops; ``stats``: where ``evictions`` are counted; ``zone``:
        account in the native zone allocator (:func:`native_zone`)."""
        self.index = data_index
        self.stats = stats
        self.lock = threading.RLock()
        #: dual LRU keyed by data_id, oldest first
        self.clean: "collections.OrderedDict[int, Data]" = \
            collections.OrderedDict()
        self.dirty: "collections.OrderedDict[int, Data]" = \
            collections.OrderedDict()
        self.used = 0
        self._budget = int(budget)
        self._writeback = writeback
        #: data_id -> accounted bytes (and, with the zone, -> offset).
        #: Truth for what THIS device accounted lives here, not in a
        #: caller's view: a copy attached from outside (a benchmark
        #: pre-placing tiles) enters the LRU without ever being
        #: accounted, and freeing it must not underflow the budget
        self._held: Dict[int, int] = {}
        self._offsets: Dict[int, int] = {}
        #: the native zone allocator (offset-based: PJRT owns the memory)
        self.zone = self._new_zone() if zone else None

    def _new_zone(self):
        from .. import native

        return native.ZoneAllocator(self._budget)

    # -- the budget --------------------------------------------------------
    @property
    def budget(self) -> int:
        return self._budget

    @budget.setter
    def budget(self, value: int) -> None:
        """A budget change rebuilds the zone, migrating live slots
        (slots that no longer fit fall out of segment accounting)."""
        with self.lock:
            self._budget = int(value)
            if self.zone is None:
                return
            fresh = self._new_zone()
            held: Dict[int, int] = {}
            offsets: Dict[int, int] = {}
            for did, nbytes in self._held.items():
                off = fresh.alloc(nbytes)
                if off is not None:
                    held[did], offsets[did] = nbytes, off
            self.zone.close()
            self.zone, self._held, self._offsets = fresh, held, offsets
            self.used = fresh.used

    def accounted(self) -> Dict[int, int]:
        """data_id -> bytes charged to the budget (a snapshot)."""
        with self.lock:
            return dict(self._held)

    # -- accounting --------------------------------------------------------
    def account(self, data: Data, nbytes: int) -> None:
        """(Re)account ``data``'s slot at ``nbytes``, evicting for
        space.  The same bytes rebound (an epilog's output over its
        input) keep the slot: nothing is allocated, nobody is evicted."""
        did = data.data_id
        with self.lock:
            if nbytes > 0 and self._held.get(did, 0) == nbytes:
                return
            # the allocatee must not be its own eviction victim (either
            # accounting): callers re-touch the LRU right after
            self.forget(data)
            old = self._held.pop(did, 0)
            if self.zone is None:
                self.reserve(max(0, nbytes - old))
                self.used += nbytes - old
                if nbytes > 0:
                    self._held[did] = nbytes
                return
            off = self._offsets.pop(did, None)
            if off is not None:
                self.zone.release(off)
            if nbytes > 0:
                guard = 0
                while True:
                    off = self.zone.alloc(nbytes)
                    if off is not None or guard > 10000 \
                            or not self.evict_one():
                        break
                    guard += 1
                if off is not None:
                    self._held[did], self._offsets[did] = nbytes, off
            self.used = self.zone.used

    def free(self, data: Data) -> None:
        """Release ``data``'s slot (none: a no-op, never an underflow)."""
        with self.lock:
            old = self._held.pop(data.data_id, 0)
            if self.zone is None:
                self.used -= old
                return
            off = self._offsets.pop(data.data_id, None)
            if off is not None:
                self.zone.release(off)
            self.used = self.zone.used

    def settle(self) -> None:
        """After commits grew residency: back under the budget (the zone
        already evicted while it allocated)."""
        if self.zone is None:
            self.reserve(0)

    def clear(self) -> None:
        """Forget every tile and every charge (detach).  The payloads
        stay attached to their Data objects (a later stage-in reuses
        them, unaccounted, like externally pre-placed copies), but a
        slot no LRU tracks can never be evicted: left charged it would
        leak phantom ``used`` across device reuse (the shared ``device=``
        pattern) until eviction stops working."""
        with self.lock:
            self.clean.clear()
            self.dirty.clear()
            if self.zone is not None:
                for off in self._offsets.values():
                    self.zone.release(off)
            self._offsets.clear()
            self._held.clear()
            self.used = 0 if self.zone is None else self.zone.used

    # -- the LRUs ----------------------------------------------------------
    def touch(self, data: Data, *, dirty: bool) -> None:
        """``data`` was just used: newest of its LRU."""
        with self.lock:
            self.forget(data)
            (self.dirty if dirty else self.clean)[data.data_id] = data

    def forget(self, data: Data) -> None:
        """Out of both LRUs (the caller holds the lock): no victim."""
        self.clean.pop(data.data_id, None)
        self.dirty.pop(data.data_id, None)

    def warm(self, data: Data) -> None:
        """Re-touch a resident copy so that eviction passes it over."""
        with self.lock:
            mine = data.get_copy(self.index)
            if mine is not None and mine.payload is not None:
                self.touch(data, dirty=mine.coherency is Coherency.OWNED)

    def resident_bytes(self, data: Data) -> int:
        """Bytes of ``data``'s copy here if it is the newest version."""
        c = data.get_copy(self.index)
        if c is None or c.payload is None:
            return 0
        newest = data.newest_copy()
        return c.nbytes if newest is None or c.version >= newest.version \
            else 0

    # -- making room -------------------------------------------------------
    def reserve(self, nbytes: int) -> None:
        """Make room: evict clean first, then write back dirty tiles
        (reference device_gpu.c:978-1120 retry/evict loops)."""
        with self.lock:
            guard = 0
            while self.used + nbytes > self._budget and guard < 10000:
                guard += 1
                if not self.evict_one():
                    break  # nothing evictable; trust the PJRT allocator

    def evict_one(self) -> bool:
        with self.lock:
            if self.clean:
                _, victim = self.clean.popitem(last=False)
                mine = victim.get_copy(self.index)
                host = victim.get_copy(0)
                if mine is not None and (host is None or host.payload is None
                                         or host.version < mine.version):
                    # a CLEAN device copy can still be the ONLY valid
                    # copy: device-native arrivals (_deposit_payload,
                    # bytes_d2d) attach no host copy — dropping without
                    # write-back would destroy the data
                    self._writeback(victim)
            elif self.dirty:
                _, victim = self.dirty.popitem(last=False)
                self._writeback(victim)
            else:
                return False
            self.drop(victim)
            return True

    def drop(self, data: Data, *, evicted: bool = True) -> None:
        """Detach ``data``'s copy here and release its slot."""
        with self.lock:
            c = data.detach_copy(self.index)
            if c is not None:
                self.free(data)
                if evicted:
                    self.stats["evictions"] += 1

    def release(self, data: Data) -> None:
        """Hand ``data``'s copy on WITHOUT a write-back and without
        counting an eviction: out of the LRUs, detached, its slot freed
        (``drop_residency``; a scratch tile's last user)."""
        with self.lock:
            self.forget(data)
            self.drop(data, evicted=False)
