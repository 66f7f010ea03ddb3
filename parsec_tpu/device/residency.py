"""Residency: which tiles hold a copy on one device, and what they are
charged against its memory budget.

The reference's ``gpu_mem_lru`` / ``gpu_mem_owned_lru`` and its
``zone_malloc`` slab (``device_gpu.h:240-243``) as one unit that knows
neither JAX programs nor tasks: a dual LRU of resident ``Data`` (clean,
and dirty = owned here), byte accounting against a budget, and eviction
— clean tiles first, then dirty ones, a BATCH at a time: as many victims
as the room asked for takes, those of which the device holds the only
valid copy written back together first (one wait a batch, not a round
trip a tile) by a callable handed in at construction.  "Allocation" is
accounting: PJRT owns the real placement.

**Pins** keep the accounting honest when the matrix is larger than the
budget (``PERF.md`` §6, PR 30): a tile staged for the chunk in flight,
or prestaged by the transfer lane for the next batch, is no victim until
its chunk is committed (:meth:`~Residency.pin` /
:meth:`~Residency.unpin`): room for one tile of a chunk is never made at
the expense of its neighbour.  When everything left is pinned,
:meth:`~Residency.reserve` says so (``reserve_gave_up``, warned once)
and the staging walk fails the chunk loudly instead of running past the
budget.  What the accounting does NOT see: a slot is free the moment a
copy is evicted or rebound, but PJRT keeps the buffer until the last
program that reads it has run.  Nothing here waits for that: on the chip
PJRT holds an allocation back until programs in flight have freed the
memory (the out-of-core cell peaks at 16.84 of 16.91 GB and completes,
my chip run, PR 30), and runs out of memory loudly if it ever cannot.
What it DOES see since PR 45: a scratch tile let go with its last reader
(:meth:`~Residency.release` with ``after``) stays charged until the chip
has run that reader's program.  A pump that leads the chip would
otherwise hold a generation of them a sweep of its lead, all charged to
nobody; now the room a program's outputs take is made before its call
(:meth:`~Residency.wait_for`) out of what costs nobody a copy, and
waited for (``parsec-wait:chip_lead``, ``lead_waits``) where that is
not enough: the most the device holds is the budget again.

Which accounting a device has is decided ONCE, in the constructor: the
native zone allocator (alignment and fragmentation modelled for real —
an allocation can fail under budget and evict) or a byte counter.
Callers see :meth:`~Residency.account` / :meth:`~Residency.free` /
:meth:`~Residency.settle` / :meth:`~Residency.clear` and never ask which.

**The victim order** (``PERF.md`` §6, PR 33).  Age says little about a
DAG: a panel tile of a right-looking factorization is read by every
update of its step and never again, while a trailing tile touched one
step ago is needed at the next.  Whoever touches a tile may say when it
is read NEXT (:meth:`~Residency.next_uses`: the rank of its next reader
in the order its pool's tasks run, ``dsl/attach_plan.py``, or
:data:`NEVER`).  Victims are then, among the unpinned tiles: those never
read again, then those nobody said anything about, then the known ones,
the farthest reader first; ties in the order above (oldest first, clean
before dirty).  Where nobody says anything (a ``Context``-path pool, a
pool without a stored plan) that IS the order above.  A tile of unknown
use goes before every known one: what is known of a tile is that a task
of the running pool WILL read it.

``lock`` is the residency lock: LRU and accounting mutations are not
single-threaded once the transfer lane prestages wave N+1 while the
pump thread commits wave N.  RLock — the stage/evict/account paths
nest.  Order: the device's ``_lock`` -> ``lock`` -> ``Data.lock``; the
write-back committer takes only ``Data.lock``, so an eviction writing
its victims home under ``lock`` cannot deadlock against it.

**An eviction and the lock** (``PERF.md`` §6, PR 35).  A copy home is
waited for by the thread that needs its room, with no lock held that
the pump's commit takes.  Whoever comes WITHOUT the lock (the transfer
lane: :meth:`~Residency.make_room`) evicts in two holds of it: the
victims are chosen under the first (out of the LRUs, so nobody picks
them twice; still accounted, so the budget holds), go home with the
lock FREE, and drop under the second, each only if nobody staged,
pinned or rewrote it meanwhile and its host copy stands
(``evict_cancelled`` counts the others, which stay).  Whoever holds
the lock around a whole walk (the pump's own staging walk, a commit's
:meth:`~Residency.settle`, the synchronous regime) evicts inside that
hold, as ever: an RLock cannot be let go from inside, and nobody waits
for those.  So the write-back callable is NOT promised the lock.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import threading
from typing import (Any, Callable, Dict, Iterable, List, MutableMapping,
                    Optional, Tuple)

from ..data.data import Coherency, Data
from ..profiling import pins
from ..utils import debug

#: dtype -> its name, as ``stats["tiles_by_dtype"]`` spells it
_DTYPE_NAMES: Dict[Any, str] = {None: "?"}

#: Declared capability, for whoever must refuse a program without it
#: (``benchmark/drivers/pump_ooc.py``): a taskpool whose tiles exceed the
#: device's budget runs to its end in bounded device AND host memory —
#: victims leave a batch at a time, a chunk's tiles are pinned while it
#: is staged, and a copy home leaves neither a cached host value beside
#: the resident tile nor a landing copy (``staging.HostWriter._alias``)
OUT_OF_CORE = True

#: a next use: no task reads the tile's version here again
NEVER = 1 << 62
#: ... and: nobody knows (below every rank)
UNKNOWN = -1


def native_zone(platform: str) -> bool:
    """Which accounting a device on ``platform`` gets: True for the
    native zone allocator, wherever the native core is built."""
    from .. import native

    built = native.available()
    if not built and platform == "tpu":
        # on the CPU backend byte-counter accounting stands in (the
        # native-off CI leg); on a chip the allocator missing is a
        # broken installation
        raise RuntimeError(
            "a TPU's HBM is accounted in the native zone allocator, but "
            f"the native core is unavailable: {native.build_error()}")
    return built


@dataclasses.dataclass
class _Leaving:
    """The victims of one eviction between their choice and their drop."""

    #: (tile, was dirty, the version of its copy here or -1 for none,
    #: the bytes that go home for it: 0 where its host copy stands)
    victims: List[Tuple[Data, bool, int, int]]
    #: victims whose next use somebody had said; of them, never again
    known: int = 0
    never: int = 0

    @property
    def home(self) -> List[Data]:
        """The victims whose copy here is the only valid one: they go
        home, as one batch, before anything drops."""
        return [v for v, _dirty, _version, nbytes in self.victims if nbytes]


class Residency:
    """The resident tiles of one device and their bytes."""

    def __init__(self, data_index: int, budget: int,
                 stats: MutableMapping[str, int],
                 writeback: Callable[[List[Data]], int], zone: bool = False,
                 span: Callable[..., Any] = None):
        """``data_index``: the device's slot in ``Data.copies``;
        ``writeback(victims)``: bring the victims' device copies home, as
        one batch, before they drop, and return the microseconds it
        waited (called with the lock held or free: the module's "An
        eviction and the lock"); ``stats``: where ``evictions`` and
        their kin are counted; ``zone``: account in the native zone allocator
        (:func:`native_zone`); ``span``: what opens a span of the device
        (``dev:evict``)."""
        self.index = data_index
        self.stats = stats
        for k in ("evictions", "evict_clean", "evict_dirty",
                  "evict_bytes_home", "evict_batches", "evict_next_use",
                  "evict_never_again", "evict_cancelled", "restaged_tiles",
                  "reserve_gave_up", "unaccounted_tiles", "lead_waits",
                  "handed_restaged", "owed_home_bytes"):
            stats.setdefault(k, 0)
        self._span = span or (lambda name, **info: contextlib.nullcontext())
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
        #: the charges by the tiles' precision, and the most that was
        #: ever charged since the last :meth:`clear`: at each new most,
        #: ``stats["tiles_by_dtype"]`` takes a snapshot (dtype name ->
        #: bytes resident at the peak; it outlives the clear, for whoever
        #: reads a solve's peak after the detach)
        self._by_dtype: Dict[str, int] = {}
        self._charges = self._most = 0
        stats.setdefault("tiles_by_dtype", {})
        #: data_id -> how many stagings hold the tile: no victim
        self._pins: Dict[int, int] = {}
        #: data_ids of an eviction's victims while they go home with the
        #: lock free (:meth:`make_room`): their copies run through an
        #: alias of the array, which nobody may donate meanwhile
        self.going_home: set = set()
        #: data_id -> the rank of the tile's next reader, or
        #: :data:`NEVER`; absent: unknown.  Only ever raised while the
        #: copy stays (the readers of a tile may run out of rank order:
        #: the one staged last must not bring an earlier reader back),
        #: forgotten with the copy
        self._next: Dict[int, int] = {}
        #: tiles an eviction dropped, until they are staged in again
        #: (``restaged_tiles``)
        self._evicted: set = set()
        #: between the pools of a compound (``TpuDevice.pool_boundary``):
        #: the ids of the tiles that a pool which has ENDED wrote (one
        #: staged in from the host again counts in ``handed_restaged``),
        #: and of the tiles that a pool still to come rewrites (a copy
        #: home of one counts its bytes in ``owed_home_bytes``: the
        #: version is not a result)
        self.handed: frozenset = frozenset()
        self.owed: frozenset = frozenset()
        self._warned: set = set()
        #: scratch tiles let go while the program that read them last may
        #: still run, oldest first: [an output of that program, the
        #: bytes still charged for them, their slots in the zone]
        self._limbo: "collections.deque[List[Any]]" = collections.deque()
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
            self._retire(0, ended=True)  # (their slots are the old zone's)
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
    def _in_use(self) -> int:
        return self.used if self.zone is None else self.zone.used

    def _charged(self, data: Data, old: int, new: int) -> None:
        """``data``'s charge went from ``old`` to ``new`` bytes (the
        caller holds the lock): the same by its precision, and the
        snapshot at a new peak."""
        if new == old:
            return
        name = _DTYPE_NAMES.get(data.dtype)
        if name is None:  # (numpy spells a dtype's name in Python: once)
            name = _DTYPE_NAMES[data.dtype] = str(data.dtype)
        by = self._by_dtype
        by[name] = by.get(name, 0) + new - old
        self._charges += new - old
        if self._charges > self._most:
            self._most = self._charges
            self.stats["tiles_by_dtype"] = {k: v for k, v in by.items() if v}

    def account(self, data: Data, nbytes: int) -> bool:
        """(Re)account ``data``'s slot at ``nbytes``, evicting for
        space.  The same bytes rebound (an epilog's output over its
        input) keep the slot: nothing is allocated, nobody is evicted.
        False when the room could not be made (everything left is
        pinned; counted by :meth:`reserve`) or, with the zone, when no
        slot could be had at all (``unaccounted_tiles``): the tile is
        then resident and charged to nobody."""
        did = data.data_id
        with self.lock:
            if nbytes > 0 and self._held.get(did, 0) == nbytes:
                return True
            # the allocatee must not be its own eviction victim (either
            # accounting): callers re-touch the LRU right after
            self.forget(data)
            old = self._held.pop(did, 0)
            if self.zone is None:
                ok = self.reserve(max(0, nbytes - old))
                self.used += nbytes - old
                if nbytes > 0:
                    self._held[did] = nbytes
                self._charged(data, old, nbytes)
                return ok
            off = self._offsets.pop(did, None)
            if off is not None:
                self.zone.release(off)
            ok = True
            if nbytes > 0:
                ok = self.reserve(nbytes)
                off = self.zone.alloc(nbytes)
                # (under the budget and no slot: the zone is fragmented;
                # one more batch of victims a try)
                while off is None and self._evict(nbytes):
                    off = self.zone.alloc(nbytes)
                if off is not None:
                    self._held[did], self._offsets[did] = nbytes, off
                else:
                    ok = False
                    self.stats["unaccounted_tiles"] += 1
                    self._warn_once(
                        "unaccounted", "no slot of %d bytes for %r in the "
                        "zone (%d of %d used): the tile stays resident "
                        "and unaccounted", nbytes, data, self.zone.used,
                        self._budget)
            self.used = self.zone.used
            self._charged(data, old, self._held.get(did, 0))
            return ok

    def free(self, data: Data) -> None:
        """Release ``data``'s slot (none: a no-op, never an underflow)."""
        with self.lock:
            old = self._held.pop(data.data_id, 0)
            self._charged(data, old, 0)
            if self.zone is None:
                self.used -= old
                return
            off = self._offsets.pop(data.data_id, None)
            if off is not None:
                self.zone.release(off)
            self.used = self.zone.used

    def settle(self) -> None:
        """After commits grew residency: back under the budget (the zone
        already evicted while it allocated), and what the chip has let
        go of meanwhile is let go of here."""
        if self._limbo:
            self._retire(0)
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
            self._retire(0, ended=True)
            self.clean.clear()
            self.dirty.clear()
            if self.zone is not None:
                for off in self._offsets.values():
                    self.zone.release(off)
            self._offsets.clear()
            self._held.clear()
            self._by_dtype.clear()
            self._charges = self._most = 0
            self._pins.clear()
            self.going_home.clear()
            self._next.clear()
            self._evicted.clear()
            self.used = 0 if self.zone is None else self.zone.used

    # -- the LRUs ----------------------------------------------------------
    def touch(self, data: Data, *, dirty: bool) -> None:
        """``data`` was just used: newest of its LRU."""
        with self.lock:
            self.forget(data)
            (self.dirty if dirty else self.clean)[data.data_id] = data

    def next_uses(self, ranks: Dict[int, int]) -> None:
        """What the tasks being staged or committed know of their
        tiles, data_id -> the rank of the NEXT reader, :data:`NEVER`, or
        :data:`UNKNOWN`, which says nothing (the caller holds the lock).
        A rank is only raised: of two readers staged out of rank order
        the later one's answer stands."""
        nxt = self._next
        for did, rank in ranks.items():
            if rank > nxt.get(did, UNKNOWN):
                nxt[did] = rank

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

    # -- pins --------------------------------------------------------------
    @property
    def pins(self) -> Dict[int, int]:
        """data_id -> how many stagings pin the tile (read under the
        lock: who would donate a tile's array asks whether anybody but
        its own chunk holds it)."""
        return self._pins

    def pin(self, data: Data) -> None:
        """``data`` is staged for a chunk that has not been committed
        (or for the batch the lane runs ahead of): no victim until
        :meth:`unpin` (the caller holds the lock)."""
        did = data.data_id
        self._pins[did] = self._pins.get(did, 0) + 1

    def unpin(self, datas: Iterable[Data]) -> None:
        # (the one method here that the solve path calls WITHOUT the
        # lock: after a chunk, a batch, a lane's walk; taken as the
        # device module takes it, so that a wait for it is seen)
        with pins.held(self.lock, "res_lock"):
            pinned = self._pins
            for data in datas:
                did = data.data_id
                n = pinned.get(did, 0) - 1
                if n > 0:
                    pinned[did] = n
                else:
                    pinned.pop(did, None)

    @property
    def chunk_limit(self) -> int:
        """Bytes of tiles one device program may BRING onto the device:
        a sixteenth of the budget, so that a chunk's pins, the lane's
        and the outputs in flight together leave the budget most of its
        room (a 64-task gemm wave of 16 MiB tiles is 4 GiB).  Counted
        (``TpuDevice._submit_wave``): every tile a task reads that has a
        home, resident at the moment or not, and every tile it writes,
        donated or not; not counted: a tile read, and not written, that
        was born on this device (a scratch tile, a tile of a collection
        born here) and is here still, which is in the accounting
        already."""
        return self._budget // 16

    # -- making room -------------------------------------------------------
    def _warn_once(self, what: str, msg: str, *args) -> None:
        if what not in self._warned:
            self._warned.add(what)
            debug.warning(msg, *args)

    def reserve(self, nbytes: int) -> bool:
        """Make room for ``nbytes`` under the budget: one batch of
        victims, clean first, then dirty ones written back together
        (reference device_gpu.c:978-1120 retry/evict loops).  False when
        the room is not there and nothing is left to evict (every
        resident tile is pinned, or the budget is smaller than what one
        chunk needs): counted in ``reserve_gave_up`` and warned once; the
        staging walk fails its chunk on it, a commit (whose outputs
        exist already) goes on over the budget."""
        with self.lock:
            need = self._in_use() + nbytes - self._budget
            if need > 0 and self._limbo:
                need = self._free_room(need)
            if need <= 0:
                return True
            self._evict(need)
            if self._in_use() + nbytes <= self._budget:
                return True
            self.stats["reserve_gave_up"] += 1
            self._warn_once(
                "gave_up", "residency: no room for %d bytes (%d of %d in "
                "use, %d tiles pinned, nothing evictable)", nbytes,
                self._in_use(), self._budget, len(self._pins))
            return False

    def wait_for(self, nbytes: int) -> None:
        """Before a program that brings ``nbytes`` onto the device is
        called: where scratch tiles let go are still charged, the room
        is had for free or waited for (:meth:`_free_room`), so that the
        outputs PJRT allocates in the call find it; nobody who would
        have to go home or come back is evicted here.  (Without such
        tiles: nothing; a commit makes the room, as ever.)"""
        if self._limbo:
            with pins.held(self.lock, "res_lock"):
                self._free_room(self._in_use() + nbytes - self._budget)

    def _free_room(self, need: int) -> int:
        """Room that costs nobody a copy, while scratch tiles let go are
        still charged behind programs in flight (the caller holds the
        lock): what the chip has let go of meanwhile; then the clean
        tiles that no task reads again (generation 0 of a stencil); then
        what the chip lets go of next, WAITED for.  Returns the bytes
        still missing."""
        need = self._retire(need, wait=False)
        if need > 0 and self.clean:
            before = self._in_use()
            self._evict(need, spent=True)
            need -= before - self._in_use()
        return self._retire(need) if need > 0 else need

    def _retire(self, need: int, wait: bool = True,
                ended: bool = False) -> int:
        """Free the slots of the scratch tiles whose last reader's
        program the chip has run (the caller holds the lock), oldest
        first: a device runs its programs in the order of their calls.
        With ``need`` > 0 and ``wait`` the thread WAITS for the programs
        still running (``lead_waits``, a ``parsec-wait:chip_lead``
        event) until that many bytes are free or nothing is left to wait
        for; returns what is still missing.  ``ended``: every program
        has (a detach).  An output that a later program was given to
        write in place can no longer be asked: its tiles count as gone,
        as every tile let go did before PR 45."""
        limbo = self._limbo
        while limbo:
            after, nbytes, offs = limbo[0]
            try:
                if not (ended or after.is_deleted() or after.is_ready()):
                    if need <= 0 or not wait:
                        break
                    self.stats["lead_waits"] += 1
                    with pins.wait("chip_lead", need=need):
                        after.block_until_ready()
            except Exception as e:  # (it has ended: its commit says how)
                debug.verbose(3, "device", "the program behind %d bytes let "
                              "go failed: %s", nbytes, e)
            limbo.popleft()
            if self.zone is None:
                self.used -= nbytes
            else:
                for off in offs:
                    self.zone.release(off)
                self.used = self.zone.used
            need -= nbytes
        return need

    def _victims(self, need: int,
                 spent: bool = False) -> List[Tuple[Data, bool]]:
        """Out of the LRUs, unpinned, until their slots cover ``need``
        bytes, as ``(tile, was dirty)``: oldest first, clean before
        dirty; and where next uses are known, in that order those never
        read again, those of unknown use, then the known ones, the
        farthest reader first (the sort is stable).  ``spent``: only the
        clean tiles that somebody said are never read again."""
        pins, held, nxt = self._pins, self._held, self._next
        if spent:
            order = [(did, self.clean) for did in self.clean
                     if did not in pins and nxt.get(did) == NEVER]
        else:
            order = [(did, lru) for lru in (self.clean, self.dirty)
                     for did in lru if did not in pins]
        if nxt and not spent:
            unknown = NEVER - 1
            order.sort(key=lambda v: -nxt.get(v[0], unknown))
        out: List[Tuple[Data, bool]] = []
        for did, lru in order:
            if need <= 0:
                break
            out.append((lru.pop(did), lru is self.dirty))
            need -= held.get(did, 0)
        return out

    def make_room(self, nbytes: int) -> None:
        """One eviction towards ``nbytes`` of room for a caller that
        comes WITHOUT the lock and will account what it brings under a
        hold of its own (the transfer lane, ``StageIn.batch``): the
        victims go home with the lock free, so that the pump's commit
        does not wait for their copies (the module's "An eviction and
        the lock").  Advisory: what was cancelled, or taken by somebody
        else meanwhile, the caller's :meth:`reserve` makes up for under
        its hold, which is the one more round, and the one that gives
        up."""
        with pins.held(self.lock, "res_lock"):
            need = self._in_use() + nbytes - self._budget
            leaving = self._leaving(need) if need > 0 else None
            if leaving is not None:
                home = leaving.home
                self.going_home.update(v.data_id for v in home)
        if leaving is None:
            return
        with self._span("dev:evict", need=need) as sp:
            wait_us = 0
            try:
                if home:
                    wait_us = self._writeback(home)
            finally:
                # (also after a write-back that raised: the victims are
                # in no LRU, and what did not get home stays)
                with pins.held(self.lock, "res_lock"):
                    self.going_home.difference_update(
                        v.data_id for v in home)
                    self._leave(leaving, wait_us, sp, check=True)

    def _evict(self, need: int, spent: bool = False) -> bool:
        """One batch of victims for ``need`` bytes (the caller holds the
        lock, and keeps it): those whose copy here is the only valid one
        go home first, together, then every victim drops.  A
        ``dev:evict`` span with ``victims``, ``dirty`` (written home),
        ``bytes_home``, ``wait_us`` (of the write-back's one wait),
        ``known`` (victims whose next use somebody had said), ``never``
        (of them, those with no reader left) and ``cancelled`` (victims
        that stayed: only where the lock was free meanwhile,
        :meth:`make_room`).  ``spent``: of :meth:`_victims`.  False: no
        victim."""
        leaving = self._leaving(need, spent)
        if leaving is None:
            return False
        home = leaving.home
        with self._span("dev:evict", need=need) as sp:
            wait_us = self._writeback(home) if home else 0
            self._leave(leaving, wait_us, sp, check=False)
        return True

    def _leaving(self, need: int,
                 spent: bool = False) -> Optional["_Leaving"]:
        """The first half of an eviction (the caller holds the lock):
        the victims for ``need`` bytes, each with the version its copy
        here has, and those of them that have to go home first.  None:
        no victim."""
        victims = self._victims(need, spent)
        if not victims:
            return None
        idx = self.index
        out = _Leaving([])
        for victim, dirty in victims:
            use = self._next.get(victim.data_id, UNKNOWN)
            out.known += use >= 0
            out.never += use == NEVER
            mine = victim.get_copy(idx)
            if mine is None or mine.payload is None:
                out.victims.append((victim, dirty, -1, 0))
                continue
            host = victim.get_copy(0)
            # a CLEAN device copy can still be the ONLY valid copy:
            # device-native arrivals (_deposit_payload, bytes_d2d)
            # attach no host copy — dropping without write-back
            # would destroy the data.  A clean copy of a PEER module's
            # version is not that: the peer holds the version (or a newer
            # one) and brings it home itself
            goes = dirty or ((host is None or host.payload is None
                              or host.version < mine.version)
                             and not self._held_by_peer(victim, mine))
            out.victims.append((victim, dirty, mine.version,
                                mine.nbytes if goes else 0))
        return out

    def _stays(self, victim: Data, version: int) -> bool:
        """Whether a victim that went home with the lock free is no
        longer this eviction's to drop (the caller holds the lock):
        somebody staged, warmed or rewrote it meanwhile (it is back in
        an LRU) or pinned it; its copy here is not the one that went
        home, or is gone (released by its last user, or staged, let go
        and evicted by a walk that held the lock: counted there); or its
        host copy does not stand at that version, and the copy here is
        still the only valid one."""
        did = victim.data_id
        if did in self._pins or did in self.clean or did in self.dirty:
            return True
        mine = victim.get_copy(self.index)
        host = victim.get_copy(0)
        return mine is None or mine.payload is None \
            or mine.version != version or host is None \
            or host.payload is None or host.version < version

    def _leave(self, leaving: "_Leaving", wait_us: int, sp,
               check: bool) -> None:
        """The second half of an eviction (the caller holds the lock):
        the victims drop — with ``check`` (the lock was free
        meanwhile), those that :meth:`_stays` lets go; the others are
        counted, and one that is resident and in no LRU goes back as the
        oldest of its own — and the span and the counters say what
        happened."""
        cancelled = left_clean = left_dirty = bytes_left = bytes_home = 0
        went_home = 0
        back: List[Tuple[Data, bool]] = []
        for victim, dirty, version, nbytes in leaving.victims:
            bytes_home += nbytes
            went_home += bool(nbytes)
            if check and self._stays(victim, version):
                cancelled += 1
                did = victim.data_id
                if did not in self.clean and did not in self.dirty \
                        and victim.get_copy(self.index) is not None:
                    back.append((victim, dirty))
                continue
            if nbytes:
                left_dirty += 1
                bytes_left += nbytes
            else:
                left_clean += 1
            self.drop(victim)
        for victim, dirty in reversed(back):  # (in their old order)
            lru = self.dirty if dirty else self.clean
            lru[victim.data_id] = victim
            lru.move_to_end(victim.data_id, last=False)
        # the counters are of the victims that LEFT (``evict_clean`` +
        # ``evict_dirty`` = ``evictions``), the span is of what was
        # chosen and what went over the link; ``cancelled`` is between
        stats = self.stats
        stats["evict_batches"] += 1
        stats["evict_clean"] += left_clean
        stats["evict_dirty"] += left_dirty
        stats["evict_bytes_home"] += bytes_left
        stats["evict_next_use"] += leaving.known
        stats["evict_never_again"] += leaving.never
        stats["evict_cancelled"] += cancelled
        if sp is not None:
            sp.note(victims=len(leaving.victims), dirty=went_home,
                    bytes_home=bytes_home, wait_us=wait_us,
                    known=leaving.known, never=leaving.never,
                    cancelled=cancelled)

    def _held_by_peer(self, data: Data, mine) -> bool:
        """Whether another device module holds ``data`` at ``mine``'s
        version or a newer one (the caller holds the lock)."""
        idx = self.index
        with data.lock:
            return any(di not in (0, idx) and c.payload is not None
                       and c.version >= mine.version
                       for di, c in data.copies.items())

    def drop_stale(self, datas: Iterable[Data]) -> int:
        """A peer module committed new versions of ``datas``: the copies
        here that are older are dropped, out of the LRUs, their slots
        freed, nothing written home (the peer's version supersedes them
        and finds its own way home); counted in ``peer_copies_dropped``.
        A program of this device that was given such a copy's array keeps
        the array; the pin of its chunk lets go of nothing.  Called with
        no lock of the peer's held."""
        n = 0
        with pins.held(self.lock, "res_lock"):
            for data in datas:
                mine = data.get_copy(self.index)
                if mine is None:
                    continue
                newest = data.newest_copy()
                if newest is None or newest.version <= mine.version:
                    continue
                self.forget(data)
                self.drop(data, evicted=False)
                n += 1
            if n:
                self.stats["peer_copies_dropped"] = \
                    self.stats.get("peer_copies_dropped", 0) + n
        return n

    def restaged(self, data: Data) -> None:
        """``data`` is staged in (the caller holds the lock): counted,
        once, when an eviction had dropped it (``restaged_tiles``)."""
        if data.data_id in self._evicted:
            self._evicted.discard(data.data_id)
            self.stats["restaged_tiles"] += 1
        if data.data_id in self.handed:
            self.stats["handed_restaged"] += 1

    def owed_home(self, datas: Iterable[Data]) -> None:
        """``datas`` are about to be copied home: the bytes of those that
        a later pool of the compound rewrites (``owed``) are counted."""
        owed = self.owed
        if owed:
            self.stats["owed_home_bytes"] += sum(
                self._held.get(d.data_id, 0) for d in datas
                if d.data_id in owed)

    def drop(self, data: Data, *, evicted: bool = True) -> None:
        """Detach ``data``'s copy here and release its slot."""
        with self.lock:
            c = data.detach_copy(self.index)
            self._next.pop(data.data_id, None)
            if c is not None:
                self.free(data)
                if evicted:
                    self.stats["evictions"] += 1
                    self._evicted.add(data.data_id)

    def release(self, data: Data, after: Any = None) -> None:
        """Hand ``data``'s copy on WITHOUT a write-back and without
        counting an eviction: out of the LRUs, detached, its slot freed
        (``drop_residency``; a scratch tile's last user).  ``after``: an
        output of the device program that read the tile last (anything
        with ``is_ready`` / ``block_until_ready`` / ``is_deleted``, as a
        ``jax.Array``): PJRT keeps the buffer until that program has
        run, so the slot stays charged until then (:meth:`_retire`)."""
        with self.lock:
            self.forget(data)
            did = data.data_id
            nbytes = self._held.get(did, 0) if after is not None else 0
            if not nbytes:
                self.drop(data, evicted=False)
                return
            data.detach_copy(self.index)
            self._next.pop(did, None)
            del self._held[did]
            self._charged(data, nbytes, 0)
            off = self._offsets.pop(did, None)
            limbo = self._limbo
            if not limbo or limbo[-1][0] is not after:
                limbo.append([after, 0, []])
            limbo[-1][1] += nbytes
            if off is not None:
                limbo[-1][2].append(off)
