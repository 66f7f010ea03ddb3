"""Host<->device staging: every byte that moves between the host and one
device moves here, synchronously or on the pipeline's two threads.

* :class:`StageIn` — the host->device half: decide, make room, put,
  attach, for one tile or for a batch in one coalesced ``device_put``.
* :class:`HostWriter` — the write-back halves: version-guarded snapshot,
  device->host copies STARTED for a whole batch before one is waited
  for (or earlier, at hand-over, for a version known to be the last),
  one wait, guarded commit of the host copy.
* :class:`StageLane` — a dedicated transfer thread the native pump
  hands the NEXT ready batch to while the current wave computes, so by
  the time the pump submits the batch every plain input is a residency
  hit.  Bounded by ``runtime_stage_depth`` (1 = synchronous, 2 =
  double-buffered default).
* :class:`WritebackCommitter` — a background thread draining deferred
  write-backs.  Completed outputs enqueue at the commit (deduplicated
  per tile, so a re-dirtied tile goes home ONCE, at its newest version);
  it drains in batched gets when the pending-bytes watermark
  (``runtime_wb_window_mb``) is crossed, on :meth:`~WritebackCommitter.
  kick` (a last version has no later one to wait for), or at the
  :meth:`~WritebackCommitter.flush` barrier ``detach()``/redistribute/
  remote sends take.  (An eviction does not wait for it: it writes its
  victims home itself, a batch at a time, ``HostWriter.writeback_batch``
  on the thread that needs the room.)  A drain that somebody
  waits for, or of last versions, starts every copy before it collects
  one; the watermark's own drain keeps a round trip a tile, its rate
  being what bounds the bytes of versions still to be superseded.  The
  version guard makes
  a stale commit safe to drop, so the committer never takes the device
  residency lock — commits are pure Data-level operations and cannot
  deadlock against an eviction that holds it.

The lock order is ``residency.py``'s: the device's ``_lock`` -> the
residency lock -> ``Data.lock``.  On the solve path the residency lock
is taken with ``pins.held(res.lock, "res_lock")``, here and in the
device module: the same acquisition, which in a profiler session leaves
a ``wait:res_lock`` event, naming the holder's span, wherever a thread
had to wait for it (``docs/TRACING.md`` "Waits").  A bare ``with
res.lock:`` on that path is the exception: whoever waits behind it
waits unseen, and whoever waits for it names no holder.

A committer failure is STICKY: the stored exception re-raises on the
next ``enqueue`` (failing the task pool through the device layer's
fail-loudly discipline) and on ``flush`` (failing ``detach()``), so a
dead committer surfaces as a pool failure, never a silent hang.  The
watchdog counts :meth:`WritebackCommitter.drained` in its progress
epoch and diagnoses a wedged committer as finding OBS011.

Nothing here imports the device module or calls into it: what it needs
of a device (its residency, its counters, its span maker) is handed in.
"""

from __future__ import annotations

import collections
import itertools
import mmap
import threading
import time
from typing import Any, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..data.data import Coherency, Data
from ..profiling import pins
from ..utils import debug, mca_param

#: max tiles per committer drain batch (its D2H copies collected in one
#: wait)
WB_BATCH = 32

#: process-wide span ids for STAGE_IN/WRITEBACK begin/end pairing
_SPAN_SEQ = itertools.count(1)


def span_id() -> int:
    """The ``id`` of the next ``dev:stage_in`` / ``dev:writeback`` span."""
    return next(_SPAN_SEQ)


def stage_depth_param() -> int:
    """The pipeline depth knob, shared by the device layer and the
    native pump: number of ready batches in flight in the prefetch
    window.  1 disables the pipeline entirely (synchronous transfers,
    no committer — the A/B baseline); 2 is the double-buffered
    default."""
    return max(1, int(mca_param.register(
        "runtime", "stage_depth", 2,
        help="host<->device staging pipeline depth: ready batches in "
             "flight in the prefetch window; also gates the async "
             "write-back committer (1 = synchronous transfers, "
             "2 = double-buffered default)")))


class _StageJob:
    """One prestage request: a ready batch whose input tiles the lane
    stages while earlier waves compute."""

    __slots__ = ("batch", "seq", "tiles", "done", "error")

    def __init__(self, batch: List[Any], seq: int, tiles: List[Any]):
        self.batch = batch
        #: the batch's tiles that are not on the device yet
        #: (``TpuDevice.prestage_tiles``)
        self.tiles = tiles
        #: the pump's number for this batch (the lane's span carries it)
        self.seq = seq
        self.done = threading.Event()
        self.error: Optional[BaseException] = None

    def wait(self) -> None:
        """Block until the lane finished this batch.  Prestage errors
        are advisory — the submit path restages (and fails loudly)
        itself — so they are logged, not raised."""
        self.done.wait()
        if self.error is not None:
            debug.warning("prestage of %d tasks failed (%s); submit "
                          "path will restage", len(self.batch), self.error)


class StageLane:
    """Dedicated transfer lane: prestages ready batches' input tiles on
    its own thread so H2D puts overlap the compute of earlier waves."""

    def __init__(self, dev):
        self._dev = dev
        self._cv = threading.Condition()
        self._jobs: Deque[_StageJob] = collections.deque()
        self._stop = False
        self._thread = threading.Thread(
            target=self._run, name=f"stage-lane:{dev.name}", daemon=True)
        self._thread.start()

    def stage(self, batch: List[Any], seq: int,
              tiles: List[Any]) -> _StageJob:
        job = _StageJob(batch, seq, tiles)
        with self._cv:
            if self._stop:
                job.done.set()  # closed lane: submit path stages
                return job
            self._jobs.append(job)
            self._cv.notify()
        return job

    def _run(self) -> None:
        while True:
            with self._cv:
                while not self._jobs and not self._stop:
                    self._cv.wait()
                if not self._jobs and self._stop:
                    return
                job = self._jobs.popleft()
            try:
                self._dev.prestage_batch(job.batch, job.seq, job.tiles)
            except BaseException as e:  # must never kill the lane
                job.error = e
            finally:
                job.done.set()

    def close(self) -> None:
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        self._thread.join(timeout=30)
        # unblock any caller still parked on an undrained job
        with self._cv:
            while self._jobs:
                self._jobs.popleft().done.set()


class NoRoom(RuntimeError):
    """No room under the device's budget for one staging batch, and
    nothing left to evict: what is resident is pinned."""


def out_of_memory(e: BaseException) -> bool:
    """Whether ``e`` says the device has no memory left — PJRT's
    ``RESOURCE_EXHAUSTED``, or :class:`NoRoom` under the budget: nothing
    a slower path could cure."""
    return isinstance(e, NoRoom) or "RESOURCE_EXHAUSTED" in str(e)


def unalias(arr, x, guard, jdev):
    """Rerun a host->device transfer from a throwaway copy when the
    result aliases ``guard`` (shared by :func:`private_device_put` and
    the batched stage-in path — the guard contract must be identical
    whether a tile travelled alone or coalesced)."""
    plat = getattr(jdev, "platform", None)
    if plat is None:
        try:
            plat = arr.devices().pop().platform
        except Exception:
            plat = "cpu"  # unknown: err on the safe side
    if plat != "cpu":
        return arr
    try:
        if np.shares_memory(np.asarray(arr), guard):
            priv = np.array(np.asarray(x), copy=True)
            arr = jax.device_put(priv, jdev) if jdev is not None \
                else jnp.asarray(priv)
    except Exception:
        pass
    return arr


def private_device_put(x, jdev=None, *, guard=None):
    """``jax.device_put`` whose result is guaranteed NOT to alias
    ``guard`` (a host numpy array someone retains).  On the CPU backend
    PJRT zero-copies suitably-aligned host buffers, so a DONATED
    execution of the transferred array writes straight through the
    retained memory — the caller's reference matrix, or a version-v
    host copy whose bytes must outlive the bump to v+1.  Whether a
    given buffer zero-copies depends on its heap alignment, which makes
    the clobber a per-allocation coin flip (seen as a suite flake:
    the LU reconstruct test intermittently compared against its own
    overwritten input).  When aliasing is detected the transfer reruns
    from a throwaway copy — the only memory jax then aliases is
    jax-private.  Non-CPU platforms always copy host→HBM; the check is
    skipped there (``np.asarray`` on such arrays would be a D2H pull)."""
    arr = jax.device_put(x, jdev) if jdev is not None else jnp.asarray(x)
    if guard is None:
        return arr
    return unalias(arr, x, guard, jdev)


class StageIn:
    """The host->device half of one device — decide, make room, put,
    attach — into its residency (``device/residency.py``): the one
    implementation behind a chunk's batched put, the synchronous
    regime's put a tile, ``data_advise`` and the transfer lane."""

    def __init__(self, res, writer: "HostWriter", jdev, stats, span):
        """``res``: the device's :class:`~.residency.Residency`;
        ``writer``: its write-back halves; ``span``: what opens one of
        its spans (``dev:h2d``)."""
        self.res, self.writer, self.jdev = res, writer, jdev
        self.stats, self.span, self.index = stats, span, res.index

    def one(self, data, tally: Optional[List[int]] = None) -> Any:
        """Materialize the newest version of ``data`` on the device: the
        one-tile case of :meth:`batch`, with the residency lock held
        over the put (the synchronous regime's way, and
        ``data_advise``'s: nobody waits for it)."""
        got: Dict[int, Any] = {}
        with pins.held(self.res.lock, "res_lock"):
            self.batch((data,), tally, coalesce=False, got=got)
        return got[data.data_id]

    def batch(self, datas, tally: Optional[List[int]] = None,
              coalesce: bool = True,
              got: Optional[Dict[int, Any]] = None,
              keep: Optional[List[Data]] = None,
              unlocked: bool = False) -> int:
        """Resident tiles are touched, stale host-side tiles are
        coalesced into ONE ``jax.device_put`` call (one enqueue RPC for
        a wave's transfers instead of one per tile; ``coalesce=False``:
        a put a tile, as :meth:`one` asks), each result re-checked
        against the per-tile aliasing guard.  Returns bytes moved
        host->device, and in ``got`` each tile's array here by data_id;
        ``tally[0:2]`` count the tiles put and their bytes; the put is
        the ``dev:h2d`` span.

        The room for everything that moves is made ONCE, for the sum of
        the bytes (one batch of victims, ``Residency.reserve``), with
        every tile of the batch pinned: neither one that is resident
        already nor one just accounted makes room for its neighbour.
        The pins go with the call unless the caller takes them over
        (``keep``: it unpins once its chunk is committed, or its batch
        submitted).  No room (everything else is pinned too):
        :class:`NoRoom`.

        The residency lock is held to decide what moves and to make room
        for it, and again to attach what arrived — NOT over the put: the
        transfer lane's put of the next batch (10 ms for 27 tiles of 1
        MiB on a v5e) used to hold the pump's staging and epilog of the
        current one for its whole length (``PERF.md`` §6, PR 27).  A tile
        that somebody else staged or wrote in between keeps their copy.
        (The pump's own call, from the staging walk, holds the lock
        around all of it, as it always did: nobody waits for it.)

        Nor is it held while the victims of that room go home, where
        the caller says that it comes without the lock (``unlocked``:
        the transfer lane, the one caller that does): the eviction
        lets go of it between the choice of its victims and their drop
        (``Residency.make_room``), so that the pump's commit does not
        wait for up to 54 copies of 16 MiB (``PERF.md`` §6, PR 35).
        The room is then taken under the hold that accounts the tiles:
        what a cancelled victim or somebody else's staging left short
        is evicted there, as everybody else evicts."""
        moved = 0
        idx, res, jdev, stats = self.index, self.res, self.jdev, self.stats
        if got is None:
            got = {}
        #: (tile, its source copy's payload, that version, the index of
        #: the device the source lives on)
        moving: List[Tuple[Data, Any, int, int]] = []
        pinned: List[Data] = []
        #: tiles whose source is a peer module's array: held from the
        #: read of the reference until the landing is enqueued
        held: List[Data] = []
        try:
            with pins.held(res.lock, "res_lock"):
                for data in datas:
                    mine = data.get_copy(idx)
                    if mine is not None \
                            and getattr(mine, "staged_by", None) is not None:
                        # a custom-staged PACKED representation must never
                        # be served as the home layout: drop it and restage
                        # from the host copy (which :meth:`custom` flushed
                        # to the same version)
                        res.drop(data, evicted=False)
                        mine = None
                    newest = data.newest_copy()
                    if mine is not None and newest is not None \
                            and mine.version >= newest.version \
                            and mine.payload is not None:
                        res.touch(data,
                                  dirty=mine.coherency is Coherency.OWNED)
                        res.pin(data)
                        pinned.append(data)
                        got[data.data_id] = mine.payload
                        continue
                    if newest is None:
                        raise RuntimeError(
                            f"{data!r}: no valid copy to stage in")
                    # (at the newest version a copy on a device goes
                    # before the host's: chip to chip, never over the host)
                    src = data.hold_source(idx)
                    if src.device_index not in (0, idx):
                        held.append(data)
                    payload = src.payload
                    if not isinstance(payload, jax.Array):
                        payload = np.asarray(payload)
                    moving.append((data, payload, src.version,
                                   src.device_index))
            need = sum(p.nbytes for (_d, p, _v, _s) in moving)
            if need and unlocked:
                res.make_room(need)
            with pins.held(res.lock, "res_lock"):
                if need and not res.reserve(need):
                    raise NoRoom(
                        f"no room on the device for {len(moving)} tiles "
                        f"({need} bytes) of one staging batch: the budget "
                        f"is {res.budget} bytes and what is resident is "
                        "pinned by the chunk in flight")
                puts: List[Tuple[Data, np.ndarray, int]] = []
                #: source device -> the tiles that land from there
                lands: Dict[int, List[Tuple[Data, Any, int]]] = {}
                for data, payload, version, src in moving:
                    # (re-staging over a stale device copy replaces it:
                    # the accounting charges the delta)
                    res.account(data, payload.nbytes)
                    res.pin(data)
                    pinned.append(data)
                    if isinstance(payload, jax.Array):
                        lands.setdefault(src, []).append(
                            (data, payload, version))
                    else:
                        puts.append((data, payload, version))
                for src, tiles in lands.items():
                    moved += self._land(src, tiles, got)
            self._let_go(held)
            if puts:
                moved += self._put(puts, tally, coalesce, got)
        except BaseException:
            self._let_go(held)
            res.unpin(pinned)
            raise
        if keep is None:
            res.unpin(pinned)
        else:
            keep.extend(pinned)
        return moved

    @staticmethod
    def _let_go(held: List[Data]) -> None:
        """The peer arrays a walk held are enqueued, or given up."""
        while held:
            held.pop().release_source()

    def _land(self, src: int, tiles, got) -> int:
        """The device-resident arrivals of one staging walk from one
        source — a peer module's newest copies (``src``: its index), or a
        device-capable fabric's arrivals from another rank (0) — landed
        with a direct ``jax.device_put``: device-to-device, ICI-class on
        multi-chip, no host numpy bounce (SURVEY §5.8), asynchronous (the
        landing of a program's output that is not computed yet chains
        behind it).  A peer module's are one ``dev:d2d`` span with
        ``src``, ``tiles``, ``bytes``; all are counted in ``bytes_d2d``
        and ``d2d_tiles``.  The caller holds the residency lock and, of a
        peer's arrays, the tiles' holds (``Data.hold_source``)."""
        idx, res, stats = self.index, self.res, self.stats
        nbytes = sum(p.nbytes for (_d, p, _v) in tiles)
        if src:
            with self.span("dev:d2d", src=src, tiles=len(tiles),
                           bytes=nbytes):
                arrs = [jax.device_put(p, self.jdev) for (_d, p, _v) in tiles]
        else:
            arrs = [jax.device_put(p, self.jdev) for (_d, p, _v) in tiles]
        for (data, _p, version), arr in zip(tiles, arrs):
            got[data.data_id] = arr
            c = data.attach_copy(idx, arr)
            c.version = version
            res.touch(data, dirty=False)
        stats["bytes_d2d"] += nbytes
        stats["d2d_tiles"] += len(tiles)
        return nbytes

    def _put(self, puts, tally, coalesce: bool, got) -> int:
        """The put of :meth:`batch` and the attach of what arrived."""
        idx, res, jdev, stats = self.index, self.res, self.jdev, self.stats
        moved = 0
        nbytes = sum(h.nbytes for (_d, h, _v) in puts)
        try:
            with self.span("dev:h2d", tiles=len(puts), bytes=nbytes):
                hosts = [h for (_d, h, _v) in puts]
                arrs = None
                if coalesce:
                    try:
                        arrs = jax.device_put(hosts, jdev)
                    except Exception as e:
                        if out_of_memory(e):
                            raise  # no slower path has more memory
                        # backend rejected the coalesced put: per tile
                        stats["stage_batch_fallbacks"] += 1
                # guard: the host copy RETAINS each buffer at version v —
                # a zero-copy put followed by a donating task would
                # overwrite it in place while its version still claims v
                if arrs is None:
                    arrs = [private_device_put(h, jdev, guard=h)
                            for h in hosts]
                else:
                    arrs = [unalias(a, h, h, jdev)
                            for a, h in zip(arrs, hosts)]
        except BaseException:
            # the room made for what never arrived
            with pins.held(res.lock, "res_lock"):
                for (data, _h, _v) in puts:
                    mine = data.get_copy(idx)
                    if mine is None or mine.payload is None:
                        res.free(data)
            raise
        if tally is not None:
            tally[0] += len(puts)
            tally[1] += nbytes
        with pins.held(res.lock, "res_lock"):
            for (data, host, ver), arr in zip(puts, arrs):
                stats["bytes_in"] += host.nbytes
                if data.scratch is not None:
                    stats["scratch_bytes_in"] += host.nbytes
                moved += host.nbytes
                res.restaged(data)
                mine = data.get_copy(idx)
                if mine is not None and mine.payload is not None \
                        and mine.version >= ver \
                        and getattr(mine, "staged_by", None) is None:
                    # staged or written meanwhile: theirs stands
                    got[data.data_id] = mine.payload
                    continue
                c = data.attach_copy(idx, arr)
                c.version = ver
                got[data.data_id] = arr
                res.touch(data, dirty=False)
            if coalesce:
                stats["stage_batched_puts"] = \
                    stats.get("stage_batched_puts", 0) + 1
                stats["stage_batched_tiles"] = \
                    stats.get("stage_batched_tiles", 0) + len(puts)
        return moved

    def custom(self, data, hook, owner) -> Any:
        """Stage via a user hook: ``hook(data, owner) -> jax.Array`` (``owner``:
        the device module).
        The hook's result becomes the flow's device copy (the reference's
        stage_in writes into the GPU copy buffer the same way); residency
        is accounted at the STAGED size, which may differ from the home
        tile's (packed subtile)."""
        with pins.held(self.res.lock, "res_lock"):
            mine = data.get_copy(self.index)
            newest = data.newest_copy()
            if mine is not None and newest is not None \
                    and mine.version >= newest.version and mine.payload is not None \
                    and getattr(mine, "staged_by", None) is hook:
                # reusable ONLY if this same hook produced it: a current
                # device copy staged by the default path (prefetch, a prior
                # epilog) holds the HOME representation, not the packed one
                self.res.touch(data, dirty=mine.coherency is Coherency.OWNED)
                return mine.payload
            if mine is not None and mine.payload is not None \
                    and getattr(mine, "staged_by", None) is None:
                host = data.get_copy(0)
                if host is None or host.payload is None \
                        or host.version < mine.version:
                    # the device copy is the ONLY up-to-date home-layout
                    # replica: flush it home BEFORE the packed staging
                    # replaces it, or that data exists nowhere (and the
                    # hook itself typically reads the host copy).  A
                    # deferred commit may still be pending for this tile —
                    # the synchronous flush lands the same version first
                    # and the committer's guarded commit drops as stale.
                    self.writer.writeback(data)
            arr = hook(data, owner)
            self.res.account(data, arr.nbytes)
            arr = jax.device_put(arr, self.jdev)
            self.stats["bytes_in"] += arr.nbytes
            self.stats["custom_stage_in"] = self.stats.get("custom_stage_in", 0) + 1
            c = data.attach_copy(self.index, arr)
            c.version = newest.version if newest is not None else 0
            c.staged_by = hook
            self.res.touch(data, dirty=False)
            return arr


def _fresh_zeros(shape, dtype) -> np.ndarray:
    """A writable array of zeros over a private anonymous mapping of its
    own: the kernel hands out zero pages when they are first touched, so
    making it touches none (3 us a 1 MiB tile).  ``np.zeros`` does that
    only while the allocator maps a block of this size afresh; once
    blocks of the size have been freed it recycles heap memory and
    clears it, 80-260 us a MiB on the thread that asks (my chip run,
    PR 44: ``dev:epilog`` paid 265 us a landed tile).  PRIVATE: Python's
    default for an anonymous map is shared memory, whose first read
    allocates every page (10 s for 4 GiB where this takes 1)."""
    nbytes = int(np.prod(shape)) * np.dtype(dtype).itemsize
    if not nbytes:
        return np.zeros(shape, dtype)
    block = mmap.mmap(-1, nbytes, flags=mmap.MAP_PRIVATE | mmap.MAP_ANONYMOUS)
    return np.frombuffer(block, dtype).reshape(shape)


def _start_copy(payload) -> bool:
    """Start the device->host copy of ``payload`` without waiting for it
    (``jax.Array.copy_to_host_async``: non-blocking, ordered behind the
    program that writes the array, a no-op once started).  False where
    there is nothing to start: a host array, or an array that a donating
    task consumed (the collect drops that one)."""
    begin = getattr(payload, "copy_to_host_async", None)
    if begin is None:
        return False
    try:
        begin()
    except RuntimeError:
        return False
    return True


class HostWriter:
    """The write-back halves of one device: snapshot a dirty device copy
    under the version guard, get it (alone or as one batch), land it as
    the host copy.  Pure Data-level operations — no residency lock, no
    task, no program — shared by the committer, eviction, ``detach()``
    and the custom stage-in's pre-flush."""

    def __init__(self, data_index: int, stats, name: str = "",
                 rank: int = 0, adopt: bool = False):
        """``data_index``: the device's slot in ``Data.copies``;
        ``stats``: the device's counters (``bytes_out``,
        ``scratch_bytes_out``, ``wb_batches``); ``name`` and ``rank``:
        what the committer's thread and the spans carry; ``adopt``: the
        device's host values are copies, never views of its memory (any
        platform but the CPU backend), so a tile comes home through an
        alias of its array and its host value IS the home tile
        (:meth:`_alias`)."""
        self.index = data_index
        self.stats = stats
        self.name = name
        self.rank = rank
        self.adopt = adopt
        #: write-backs that did NOT go through an alias whose host value
        #: became the home tile although ``adopt`` is on: each leaves a
        #: host value cached beside a resident tile, or costs a landing
        #: copy — the second matrix on the host that an out-of-core solve
        #: has no room for (0 on a healthy run; warned once)
        stats.setdefault("wb_alias_fallbacks", 0)
        #: home tiles landed as zeros on a body's word, no copy from the
        #: device (:meth:`land_zeros`)
        stats.setdefault("wb_zeros_landed", 0)
        self._warned = False

    def _fell_back(self, why: str, *args) -> None:
        self.stats["wb_alias_fallbacks"] += 1
        if not self._warned:
            self._warned = True
            debug.warning("write-back without an adopted alias (host "
                          "memory grows by a copy a tile): " + why, *args)

    def _alias(self, payload):
        """A second ``jax.Array`` over the SAME device buffer, for the
        copy home to go through.  A ``jax.Array`` keeps the host value
        of a copy it made for as long as it lives: 16 MiB cached beside
        every resident 16 MiB tile that went home is a second matrix on
        the host when the matrix is larger than the chip (``PERF.md``
        §6, PR 30), and a landing copy of it a third.  The alias dies
        with the write-back; its host value, which nobody else can
        reach, is adopted as the home tile without a copy
        (:meth:`_adopt`).  The payload itself where there is nothing to
        alias (``adopt`` off; a host array, a test's double, an array a
        donating task consumed) — and where JAX refuses the alias:
        counted in ``wb_alias_fallbacks`` and warned once, never
        silent."""
        if not self.adopt or not isinstance(payload, jax.Array) \
                or payload.is_deleted():
            return payload
        try:
            return jax.make_array_from_single_device_arrays(
                payload.shape, payload.sharding, [payload])
        except Exception as e:
            if not payload.is_deleted():  # (consumed meanwhile: no copy)
                self._fell_back("no alias of %r: %r", payload.shape, e)
            return payload

    def _adopt(self, host: np.ndarray) -> np.ndarray:
        """The host value of an alias that died with its write-back,
        made writable: it becomes the home tile as it is.  Where numpy
        refuses — the value is a view of memory that is not its own
        after all — :meth:`commit` copies it, and the fallback is
        counted."""
        try:
            host.flags.writeable = True
        except ValueError:
            self._fell_back("the host value of a %r alias is a view",
                            host.shape)
        return host

    def snapshot(self, data):
        """Version-guarded snapshot of a dirty device copy: returns
        ``(payload, version)`` to commit home, or None when the commit
        would be wrong or redundant.  Taken under the Data lock so a
        concurrent epilog rebind cannot tear payload from version."""
        with data.lock:
            c = data.get_copy(self.index)
            if c is None or c.payload is None:
                return None
            if getattr(c, "staged_by", None) is not None:
                # packed custom-staged representation: flushing it home
                # would corrupt the home tile; the host copy already holds
                # the same version in home layout (the custom stage-in
                # pre-flushes)
                return None
            hc = data.get_copy(0)
            if hc is not None and hc.payload is not None \
                    and hc.version >= c.version:
                # the host already holds this version OR NEWER (a CPU body
                # consumed the device output and bumped past it — the mixed
                # native_device DAG shape): flushing the stale device copy
                # would roll the tile back
                return None
            return (c.payload, c.version)

    def commit(self, data, version: int, host) -> bool:
        """Land a D2H'd payload as the host copy at ``version``.  The
        guard re-checks under the Data lock: a newer commit that landed
        while our get was in flight wins and ours drops (stale commits
        are safe to drop — the version guard).  Deliberately NO
        version_bump: the committed value is the same write the device
        epilog already bumped for, and a second bump would make every
        deferred commit an RT001 unordered-writer false positive."""
        if not host.flags.writeable:
            # host copies must be mutable for CPU bodies, and a device
            # array's host value is not ours to adopt: the array keeps it
            # cached, so a later ``np.asarray`` of it (a remote send)
            # would read what a CPU body writes.  ``copy`` gives up the
            # GIL for the memcpy, on purpose: a landing that keeps it
            # held the pump's solve up by half (``PERF.md`` §6, PR 29)
            host = host.copy()
        with data.lock:
            hc = data.get_copy(0)
            if hc is not None and hc.payload is not None \
                    and hc.version >= version:
                return False
            hc = data.attach_copy(0, host)
            hc.version = version
            hc.coherency = Coherency.SHARED
        self.stats["bytes_out"] += host.nbytes
        if data.scratch is not None:  # spilled by an eviction
            self.stats["scratch_bytes_out"] += host.nbytes
        return True

    def land_zeros(self, datas) -> None:
        """The dirty device copies of ``datas`` are exact zeros by their
        body's own word (``_zeros``) and the last versions of their
        tiles: the home tiles become zeros on the calling thread, under
        the version guard every landing passes (:meth:`snapshot`,
        :meth:`commit`; counted in ``bytes_out`` like any tile written
        home, and in ``wb_zeros_landed``).  The copy on the chip stays
        what the program wrote, resident and current.  A tile costs this
        thread the landing alone, where its copy home cost the pump's
        thread 240 us to start and the committer as much to collect
        (``PERF.md`` §6, PR 44)."""
        for data in datas:
            snap = self.snapshot(data)
            if snap is not None:
                p = snap[0]
                if self.commit(data, snap[1], _fresh_zeros(p.shape, p.dtype)):
                    self.stats["wb_zeros_landed"] += 1

    def start(self, data) -> Optional[int]:
        """Start, without waiting for it, the device->host copy of the
        dirty device copy of ``data`` as it stands (the copy is ordered
        behind the program that writes the tile): for the thread that
        just committed a version it knows to be the tile's LAST — there
        is no later one the committer's dedup could save the bytes of.
        Returns ``(version, array)``: the version the copy was started
        for and the array it was started on (:meth:`_alias`), which the
        drain collects if that version still stands (None: nothing to
        take home, or nothing to start); counted in
        ``wb_started_early``."""
        snap = self.snapshot(data)
        if snap is None:
            return None
        arr = self._alias(snap[0])
        if not _start_copy(arr):
            return None
        self.stats["wb_started_early"] += 1
        return snap[1], arr

    def d2h_batch(self, payloads: List[Any]) -> List[Optional[np.ndarray]]:
        """Collect a batch's device->host copies: the last one first —
        where the copies were started (:meth:`writeback_batch`,
        :meth:`start`) they complete in the order they were started, so
        that is the ONE wait and the rest convert without blocking,
        hence without a hand-back of the GIL from a busy pump a tile.
        No device sync: a copy waits for the program that writes its
        tile.  A payload that a donating task consumed since it was
        snapshotted comes back as None: that version no longer exists
        anywhere, and the consumer's own output supersedes it.  A failed
        computation raises here."""
        hosts: List[Optional[np.ndarray]] = [None] * len(payloads)
        last = len(payloads) - 1
        for k in (last, *range(last)) if payloads else ():
            p = payloads[k]
            try:
                hosts[k] = np.asarray(p)
            except RuntimeError:
                if not (isinstance(p, jax.Array) and p.is_deleted()):
                    raise
        return hosts

    def writeback(self, data) -> None:
        """Synchronous write-back-to-rest of a dirty tile (reference w2r
        tasks, ``parsec_gpu_create_w2r_task``); the deferred path shares
        its snapshot/commit halves."""
        snap = self.snapshot(data)
        if snap is not None:
            arr = self._alias(snap[0])
            host = self.d2h_batch([arr])[0]
            if host is not None:  # None: consumed by a donating task
                self.commit(data, snap[1],
                            host if arr is snap[0] else self._adopt(host))

    def writeback_batch(self, datas, pool: int = 0, batch: int = 0,
                        tickets=(), early=(),
                        ahead: bool = True) -> Tuple[int, int]:
        """One batch home: snapshot every tile (version guard), START
        every copy before the first is collected (they run behind one
        another on the link, not a round trip each), ONE wait, guarded
        commits — under one ``dev:writeback`` span that names ``(pool,
        batch)`` as its cause and notes ``wait_us`` (the collect,
        :meth:`d2h_batch`) and ``early`` (tiles whose copy :meth:`start`
        had started for the very version collected here:
        ``wb_early_hits``).  ``tickets``: per tile, the hb tickets of
        the enqueues that fed it; ``early``: per tile, what
        :meth:`start` returned at hand-over (both the committer's).
        ``ahead=False`` starts nothing: the committer's watermark drain
        of versions that a later task may supersede keeps a round trip a
        tile, because on that path its RATE is what bounds the bytes
        that go home (started ahead, `tile_ctx_n8192` sent 4.37 versions
        of a tile home for 1.89 and its solve took 14% longer:
        ``PERF.md`` §6, PR 29).  Returns ``(tiles committed, tiles
        got)``: the others were stale, or consumed by a donating task."""
        snaps = []
        #: what each copy goes through: the array a copy was started on
        #: at hand-over, an alias of the payload, or the payload
        arrays = []
        joined: List[int] = []
        hits = 0
        for k, d in enumerate(datas):
            s = self.snapshot(d)
            if s is not None:
                snaps.append((d, s[0], s[1]))
                if tickets:
                    joined.extend(tickets[k])
                if early and early[k] is not None and early[k][0] == s[1]:
                    hits += 1
                    arrays.append(early[k][1])
                else:
                    arrays.append(self._alias(s[0]))
        if not snaps:
            return 0, 0
        self.stats["wb_early_hits"] += hits
        committed = 0
        nbytes = sum(int(getattr(p, "nbytes", 0)) for (_d, p, _v) in snaps)
        with pins.span("dev:writeback", pool=pool, rank=self.rank,
                       dev=self.index, id=span_id(), tiles=len(snaps),
                       batch=batch, bytes=nbytes) as sp:
            t0 = time.perf_counter_ns()
            if ahead:
                with pins.wait("d2h_start") as w:
                    n = sum(map(_start_copy, arrays))
                    w.note(n=n, bytes=nbytes)
            hosts = self.d2h_batch(arrays)
            sp.note(wait_us=(time.perf_counter_ns() - t0) // 1000,
                    early=hits)
            for (data, p, version), a, host in zip(snaps, arrays, hosts):
                # host is None: a donating task consumed that version
                if host is None:
                    continue
                if a is not p:
                    host = self._adopt(host)
                if self.commit(data, version, host):
                    committed += 1
            if joined and pins.active(pins.HB_WB_COMMIT):
                # acquire edge: the committer joins every enqueue that
                # fed this batch — exec happens-before write-back commit
                pins.fire(pins.HB_WB_COMMIT, None, {"tickets": joined})
        return committed, len(snaps)


class WritebackCommitter:
    """Background committer for version-guarded deferred write-backs.

    ``enqueue`` is called by the device epilog with the Data whose
    device copy is dirty; entries deduplicate per tile and
    the committer snapshots the NEWEST device version at commit time,
    so a tile re-dirtied while pending commits once.  Draining is
    watermark-driven — batched D2H gets once ``runtime_wb_window_mb``
    of dirty bytes are pending — plus on :meth:`kick` (last versions)
    and at the :meth:`flush` barrier."""

    def __init__(self, writer: HostWriter):
        self._writer = writer
        self._cv = threading.Condition()
        #: data_id -> (Data, [hb tickets], nbytes at enqueue, what
        #: ``HostWriter.start`` returned at enqueue: None, or the version
        #: whose copy home was started and the array it was started on)
        self._pending: "collections.OrderedDict[int, Tuple[Any, List[int], int, Optional[Tuple[int, Any]]]]" = \
            collections.OrderedDict()
        self._inflight: Dict[int, Any] = {}
        self._pending_bytes = 0
        self._window = max(1, int(mca_param.register(
            "runtime", "wb_window_mb", 32,
            help="deferred write-back watermark (MB): the committer "
                 "drains batched D2H gets once this many dirty bytes "
                 "are pending (a flush or a last version drains sooner)"))) << 20
        self._tickets = itertools.count(1)
        #: (pool, batch) of the newest enqueue: the cause a commit names
        self._cause = (0, 0)
        self._kick = False
        self._flushing = False
        self._stop = False
        self.error: Optional[BaseException] = None
        self.stats: Dict[str, int] = {
            "enqueued": 0, "committed": 0, "dropped_stale": 0,
            "batches": 0, "capacity_waits": 0}
        self._thread = threading.Thread(
            target=self._run, name=f"wb-committer:{writer.name}",
            daemon=True)
        self._thread.start()

    # -- producer side ---------------------------------------------------
    def enqueue(self, data, pool: int = 0, batch: int = 0) -> int:
        """:meth:`enqueue_all` of one tile; returns its ticket."""
        return self.enqueue_all((data,), pool, batch)[0]

    def enqueue_all(self, datas, pool: int = 0, batch: int = 0,
                    last: bool = False) -> List[int]:
        """Queue deferred write-backs of the dirty device copies of
        ``datas`` in ONE round of the condition variable (``pool`` and
        ``batch``: the batch whose epilog wrote them, which the
        ``dev:writeback`` span of the commit names as its cause).
        ``last``: the caller knows these to be the LAST versions of
        their tiles.  Their copies home are started here, before any
        wait (:meth:`HostWriter.start`), and the committer drains them
        now, below its watermark: the watermark exists to let a tile
        that is rewritten commit once.  Of a version that may be
        superseded nothing is started before its drain: an early copy
        would move bytes that the dedup saves.
        Deduplicated per tile; bounded by a capacity wait at 4x the
        drain watermark (or the bytes of this one hand-over, if more) so
        a stalled committer applies backpressure instead of
        accumulating unbounded dirty state — but for a last version
        whose copy was started: it is queued once, its copy is on its
        way whether the queue is long or short, and what its entry pins
        is the tile's own resident, accounted buffer, so a wait would
        save no byte and no memory and only make the committing thread
        wait for the chip once a wave (``PERF.md`` §6, PR 35: 1.9 s of
        an out-of-core solve).  Raises the
        stored committer error if the committer died — the caller's
        fail-loudly discipline turns that into a pool failure.
        Returns one ticket a tile."""
        self._cause = (pool, batch)
        heard = pins.active(pins.HB_WB_ENQUEUE)
        index = self._writer.index
        tickets: List[int] = []
        entries = []
        for data in datas:
            ticket = next(self._tickets)
            if heard:
                # release edge: the enqueuing thread just committed this
                # task's epilog — its clock must reach the commit
                pins.fire(pins.HB_WB_ENQUEUE, None,
                          {"ticket": ticket, "data": data.data_id})
            c = data.get_copy(index)
            entries.append([data, ticket, c.nbytes if c is not None else 0,
                            None])
            tickets.append(ticket)
        if last:
            # the thread is inside the runtime for as long as the copies
            # take to start, and can do nothing else: a wait
            with pins.wait("d2h_start") as w:
                n = nbytes = 0
                for e in entries:
                    e[3] = self._writer.start(e[0])
                    if e[3] is not None:
                        n += 1
                        nbytes += e[2]
                w.note(n=n, bytes=nbytes)
        # the capacity: 4x the watermark, or what this one hand-over
        # brings if that is more (a chunk's last versions of 16 MiB
        # tiles are 256 MiB against 128): the wait is for what was
        # pending BEFORE, never for room this very call fills
        cap = max(4 * self._window, sum(e[2] for e in entries))
        with self._cv:
            self._raise_if_dead()
            for data, ticket, nb, early in entries:
                if early is None and self._over(nb, cap):
                    with pins.wait("wb_capacity",
                                   pending_mb=self._pending_bytes >> 20):
                        while self._over(nb, cap):
                            self.stats["capacity_waits"] += 1
                            self._cv.notify_all()  # what is queued may drain
                            self._cv.wait(timeout=1.0)
                self._raise_if_dead()
                entry = self._pending.get(data.data_id)
                if entry is None:
                    self._pending[data.data_id] = (data, [ticket], nb, early)
                    self._pending_bytes += nb
                else:
                    entry[1].append(ticket)
                    if early is not None:  # the newest start stands
                        self._pending[data.data_id] = entry[:3] + (early,)
                self.stats["enqueued"] += 1
            if last:
                self._kick = True
            self._cv.notify_all()
        return tickets

    def _over(self, nb: int, cap: int) -> bool:
        """Whether ``nb`` more bytes have to wait for a drain (called
        with ``_cv`` held)."""
        return (self._pending_bytes + nb > cap and bool(self._pending)
                and self.error is None and not self._stop)

    def _raise_if_dead(self) -> None:
        if self.error is not None:
            raise RuntimeError(
                f"async write-back committer failed: {self.error!r}") \
                from self.error

    def kick(self) -> None:
        """Ask the committer to drain below-watermark pending entries."""
        with self._cv:
            self._kick = True
            self._cv.notify_all()

    def flush(self, timeout: float = 300.0) -> None:
        """Barrier: every deferred write-back enqueued so far is
        committed (or provably stale) on return.  ``detach()``,
        redistribute and remote sends call this before reading host
        tiles.  Re-raises a committer failure loudly."""
        deadline = time.monotonic() + timeout
        with self._cv:
            self._flushing = True
            self._cv.notify_all()
            try:
                while self._pending or self._inflight:
                    if self.error is not None:
                        break
                    left = deadline - time.monotonic()
                    if left <= 0:
                        raise RuntimeError(
                            "async write-back committer flush timed out "
                            f"with {len(self._pending)} pending")
                    self._cv.wait(timeout=min(left, 1.0))
            finally:
                self._flushing = False
            self._raise_if_dead()

    def holding(self, data_ids) -> set:
        """Of ``data_ids``, the tiles queued here or in a drain: a copy
        home of theirs may be on its way through an alias of the array
        (a last version's was started at its hand-over), so nobody may
        donate that array now."""
        with self._cv:
            if not self._pending and not self._inflight:
                return set()
            return {d for d in data_ids
                    if d in self._pending or d in self._inflight}

    # -- gauges ----------------------------------------------------------
    def pending(self) -> int:
        with self._cv:
            return len(self._pending) + len(self._inflight)

    def pending_bytes(self) -> int:
        with self._cv:
            return self._pending_bytes

    def drained(self) -> int:
        """Progress currency for the watchdog epoch: total entries the
        committer has disposed of (committed or dropped stale)."""
        return self.stats["committed"] + self.stats["dropped_stale"]

    @property
    def healthy(self) -> bool:
        return self.error is None and not self._stop

    # -- committer thread ------------------------------------------------
    def _run(self) -> None:
        while True:
            with self._cv:
                while not self._should_drain() and not self._stop:
                    self._cv.wait(timeout=0.25)
                if self._stop and not self._pending:
                    return
                # somebody waits for these tiles, or they are last
                # versions: nothing a slower drain could still save
                forced = self._kick or self._flushing or self._stop
                self._kick = False
                grab = list(itertools.islice(
                    self._pending.items(), WB_BATCH))
                for did, entry in grab:
                    del self._pending[did]
                    self._pending_bytes -= entry[2]
                    self._inflight[did] = entry
            if not grab:
                continue
            try:
                self._commit([entry for _did, entry in grab], forced)
            except BaseException as e:
                with self._cv:
                    self.error = e
                    self._inflight.clear()
                    self._cv.notify_all()
                debug.error("write-back committer died: %s", e)
                return
            finally:
                with self._cv:
                    for did, _entry in grab:
                        self._inflight.pop(did, None)
                    self._cv.notify_all()

    def _should_drain(self) -> bool:
        if not self._pending:
            return False
        return (self._pending_bytes >= self._window or self._kick
                or self._flushing or self._stop)

    def _commit(self, entries, forced: bool) -> None:
        """One drain batch (:meth:`HostWriter.writeback_batch`; its
        copies start ahead of the collect when the drain is ``forced``:
        a kick, a flush or the stop, not the watermark alone).  Runs
        entirely at the Data level — never takes the device residency
        lock."""
        pool, batch = self._cause  # the newest; earlier ones ride along
        committed, got = self._writer.writeback_batch(
            [e[0] for e in entries], pool, batch,
            [e[1] for e in entries], [e[3] for e in entries], forced)
        self.stats["committed"] += committed
        self.stats["dropped_stale"] += len(entries) - committed
        if got:
            self.stats["batches"] += 1

    def close(self, flush: bool = True) -> None:
        if flush and self.error is None:
            try:
                self.flush()
            except Exception:
                pass  # close is teardown: the error already surfaced
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        self._thread.join(timeout=30)
