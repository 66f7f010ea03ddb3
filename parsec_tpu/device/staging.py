"""Asynchronous host<->device staging pipeline.

PR 18 made the task lifecycle native, but every transfer still ran
synchronously on the dispatch thread: ``_stage_in`` blocked the pump on
each H2D put, ``_writeback`` blocked eviction on a D2H get, and
``detach()`` flushed dirty tiles home one at a time.  This module is
the asynchronous half of the staging layer (ROADMAP item 5(b); the
data-transfer overlap story of AXI4MLIR and the tiled-transfer
scheduling of "Design in Tiles", PAPERS.md):

* :class:`StageLane` — a dedicated transfer thread the native pump
  hands the NEXT ready batch to while the current wave computes.  The
  lane prestages input tiles through the device's batched stage-in
  (coalesced ``device_put``), so by the time the pump submits the
  batch every plain input is a residency hit.  Bounded by the
  ``runtime_stage_depth`` MCA param (1 = synchronous, 2 =
  double-buffered default).

* :class:`WritebackCommitter` — a background thread draining
  version-guarded deferred write-backs.  Completed outputs enqueue at
  epilog (deduplicated per tile, so a re-dirtied tile commits its
  NEWEST version once); the committer drains in batched D2H gets when
  the pending-bytes watermark (``runtime_wb_window_mb``) is crossed,
  when an eviction needs a victim committed (:meth:`kick`), or at the
  :meth:`flush` barrier ``detach()``/redistribute/remote sends take.
  The PR 3 version guard makes a stale commit safe to drop, so the
  committer never takes the device residency lock — commits are pure
  Data-level operations and cannot deadlock against eviction waits.

A committer failure is STICKY: the stored exception re-raises on the
next ``enqueue`` (failing the task pool through the device layer's
fail-loudly discipline) and on ``flush`` (failing ``detach()``), so a
dead committer surfaces as a pool failure, never a silent hang.  The
watchdog counts :meth:`WritebackCommitter.drained` in its progress
epoch and diagnoses a wedged committer as finding OBS011.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Any, Deque, Dict, List, Optional, Tuple

from ..profiling import pins
from ..utils import debug, mca_param

#: process-wide span ids for STAGE_IN/WRITEBACK begin/end pairing
_SPAN_SEQ = itertools.count(1)


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


class WritebackCommitter:
    """Background committer for version-guarded deferred write-backs.

    ``enqueue`` is called by the device epilog (and eviction) with the
    Data whose device copy is dirty; entries deduplicate per tile and
    the committer snapshots the NEWEST device version at commit time,
    so a tile re-dirtied while pending commits once.  Draining is
    watermark-driven — batched D2H gets once ``runtime_wb_window_mb``
    of dirty bytes are pending — plus on :meth:`kick` (eviction wants a
    victim home NOW) and at the :meth:`flush` barrier."""

    def __init__(self, dev):
        self._dev = dev
        self._cv = threading.Condition()
        #: data_id -> (Data, [hb tickets], nbytes at enqueue)
        self._pending: "collections.OrderedDict[int, Tuple[Any, List[int], int]]" = \
            collections.OrderedDict()
        self._inflight: Dict[int, Any] = {}
        self._pending_bytes = 0
        self._window = max(1, int(mca_param.register(
            "runtime", "wb_window_mb", 32,
            help="deferred write-back watermark (MB): the committer "
                 "drains batched D2H gets once this many dirty bytes "
                 "are pending (flush/eviction drain sooner)"))) << 20
        self._batch = max(1, int(mca_param.register(
            "runtime", "wb_batch", 32,
            help="max tiles per committer drain batch (one device sync "
                 "+ coalesced D2H gets per batch)")))
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
            target=self._run, name=f"wb-committer:{dev.name}", daemon=True)
        self._thread.start()

    # -- producer side ---------------------------------------------------
    def enqueue(self, data, pool: int = 0, batch: int = 0) -> int:
        """:meth:`enqueue_all` of one tile; returns its ticket."""
        return self.enqueue_all((data,), pool, batch)[0]

    def enqueue_all(self, datas, pool: int = 0, batch: int = 0,
                    kick: bool = False) -> List[int]:
        """Queue deferred write-backs of the dirty device copies of
        ``datas`` in ONE round of the condition variable (``pool`` and
        ``batch``: the batch whose epilog wrote them, which the
        ``dev:writeback`` span of the commit names as its cause;
        ``kick``: drain them now, below the watermark).
        Deduplicated per tile; bounded by a capacity wait at 4x the
        drain watermark so a stalled committer applies backpressure
        instead of accumulating unbounded dirty state.  Raises the
        stored committer error if the committer died — the caller's
        fail-loudly discipline turns that into a pool failure.
        Returns one ticket a tile."""
        self._cause = (pool, batch)
        heard = pins.active(pins.HB_WB_ENQUEUE)
        index = self._dev.data_index
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
            entries.append((data, ticket, c.nbytes if c is not None else 0))
            tickets.append(ticket)
        cap = 4 * self._window
        with self._cv:
            self._raise_if_dead()
            for data, ticket, nb in entries:
                while (self._pending_bytes + nb > cap and self._pending
                       and self.error is None and not self._stop):
                    self.stats["capacity_waits"] += 1
                    self._cv.notify_all()  # what is queued may drain
                    self._cv.wait(timeout=1.0)
                self._raise_if_dead()
                entry = self._pending.get(data.data_id)
                if entry is None:
                    self._pending[data.data_id] = (data, [ticket], nb)
                    self._pending_bytes += nb
                else:
                    entry[1].append(ticket)
                self.stats["enqueued"] += 1
            if kick:
                self._kick = True
            self._cv.notify_all()
        return tickets

    def _raise_if_dead(self) -> None:
        if self.error is not None:
            raise RuntimeError(
                f"async write-back committer failed: {self.error!r}") \
                from self.error

    def kick(self) -> None:
        """Ask the committer to drain below-watermark pending entries
        (eviction pressure: a victim must be home before its device
        copy drops)."""
        with self._cv:
            self._kick = True
            self._cv.notify_all()

    def wait_for(self, data_id: int, timeout: float = 60.0) -> bool:
        """Block until ``data_id`` is neither pending nor in flight.
        Returns False on committer death or timeout — the caller falls
        back to a synchronous write-back (the version guard makes the
        duplicate safe)."""
        deadline = time.monotonic() + timeout
        with self._cv:
            self._kick = True
            self._cv.notify_all()
            while data_id in self._pending or data_id in self._inflight:
                if self.error is not None:
                    return False
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self._cv.wait(timeout=min(left, 1.0))
            return self.error is None

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
                self._kick = False
                grab = list(itertools.islice(
                    self._pending.items(), self._batch))
                for did, entry in grab:
                    del self._pending[did]
                    self._pending_bytes -= entry[2]
                    self._inflight[did] = entry
            if not grab:
                continue
            try:
                self._commit([entry for _did, entry in grab])
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

    def _commit(self, entries) -> None:
        """One drain batch: snapshot (version guard), ONE device sync +
        coalesced D2H gets, guarded host commits.  Runs entirely at the
        Data level — never takes the device residency lock."""
        dev = self._dev
        snaps = []
        tickets: List[int] = []
        for (data, tks, _nb) in entries:
            snap = dev._wb_snapshot(data)
            if snap is None:
                self.stats["dropped_stale"] += 1
                continue
            snaps.append((data, snap[0], snap[1]))
            tickets.extend(tks)
        if not snaps:
            return
        pool, batch = self._cause  # the newest; earlier ones ride along
        with pins.span("dev:writeback", pool=pool,
                       rank=getattr(dev.context, "rank", 0),
                       id=next(_SPAN_SEQ), tiles=len(snaps), batch=batch,
                       bytes=sum(int(getattr(p, "nbytes", 0))
                                 for (_d, p, _v) in snaps)):
            hosts = dev._d2h_batch([p for (_d, p, _v) in snaps])
            for (data, _payload, version), host in zip(snaps, hosts):
                # host is None: a donating task consumed that version
                if host is not None and dev._commit_host(data, version,
                                                         host):
                    self.stats["committed"] += 1
                else:
                    self.stats["dropped_stale"] += 1
            if pins.active(pins.HB_WB_COMMIT) and tickets:
                # acquire edge: the committer joins every enqueue that
                # fed this batch — exec happens-before write-back commit
                pins.fire(pins.HB_WB_COMMIT, None, {"tickets": tickets})
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
