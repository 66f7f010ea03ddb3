"""Data and per-device copies with coherency and versioning.

Reference: ``/root/reference/parsec/data.{c,h}``, ``data_internal.h`` —
``parsec_data_t`` is a meta-object keyed into a collection holding one
``parsec_data_copy_t`` per device; copies carry a MOESI-like
``coherency_state`` (INVALID/OWNED/EXCLUSIVE/SHARED), a ``version``, and
ownership flags (``data.h:27-60``). Ownership transfer on access is
``parsec_data_transfer_ownership_to_copy`` (``data.h:119-130``).

Payloads: numpy arrays on the CPU device, ``jax.Array`` on TPU devices.
"""

from __future__ import annotations

import enum
import itertools
import threading
from typing import Any, Dict, Optional, TYPE_CHECKING

from ..core.lifecycle import AccessMode
from ..profiling import pins

if TYPE_CHECKING:  # pragma: no cover
    from .collection import DataCollection


_OUT = int(AccessMode.OUT)

#: ``Data.scratch`` of a tile that has no home and that nobody's
#: retirement frees: a tile of a collection that is born on the device
#: (``datadist.matrix.TiledMatrix(device_born=True)``).  It lives as long
#: as its collection, is never written home (only an eviction spills it)
#: and is read where it lives
KEPT = -1


class BeingOverwritten(RuntimeError):
    """A device module asked for a version that another module's program
    is overwriting in place (``Data.hold_source``)."""


class Coherency(enum.Enum):
    """Reference PARSEC_DATA_COHERENCY_* (data.h:39-44)."""

    INVALID = "invalid"      # content stale; must be refreshed before use
    OWNED = "owned"          # this device has the authoritative, dirty copy
    EXCLUSIVE = "exclusive"  # sole valid copy, clean
    SHARED = "shared"        # valid copy, possibly replicated


class DataCopy:
    """One device-resident replica of a Data (reference
    ``parsec_data_copy_t``)."""

    __slots__ = (
        "data",
        "device_index",
        "payload",
        "coherency",
        "version",
        "readers",
        "flags",
        "arena",
        "staged_by",
    )

    def __init__(self, data: "Data", device_index: int, payload: Any = None):
        self.data = data
        self.device_index = device_index
        self.payload = payload
        self.coherency = Coherency.INVALID if payload is None else Coherency.SHARED
        self.version: int = 0
        self.readers: int = 0
        self.flags: int = 0
        self.arena = None  # owning arena, for recycled temp buffers
        #: the custom stage_in hook that produced this copy's payload, if
        #: any — a packed/converted representation is only reusable by
        #: the SAME hook (device/tpu.py _stage_in_custom fast path)
        self.staged_by = None

    @property
    def nbytes(self) -> int:
        p = self.payload
        return int(getattr(p, "nbytes", 0))

    def __repr__(self) -> str:
        return (
            f"DataCopy(key={self.data.key}, dev={self.device_index}, "
            f"{self.coherency.value}, v{self.version})"
        )


class Data:
    """The device-agnostic data meta-object (reference ``parsec_data_t``)."""

    _ids = itertools.count()

    __slots__ = (
        "key",
        "collection",
        "copies",
        "owner_device",
        "preferred_device",
        "nb_elts",
        "shape",
        "dtype",
        "lock",
        "data_id",
        "user",
        "scratch",
        "peer_holds",
        "__weakref__",
    )

    def __init__(
        self,
        key: Any,
        collection: Optional["DataCollection"] = None,
        *,
        shape=None,
        dtype=None,
        nb_elts: int = 0,
    ):
        self.key = key
        self.collection = collection
        self.copies: Dict[int, DataCopy] = {}
        self.owner_device: int = -1
        self.preferred_device: int = -1
        self.nb_elts = nb_elts
        self.shape = shape
        self.dtype = dtype
        self.lock = threading.RLock()
        self.data_id = next(self._ids)
        self.user: Any = None
        #: None for a tile that has a home; for a scratch tile, the Data
        #: of a ``NEW`` flow, its declared users left (device/scratch.py);
        #: :data:`KEPT` for a device-born collection's tile
        self.scratch: Optional[int] = None
        #: landings of the newest copy's ARRAY that are between "read the
        #: reference" and "enqueued" on other device modules of the
        #: context (:meth:`hold_source`); -1 while the module that holds
        #: the array has given it to a program to overwrite in place
        #: (:meth:`claim_for_donation`, until that program's commit).
        #: Read and written under ``lock``
        self.peer_holds: int = 0

    # -- copy management --------------------------------------------------
    def attach_copy(self, device_index: int, payload: Any) -> DataCopy:
        """Reference ``parsec_data_copy_attach``."""
        with self.lock:
            c = DataCopy(self, device_index, payload)
            existing = self.copies.get(device_index)
            if existing is not None:
                c.version = existing.version
            self.copies[device_index] = c
            if self.owner_device < 0:
                self.owner_device = device_index
                c.coherency = Coherency.EXCLUSIVE
            return c

    def detach_copy(self, device_index: int) -> Optional[DataCopy]:
        with self.lock:
            c = self.copies.pop(device_index, None)
            if c is not None and self.owner_device == device_index:
                self.owner_device = next(iter(self.copies), -1)
            return c

    def get_copy(self, device_index: int) -> Optional[DataCopy]:
        with self.lock:
            return self.copies.get(device_index)

    def newest_copy(self) -> Optional[DataCopy]:
        with self.lock:
            best = None
            for c in self.copies.values():
                if c.coherency is Coherency.INVALID:
                    continue
                if best is None or c.version > best.version:
                    best = c
            return best

    def hold_source(self, device_index: int) -> Optional[DataCopy]:
        """The copy a staging walk of ``device_index`` takes the tile
        from: the newest valid one and, among copies at that version, one
        that lives on a device (a chip-to-chip landing never crosses the
        host; a tile that went home as a last version is still read from
        the chip that bore it).  Where that copy is another device
        module's, the walk HOLDS its array from here until its landing is
        enqueued (:meth:`release_source`): under one hold of the tile's
        lock the reference is read and the hold counted, so the module
        that owns the array can never give it to a donating program in
        between (:meth:`claim_for_donation`).  A version that its sole
        consumer is overwriting in place has no reader left by the DAG's
        own word: :class:`BeingOverwritten`."""
        with self.lock:
            best = None
            for c in self.copies.values():
                if c.coherency is Coherency.INVALID or c.payload is None:
                    continue
                if best is None or c.version > best.version or (
                        c.version == best.version
                        and best.device_index == 0 and c.device_index != 0):
                    best = c
            if best is not None and best.device_index not in (0, device_index):
                if self.peer_holds < 0:
                    raise BeingOverwritten(
                        f"{self!r}: version {best.version} on device "
                        f"{best.device_index} is being overwritten in place "
                        "by the task that alone consumes it")
                self.peer_holds += 1
            return best

    def release_source(self) -> None:
        """The landing that :meth:`hold_source` counted is enqueued (or
        given up)."""
        with self.lock:
            if self.peer_holds > 0:
                self.peer_holds -= 1

    def claim_for_donation(self) -> bool:
        """The module that holds the newest copy's array is about to give
        it to a program that writes its output over it: False where a
        peer's landing holds the array (the program goes out functional);
        True marks the tile until the program's commit rebinds the copy
        (:meth:`donation_committed`), and a peer that asks meanwhile is
        refused loudly."""
        with self.lock:
            if self.peer_holds:
                return False
            self.peer_holds = -1
            return True

    def donation_committed(self) -> None:
        with self.lock:
            if self.peer_holds < 0:
                self.peer_holds = 0

    def current_copy(self, device_index: int) -> Optional[DataCopy]:
        """The copy on ``device_index`` when it holds the tile at the
        newest valid version (what :meth:`get_copy` and
        :meth:`newest_copy` say together, under one hold of the lock);
        None when that device has to stage the tile in first — also over
        a custom-staged copy, which holds a packed representation and
        not the tile."""
        with self.lock:
            mine = self.copies.get(device_index)
            if mine is None or mine.payload is None \
                    or mine.staged_by is not None:
                return None
            newest = -1
            for c in self.copies.values():
                if c.version > newest and c.coherency is not Coherency.INVALID:
                    newest = c.version
            return mine if 0 <= newest <= mine.version else None

    # -- coherency protocol ----------------------------------------------
    def transfer_ownership(self, device_index: int, access: AccessMode) -> DataCopy:
        """MOESI-like ownership transition before ``device_index`` touches
        the data (reference ``parsec_data_transfer_ownership_to_copy``,
        ``data.c``). Returns the target copy (payload may still need a
        stage-in by the caller if its version lags)."""
        with self.lock:
            copy = self.copies.get(device_index)
            if copy is None:
                copy = DataCopy(self, device_index)
                self.copies[device_index] = copy
            if int(access) & _OUT:  # plain ints: no enum arithmetic
                # writer: invalidate all other replicas, become OWNED
                for di, c in self.copies.items():
                    if di != device_index:
                        c.coherency = Coherency.INVALID
                copy.coherency = Coherency.OWNED
                self.owner_device = device_index
            else:
                # reader: join the sharers; demote an exclusive owner
                if copy.coherency is Coherency.INVALID:
                    copy.coherency = Coherency.SHARED
                owner = self.copies.get(self.owner_device)
                if owner is not None and owner is not copy and owner.coherency is Coherency.EXCLUSIVE:
                    owner.coherency = Coherency.SHARED
                copy.readers += 1
            return copy

    def version_bump(self, device_index: int,
                     listening: Optional[bool] = None) -> int:
        """After a write completes on ``device_index``: new authoritative
        version (reference: epilog version bump, ``device_gpu.c:2343``).
        ``listening``: whether ``DATA_VERSION_BUMP`` has a subscriber,
        from a caller that asked once for many bumps."""
        with self.lock:
            copy = self.copies[device_index]
            newv = 0
            for c in self.copies.values():
                if c.version > newv:
                    newv = c.version
            newv += 1
            copy.version = newv
            copy.coherency = Coherency.OWNED
            self.owner_device = device_index
        # happens-before site: a write to this tile retired.  The hb
        # checker flags two bumps with no dependency/completion/frame
        # path between them (RT001) — the version counter itself is
        # lock-serialized, but the payload writes it summarizes are not.
        if listening is None:
            listening = pins.active(pins.DATA_VERSION_BUMP)
        if listening:
            pins.fire(pins.DATA_VERSION_BUMP, None,
                      {"data": self.data_id, "key": self.key,
                       "version": newv, "device": device_index})
        return newv

    def __repr__(self) -> str:
        return f"Data(key={self.key}, copies={list(self.copies)})"


def land_into_home(home: "Data", payload) -> None:
    """Receiver half of a cross-rank final write-back: store the arrived
    value into the home tile's host copy and bump its version.  Shared by
    every consumer of the writeback wire message (PTG taskpools,
    the distributed native executor) — both sides of the protocol must
    land payloads identically."""
    if payload is None:
        return
    import numpy as np

    dst = home.get_copy(0)
    buf = np.asarray(payload)
    if dst is None or dst.payload is None:
        home.attach_copy(0, np.array(buf))  # writable private copy
    else:
        np.copyto(dst.payload, buf)
    home.version_bump(0)


def data_create(key: Any, collection=None, payload=None, device_index: int = 0, **kw) -> Data:
    """Reference ``parsec_data_create``: make a Data with an initial
    device-0 (CPU) copy."""
    d = Data(key, collection, **kw)
    if payload is not None:
        d.attach_copy(device_index, payload)
        if d.shape is None:
            d.shape = getattr(payload, "shape", None)
        if d.dtype is None:
            d.dtype = getattr(payload, "dtype", None)
    return d
