"""Scratch tiles: the :class:`~parsec_tpu.data.data.Data` behind a flow
whose source is ``NEW``.

Such a tile has no value to stage and no home to go to (PaRSEC's NEW
flows are arena temporaries that live where they are produced and are
released with their last reader: ``arena.c``, ``datarepo.c``).  Here it
is a ``Data`` created WITHOUT a payload, with its shape and dtype, and a
count of the tasks that still have to use it:

* it is **born** where its first task runs.  On a device that task's
  program gets no argument for it — inside the trace the body sees zeros
  (``device/value_args.py``) — and the epilog attaches the body's output
  as the first copy; on the host ``stage_to_cpu`` hands the CPU body a
  zeroed array (:func:`host_zeros`);
* the device module never writes it home (no committer enqueue, nothing
  at ``flush``/``detach``); only an eviction under memory pressure
  spills one that still has users, and only then do
  ``scratch_bytes_out`` / ``scratch_bytes_in`` move off 0;
* whoever builds the task graph declares its users (:func:`add_users`:
  the native executor from the captured graph, ``PTGTaskpool`` from the
  flow's out-dependencies); the device module calls :func:`release` once
  per task in that task's epilog, and drops its copy with the last one.
  A user that never releases (a CPU body, a write-back of the tile into
  a collection) only keeps the tile until its ``Data`` dies, as before.

A tile of a collection that is BORN ON THE DEVICE
(``TiledMatrix(device_born=True)``) is the same thing with one
difference: its collection keeps it (``Data.scratch`` is
:data:`~parsec_tpu.data.data.KEPT`), so no task's retirement frees it
and :func:`add_users` / :func:`release` leave it alone.  It is born by
its first writer, never written home, and read after the pool — by the
next pool, or by its owner, who asks for the rows he wants — where it
lives.

No option switches any of this: a ``Data`` is a scratch tile or has a
home.
"""

from __future__ import annotations

from typing import Any

import numpy as np

from ..data.data import KEPT, Data


def new(key: Any, shape, dtype) -> Data:
    """A scratch tile: no copy anywhere yet, no user declared
    (``Data.scratch`` counts the declared users left)."""
    d = Data(key, shape=tuple(shape), dtype=np.dtype(dtype))
    d.scratch = 0
    return d


def unborn(data: Data) -> bool:
    """A scratch tile no task has written yet: there is nothing to stage."""
    if data.scratch is None:
        return False
    with data.lock:
        return not any(c.payload is not None for c in data.copies.values())


def add_users(data: Data, n: int = 1) -> None:
    with data.lock:
        if data.scratch != KEPT:
            data.scratch += n


def release(data: Data) -> bool:
    """One declared user has completed; True when it was the last (never
    of a tile its collection keeps)."""
    with data.lock:
        if data.scratch == KEPT:
            return False
        data.scratch -= 1
        return data.scratch == 0


def nbytes(data: Data) -> int:
    """The bytes of one copy of the tile."""
    return int(np.prod(data.shape)) * data.dtype.itemsize


def host_zeros(data: Data) -> np.ndarray:
    """Birth on the host: the zeroed array a CPU body gets for a scratch
    tile nobody has written, attached as the host copy."""
    with data.lock:
        c = data.get_copy(0)
        if c is None or c.payload is None:
            c = data.attach_copy(0, np.zeros(data.shape, data.dtype))
        return c.payload
