"""Device registry and best-device selection.

Reference: ``/root/reference/parsec/mca/device/device.{c,h}`` — device 0 is
the CPU-cores device, accelerators attach after; per-task placement picks the
device minimizing estimated-time-of-availability (device load + per-task
time estimate, with a load-balance skew factor), after honouring data
affinity: if a task's data is already resident on an accelerator, prefer it
(``parsec_select_best_device``, ``device.c:92-266``, skew ``:54-60``).
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, TYPE_CHECKING

from ..utils import Component, debug, mca_param, register_component
from ..core.lifecycle import AccessMode, DEV_CPU, HookReturn

if TYPE_CHECKING:  # pragma: no cover
    from ..core.context import Context
    from ..core.task import Task


# data_advise advice values (reference device.h:76-78)
ADVICE_PREFETCH = 0x01
ADVICE_PREFERRED_DEVICE = 0x02
ADVICE_WARMUP = 0x03

#: multiplier applied to accelerator ETAs in :func:`select_device`
#: (<1 favours accelerators; reference ``device_load_balance_skew``)
LOAD_BALANCE_SKEW = 0.9


class Device(Component):
    """Base device module (reference device vtable, ``device.h:142-158``)."""

    mca_type = "device"
    device_type: str = DEV_CPU
    #: a module of this class drives ONE accelerator: a context that is
    #: given several (``Context(accelerators=g)``) attaches as many
    #: instances, ``cls(context, index, place=i, of=g)``
    per_accelerator: bool = False

    def __init__(self, context: "Context", index: int):
        self.context = context
        self.index = index
        self.name = f"{self.mca_name}{index}"
        self._load_lock = threading.Lock()
        #: estimated completion horizon (seconds of queued work)
        self.device_load: float = 0.0
        #: relative throughput weight used by the default time estimate;
        #: reference derives GFLOPS ratings per device
        self.gflops_rating: float = 1.0
        self.stats: Dict[str, int] = {
            "executed_tasks": 0,
            "bytes_in": 0,
            "bytes_out": 0,
            "bytes_d2d": 0,  # device-to-device landings (no host bounce)
            "d2d_tiles": 0,  # ... and the tiles that landed so
            "evictions": 0,
        }
        self.enabled = True
        #: the other modules of this class in the context (several
        #: accelerators under one ``Context``); empty with one
        self.peers: List["Device"] = []

    # -- vtable ---------------------------------------------------------
    def attach(self) -> None:
        pass

    def detach(self) -> None:
        pass

    def taskpool_register(self, tp) -> None:
        pass

    def memory_register(self, data) -> None:
        pass

    def memory_unregister(self, data) -> None:
        pass

    def data_advise(self, data, advice: int) -> None:
        """Placement hints (reference ``device.h:76-78,328``): PREFETCH
        stages a copy here ahead of use, PREFERRED_DEVICE pins the
        selector's choice, WARMUP marks the copy recently used.
        Accelerator modules extend; the base handles PREFERRED_DEVICE."""
        if advice == ADVICE_PREFERRED_DEVICE:
            data.preferred_device = self.index

    def time_estimate(self, task: "Task") -> float:
        """Seconds this task would take here (lower = better)."""
        tc = task.task_class
        if tc.time_estimate is not None:
            return tc.time_estimate(task, self)
        return 1e-4 / self.gflops_rating

    def kernel_scheduler(self, es, task: "Task") -> HookReturn:
        """Accelerators override: take ownership of the task (ASYNC)."""
        raise NotImplementedError

    def flush_home(self, datas, wait: bool = True) -> None:
        """A DSL's flush (DTD's ``data_flush`` / ``flush_all``): ``datas``
        are tiles whose newest version lives in this module's memory and
        is, the caller knows, their last for now — bring them home
        together.  ``wait=False`` only starts them on their way: they are
        at home after the module's own flush or its ``detach``.  A module
        that keeps no copy of its own has nothing to do (the base), and
        the caller pulls what is still not at home."""

    def add_load(self, dt: float) -> None:
        with self._load_lock:
            self.device_load += dt

    def sub_load(self, dt: float) -> None:
        with self._load_lock:
            self.device_load = max(0.0, self.device_load - dt)

    def resident_data(self, task: "Task") -> int:
        """Bytes of this task's input data already resident here (affinity)."""
        return 0


@register_component("device")
class CpuDevice(Device):
    """Device 0: the worker cores themselves. CPU chores run inline in the
    calling worker, so the kernel_scheduler is never used."""

    mca_name = "cpu"
    mca_priority = 100
    device_type = DEV_CPU

    def kernel_scheduler(self, es, task):  # pragma: no cover - inline exec
        raise AssertionError("CPU chores execute inline")


def attach_devices(context: "Context", names: Optional[List[str]] = None,
                   accelerators: int = 1) -> List[Device]:
    """Instantiate the CPU device plus every available accelerator module
    (reference ``parsec_mca_device_init``/``attach``, ``parsec.c:809-815``:
    one module per visible accelerator).  ``accelerators``: how many
    instances of a module that drives one chip each
    (``Device.per_accelerator``) the context is given, at indices that
    follow one another; 1 is one instance, built as it always was."""
    from ..utils import components_of_type

    sel = names
    if sel is None:
        sel_param = str(mca_param.register(
            "device", "enabled", "", help="comma list of device modules (empty=all available)"))
        sel = [s.strip() for s in sel_param.split(",") if s.strip()] or None

    devices: List[Device] = []
    for cls in components_of_type("device"):
        explicit = sel is not None and cls.mca_name in sel
        if sel is not None and not explicit and cls.mca_name != "cpu":
            continue
        # explicit naming trumps the availability probe (a module that is
        # inert by default, like template, still attaches when asked for)
        if not cls.available() and not explicit:
            continue
        # a module that was asked for — by name, or by reporting itself
        # available — and cannot attach is an ERROR: continuing without
        # it would silently run every device chore somewhere else
        places = range(accelerators) \
            if accelerators > 1 and cls.per_accelerator else (None,)
        group: List[Device] = []
        for place in places:
            try:
                dev = cls(context, len(devices)) if place is None \
                    else cls(context, len(devices), place=place,
                             of=accelerators)
                dev.attach()
            except Exception as e:
                raise RuntimeError(
                    f"device module {cls.mca_name!r} failed to attach: "
                    f"{e}") from e
            devices.append(dev)
            group.append(dev)
        for dev in group:
            dev.peers = [d for d in group if d is not dev]
    if not devices or devices[0].device_type != DEV_CPU:
        raise RuntimeError("CPU device must attach first")
    return devices


def detach_devices(context: "Context") -> None:
    for dev in getattr(context, "devices", []):
        try:
            dev.detach()
        except Exception as e:  # teardown must not raise
            debug.warning("device %s detach failed: %s", dev.name, e)


def _prefers_device(task: "Task", dev: Device) -> bool:
    args = task.body_args
    if not isinstance(args, (list, tuple)):
        return False
    for spec in args:
        if (isinstance(spec, (list, tuple)) and len(spec) >= 2
                and spec[0] == "data" and spec[1] is not None
                and getattr(spec[1], "preferred_device", -1) == dev.index):
            return True
    return False


_OUT = int(AccessMode.OUT)


def _written_tile(task: "Task"):
    """The tile of the task's first read-write (or write) flow, or None:
    body_args may be an opaque payload for internal tasks (DTD comm
    tasks carry raw tuples) — only ("data", Data, mode) specs count."""
    args = task.body_args
    if not isinstance(args, (list, tuple)):
        return None
    for spec in args:
        if (isinstance(spec, (list, tuple)) and len(spec) >= 3
                and spec[0] == "data" and spec[1] is not None
                and int(spec[2]) & _OUT):
            return spec[1]
    return None


def _place(context: "Context", task: "Task", accs):
    """One of several eligible accelerators for ``task`` by where its
    data is, the reference's rule (``device.c:92-266``: "the location of
    the first data that is used in READ/WRITE, or of one of the READ
    data"): the accelerator that holds the newest version of the tile of
    the task's first written flow (``Data.owner_device``), else the one
    that tile is advised to (``preferred_device``); failing a written
    flow, the accelerator that holds most of the inputs' bytes.  None
    where the data says nothing: the load decides.  ``accs``: the
    eligible ``(device, chore, index)`` of accelerators, more than one.
    The criterion that placed the task is counted on the context."""
    stats = context.stats
    data = _written_tile(task)
    if data is not None:
        by_index = {e[0].index: e for e in accs}
        best = by_index.get(data.owner_device)
        if best is not None:
            stats["selected_by_owner"] += 1
            return best
        best = by_index.get(data.preferred_device)
        if best is not None:
            stats["selected_by_advice"] += 1
            return best
    best, best_bytes = None, 0
    for e in accs:
        rb = e[0].resident_data(task)
        if rb > best_bytes:
            best, best_bytes = e, rb
    if best is not None:
        stats["selected_by_bytes"] += 1
    return best


def _least_eta(task: "Task", eligible):
    best = best_eta = None
    for dev, chore, ci in eligible:
        est = chore.time_estimate(task, dev) if chore.time_estimate else dev.time_estimate(task)
        eta = dev.device_load + est
        if dev.device_type != DEV_CPU:
            eta *= LOAD_BALANCE_SKEW
        if best_eta is None or eta < best_eta:
            best_eta, best = eta, (dev, chore, ci)
    return best


def select_best_device(context: "Context", task: "Task") -> HookReturn:
    """Pick (device, chore) for a ready task; reference ``device.c:92-266``.

    One eligible device is the answer, before anything is asked of the
    task's data.  With ONE accelerator among the eligible (and a CPU
    chore beside it), in this order:
      0. an input advised to a device (``data_advise`` PREFERRED_DEVICE);
      1. data affinity — the accelerator already holding the task's
         inputs wins outright (saves HBM traffic);
      2. minimal ETA = device_load + time_estimate, accelerators
         discounted by :data:`LOAD_BALANCE_SKEW`.
    With SEVERAL accelerators eligible the task goes where its data is
    (:func:`_place`: the written tile's owner, its advice, the inputs'
    bytes), and failing that to the least ETA of all the eligible
    (``selected_by_load``).
    """
    tc = task.task_class
    eligible = []
    for dev in context.devices:
        if not dev.enabled:
            continue
        for ci, chore in enumerate(tc.chores):
            if not chore.enabled or chore.device_type != dev.device_type:
                continue
            if not (task.chore_mask & (1 << ci)):
                continue
            if chore.evaluate is not None and not chore.evaluate(task):
                continue
            eligible.append((dev, chore, ci))
            break
    if not eligible:
        return HookReturn.NEXT

    if len(eligible) == 1:
        best = eligible[0]
    else:
        accs = [e for e in eligible if e[0].device_type != DEV_CPU]
        if len(accs) > 1:
            best = _place(context, task, accs)
            if best is None:
                best = _least_eta(task, eligible)
                context.stats["selected_by_load"] += 1
        else:
            # 0. explicit preference on any input
            best = None
            for e in eligible:
                if _prefers_device(task, e[0]):
                    best = e
                    break
            # 1. affinity
            best_bytes = 0
            if best is None:
                for e in accs:
                    rb = e[0].resident_data(task)
                    if rb > best_bytes:
                        best, best_bytes = e, rb
            # 2. ETA
            if best is None:
                best = _least_eta(task, eligible)
    dev, chore, ci = best
    task.selected_device = dev
    task.selected_chore = chore
    task.selected_chore_idx = ci
    est = chore.time_estimate(task, dev) if chore.time_estimate else dev.time_estimate(task)
    dev.add_load(est)
    task.prof["est"] = est
    return HookReturn.DONE
