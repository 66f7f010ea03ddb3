"""Template device module — the documented starting point for a new
accelerator backend.

Reference: ``/root/reference/parsec/mca/device/template/`` ships a
skeleton component precisely so a new device type (there: a hypothetical
accelerator; here: e.g. a second TPU slice, a remote PJRT endpoint, or a
simulator) can be written by filling in the vtable.  This module is the
same thing for this framework, **and it runs**: bodies execute
synchronously on the host, so you can attach it and watch tasks flow
before writing any real backend code.

To build a real backend from this template:

1. copy the file, rename the class and ``mca_name``;
2. keep the ``@register_component("device")`` decorator — the MCA
   registry discovers it by type, and ``--mca device <name>`` /
   ``PARSEC_MCA_device=<name>`` selects it (reference:
   ``parsec_mca_device_attach``, ``device.h:224``);
3. decide your ``device_type`` tag — task bodies are matched to devices
   by this string (a ``Chore(device_type=...)`` per incarnation);
4. implement the five capability areas, in rough order of payoff:

   * **kernel_scheduler** (mandatory): called on a *worker* thread when
     the core selected this device (``scheduling.c:137``).  Return
     ``HookReturn.DONE`` for synchronous completion, or enqueue the task,
     return ``HookReturn.ASYNC``, and later call
     ``scheduling.complete_execution(...)`` from your manager thread —
     the reference GPU manager-thread state machine
     (``device_gpu.c:2510-2730``; see ``tpu.py`` for the full version
     with stage-in/out phases, dual-LRU HBM residency and async lanes).
     A manager that completes tasks on a worker's stream may mark it
     (``es.managing = self`` for the time of its loop) and answer
     ``keep_released(task)``: ``schedule_ready`` then asks it first about
     every task that thread releases, and parks no successor on the
     stream;
   * **stage in/out**: move ``Data`` copies to/from your memory space,
     bump ``data.attach_copy(self.data_index, ...)`` versions, and
     account ``stats["bytes_in"/"bytes_out"]``;
   * **time_estimate**: seconds a task would take here — the device
     selector minimizes load + estimate (``device.c:92-266``), so a
     realistic rating steers work your way;
   * **memory_register/unregister**: pin/unpin host buffers if your
     transport needs it;
   * **taskpool_register**: per-taskpool warm-up (e.g. precompile the
     task classes' kernels).
"""

from __future__ import annotations

from typing import TYPE_CHECKING

from ..core.lifecycle import HookReturn
from ..utils import register_component
from .device import Device

if TYPE_CHECKING:  # pragma: no cover
    from ..core.task import Task

#: the device_type string task chores must carry to run here
DEV_TEMPLATE = "template"


@register_component("device")
class TemplateDevice(Device):
    """A minimal synchronous device: host execution, full accounting."""

    mca_name = "template"
    mca_priority = -1
    device_type = DEV_TEMPLATE

    @classmethod
    def available(cls) -> bool:
        """Inert unless asked for by name (the reference template never
        builds by default either): pass ``devices=[..., "template"]`` to
        Context, or name it in ``device_enabled``."""
        return False

    def __init__(self, context, index: int):
        super().__init__(context, index)
        self.data_index = index
        # advertise a modest rating so the ETA-based selector only sends
        # tasks that declare a template chore and nothing else competes
        self.gflops_rating = 1.0

    # -- lifecycle -------------------------------------------------------
    def attach(self) -> None:
        """Bind your hardware here.  Report a missing backend from
        ``available()``: a selected module that raises in ``attach``
        fails the context (``attach_devices``)."""

    def detach(self) -> None:
        """Flush dirty copies home, release handles."""

    # -- the one mandatory hook ------------------------------------------
    def kernel_scheduler(self, es, task: "Task") -> HookReturn:
        """Synchronous exemplar: resolve args like the CPU path, run the
        chore's body function, retire inline.  A real backend would
        enqueue + return ASYNC here."""
        chore = task.selected_chore
        body = chore.body_fn or getattr(chore, "hook", None)
        if body is None:
            raise RuntimeError(f"template chore of {task!r} has no body")
        from ..dsl.dtd import stage_to_cpu

        args = []
        for spec in task.body_args or ():
            kind, payload, mode = spec
            if kind == "data":
                # stage the newest version to the host copy (the template
                # "device memory" is host memory), like the CPU path does
                args.append(stage_to_cpu(payload) if payload is not None else None)
            elif kind == "value":
                args.append(payload)
            # "ctl" contributes no argument
        result = body(*args)
        # write-back convention: a returned tuple replaces writable flows;
        # the consistent pair is host copy 0 + version_bump(0) (matching
        # the CPU hook), never newest_copy() which may be a device copy
        from ..core.lifecycle import AccessMode

        writable = [spec[1] for spec in task.body_args or ()
                    if spec[0] == "data" and spec[1] is not None
                    and spec[2] & AccessMode.OUT]
        if result is not None:
            outs = result if isinstance(result, (tuple, list)) else (result,)
            if len(outs) != len(writable):
                raise ValueError(
                    f"{task!r}: body returned {len(outs)} outputs for "
                    f"{len(writable)} writable flows")
            import numpy as np

            for data, new in zip(writable, outs):
                data.get_copy(0).payload = np.asarray(new)
        for data in writable:
            data.version_bump(0)
        # executed_tasks is accounted centrally at completion
        # (core/scheduling.py), like every other device
        return HookReturn.DONE
