"""Task, TaskClass, Flow, Chore — the task model.

Mirrors the reference's task model (``parsec_task_t``,
``parsec_task_class_t``, ``parsec_flow_t``, ``__parsec_chore_t`` —
``/root/reference/parsec/parsec_internal.h:396-553``) as plain Python
objects.  The per-class *vtable* entries that the reference's DSLs generate
as C functions (``iterate_successors``, ``release_deps``, ``data_lookup``,
``make_key`` …) are callables installed by the front-ends (PTG builder /
DTD engine).
"""

from __future__ import annotations

import itertools
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, TYPE_CHECKING

from .lifecycle import AccessMode, HookReturn, TaskStatus, DEV_CPU

if TYPE_CHECKING:  # pragma: no cover
    from .taskpool import Taskpool
    from ..data.data import DataCopy


class Flow:
    """A named dataflow slot of a task class (reference ``parsec_flow_t``)."""

    __slots__ = ("name", "access", "index")

    def __init__(self, name: str, access: AccessMode, index: int = -1):
        self.name = name
        self.access = access
        self.index = index

    def __repr__(self) -> str:
        return f"Flow({self.name}, {self.access!r}, idx={self.index})"


class Chore:
    """One BODY incarnation of a task class (reference ``__parsec_chore_t``,
    ``parsec_internal.h:396-402``): a device type + hook, with an optional
    ``evaluate`` predicate deciding applicability per task."""

    __slots__ = ("device_type", "hook", "evaluate", "enabled", "time_estimate", "body_fn",
                 "wave_key")

    def __init__(
        self,
        device_type: str,
        hook: Callable[["Any", "Task"], HookReturn],
        evaluate: Optional[Callable[["Task"], bool]] = None,
        time_estimate: Optional[Callable[["Task", "Any"], float]] = None,
    ):
        self.device_type = device_type
        self.hook = hook
        self.evaluate = evaluate
        self.enabled = True
        self.time_estimate = time_estimate
        #: raw functional body for device execution (set by front-ends for
        #: accelerator chores; the device module jits and dispatches it)
        self.body_fn = None
        #: the device module's memo for this chore: ``(body_fn it was
        #: worked out for, what a wave signature starts with, the body's
        #: per-flow staging hooks, whether its signatures may name
        #: donated flows: the body names no donated arguments itself)``
        self.wave_key: Optional[Tuple[Any, ...]] = None


class TaskClass:
    """Per-class vtable (reference ``parsec_task_class_t``,
    ``parsec_internal.h:409-457``).

    Front-ends populate the callable slots; ``None`` slots fall back to
    no-op defaults in the scheduling core.
    """

    _ids = itertools.count()

    def __init__(
        self,
        name: str,
        flows: Sequence[Flow] = (),
        chores: Sequence[Chore] = (),
        *,
        nb_parameters: int = 0,
        dependencies_goal: int = 0,
        task_class_id: Optional[int] = None,
    ):
        self.name = name
        self.task_class_id = task_class_id if task_class_id is not None else next(self._ids)
        self.flows: List[Flow] = list(flows)
        for i, f in enumerate(self.flows):
            if f.index < 0:
                f.index = i
        self.chores: List[Chore] = list(chores)
        #: ``only_device_type``'s answer (None: not asked yet)
        self._only_device: Optional[str] = None
        self.nb_parameters = nb_parameters
        #: number of input dependencies a task must see released before it
        #: becomes ready (counter-mode tracking); front-ends may instead use
        #: per-task goals via the dep tracker.
        self.dependencies_goal = dependencies_goal

        # vtable slots (all optional):
        self.make_key: Callable[[Tuple], Any] = lambda locals_: locals_
        self.prepare_input: Optional[Callable] = None     # data_lookup
        self.prepare_output: Optional[Callable] = None
        self.complete_execution: Optional[Callable] = None
        #: release_deps(es, task) -> iterable of ready successor Tasks
        self.release_deps: Optional[Callable] = None
        self.iterate_successors: Optional[Callable] = None
        self.iterate_predecessors: Optional[Callable] = None
        self.release_task: Optional[Callable] = None
        self.time_estimate: Optional[Callable] = None
        self.priority_fn: Optional[Callable] = None
        self.get_datatype: Optional[Callable] = None

    def add_chore(self, chore: Chore) -> None:
        self.chores.append(chore)
        self.chores_changed()

    def chores_changed(self) -> None:
        """A chore was added or switched off: what was worked out from
        the chores is asked again."""
        self._only_device = None

    def only_device_type(self) -> str:
        """The one accelerator device type whose module alone can run
        this class's tasks: every enabled chore is of that type and none
        asks an ``evaluate`` task by task.  ``""`` where a CPU can run
        them, where several types can, or where no chore is enabled.
        Worked out once a class: what a device manager releases goes
        straight into its own queue by this
        (``scheduling.schedule_ready``)."""
        only = self._only_device
        if only is None:
            live = [c for c in self.chores if c.enabled]
            types = {c.device_type for c in live}
            only = ""
            if len(types) == 1 and DEV_CPU not in types \
                    and all(c.evaluate is None for c in live):
                only, = types
            self._only_device = only
        return only

    def chores_for(self, device_types: Sequence[str]) -> List[Chore]:
        return [c for c in self.chores if c.enabled and c.device_type in device_types]

    def __repr__(self) -> str:
        return f"TaskClass({self.name}#{self.task_class_id})"


class Task:
    """A task instance (reference ``parsec_task_t``,
    ``parsec_internal.h:521-553``)."""

    __slots__ = (
        "taskpool",
        "task_class",
        "locals",
        "priority",
        "status",
        "chore_mask",
        "selected_device",
        "selected_chore",
        "selected_chore_idx",
        "counted",
        "data_in",
        "data_out",
        "repo_entry",
        "retired",
        "body_args",
        "on_complete",
        "prof",
        "user",
        "fused_n",
        "_tpu_completed",
        "_tpu_attempts",
        "_tpu_effects",
        "_tpu_enq",
        "_tpu_direct",
        "_tpu_scratch",
        "_tpu_home",
        "_tpu_donate",
        "_tpu_next",
        "_tpu_sig",
    )

    def __init__(
        self,
        taskpool: "Taskpool",
        task_class: TaskClass,
        locals_: Tuple = (),
        priority: int = 0,
    ):
        self.taskpool = taskpool
        self.task_class = task_class
        self.locals = tuple(locals_)
        # the pool's composed (tenant weight, job priority) offset — set
        # by the serving plane, 0 everywhere else — rides every task so
        # one choke point covers all front-ends: the scheduler pop order
        # AND the priority-ordered remote sends see the composition
        self.priority = priority + getattr(taskpool, "priority_base", 0)
        self.status = TaskStatus.NONE
        self.chore_mask: int = ~0  # bitmask over task_class.chores indices
        self.selected_device = None
        self.selected_chore: Optional[Chore] = None
        self.selected_chore_idx: int = -1
        #: already counted into auto-count termination detection
        self.counted = False
        #: per-flow input DataCopy (or None); parallel to task_class.flows
        self.data_in: List[Optional["DataCopy"]] = [None] * len(task_class.flows)
        #: per-flow output DataCopy
        self.data_out: List[Optional["DataCopy"]] = [None] * len(task_class.flows)
        self.repo_entry = None
        #: set once complete_execution has retired this task (guards
        #: against double-retire in error containment paths)
        self.retired = False
        #: opaque arguments handed to the body hook (DTD arg list, PTG env)
        self.body_args: Any = None
        self.on_complete: Optional[Callable[["Task"], None]] = None
        self.prof: Dict[str, float] = {}
        self.user: Any = None
        #: member-task count of a fused supertask (dsl.fusion): ONE
        #: completion retires this many tasks through Taskpool.task_done
        #: (termdet + nb_retired progress accounting); 1 everywhere else
        self.fused_n: int = 1
        #: set by the TPU device module once its eager-completion path has
        #: retired the task (guards the manager's error-containment fallback
        #: against double-completion)
        self._tpu_completed = False
        #: ``perf_counter_ns`` at the moment the device module queued the
        #: task (its ready-queue wait: the ``waited_us`` of ``dev:wave``)
        self._tpu_enq = 0
        #: the device's manager queued the task itself, on the thread
        #: that released it (``direct`` on ``dev:wave``)
        self._tpu_direct = False
        #: the scratch tiles among the task's flows, as the device module
        #: staged them: it releases one user of each in the task's epilog
        self._tpu_scratch: Tuple = ()
        #: positions in ``body_args`` of the outputs that go home (the
        #: device module's write-back committer takes only these); None
        #: where whoever built the task does not know: then every one.
        #: Three builders say: the pump, from the captured graph's plan
        #: (``dsl/native_exec.py``: the DAG's last versions); a PTG pool
        #: on the ``Context`` route, from the task's own output
        #: dependencies (``PTGTaskpool._home_rule``: the versions no
        #: successor overwrites); ``insert_task`` (``dsl/dtd.py``: ``()``,
        #: a tile goes home at its flush)
        self._tpu_home: Optional[Tuple[int, ...]] = None
        #: positions in ``body_args`` of the read-write flows whose INPUT
        #: version this task is the only consumer of (no other task reads
        #: it, its producer does not send it home): the device module may
        #: give that tile's array to the task's program to write the
        #: output over, where it finds nobody else holding the array
        #: (``TpuDevice._not_sole``); None where whoever built the task
        #: does not know: then nothing is donated, the body is functional.
        #: Three builders say: the pump, from the captured graph's edges
        #: (``dsl/attach_plan.py`` ``_donations``); ``insert_task``
        #: (``dsl/dtd.py``: the insertion's exclusive writer); a PTG pool
        #: on the ``Context`` route in a context of one rank, from the
        #: classes' own dependencies (``PTGTaskpool._donate_rule``: the
        #: producer's output dependencies name this task and nobody else)
        self._tpu_donate: Optional[Tuple[int, ...]] = None
        #: where the task's row starts in its pool's table of next uses
        #: (``taskpool.next_use[_tpu_next + position in body_args]``: the
        #: rank of the tile's next reader, ``device/residency.py``); -1
        #: where whoever built the task does not know
        self._tpu_next = -1
        #: the task's signature ``(wave key, FlowPlan)``, once the device
        #: module has worked it out (a ready task's flows no longer
        #: change): the key is None when it cannot ride a wave; None
        #: without a device body; False until somebody asked
        self._tpu_sig: Any = False

    @property
    def key(self) -> Any:
        return self.task_class.make_key(self.locals)

    def unique_key(self) -> Tuple[int, Any]:
        return (self.task_class.task_class_id, self.key)

    def __repr__(self) -> str:
        loc = ",".join(map(str, self.locals))
        return f"{self.task_class.name}({loc})"
