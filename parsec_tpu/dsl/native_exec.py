"""Native execution engine for captured PTG taskpools.

The reference's hot loop — ready-queue pops, dependency counting,
release_deps — is native C (``scheduling.c``, ``mca/sched``); only task
BODYs are application code.  This module reproduces that split: the
captured DAG (:mod:`parsec_tpu.dsl.graph`) is handed to the C++ engine
(``native/src/graph.cpp`` — atomic dependency counters, priority pool,
native worker threads), and Python is entered once per task through a
ctypes trampoline to run the BODY.  Dependency resolution, scheduling
and termination detection never touch the interpreter.

Scope: single-rank.  Two body regimes:

* CPU chores (default) — in-place numpy tiles, Python entered once per
  BODY through the trampoline;
* **native device dispatch** (``native_device=True``) — classes with an
  accelerator BODY run through the :class:`TpuDevice` dispatch machinery
  (staging, wave batching, jit cache all intact) under one of two
  protocols:

  - **pump mode** (all-device DAGs): the native engine owns the ENTIRE
    per-task lifecycle — ready-queue ordering (spq priority order, the
    serve plane's wdrr tenant bins, or the schedule explorer's seeded
    perturbation), dep-counter decrement on completion, successor
    pushes and quiescence counting.  A single Python pump loop makes
    ONE ``pz_graph_pop_batch`` ctypes call per batch of ready tasks,
    dispatches the batch through the device manager's wave path, and
    retires it with ONE ``pz_graph_done_batch`` call.  Per task the
    interpreter is entered **zero** times between attach and drain —
    no trampoline, no completion callback; Python cost is O(batches).
    Lifecycle events (dep decrements, publishes, retires) buffer
    natively and drain in batches into the existing PINS sites when
    observers (hb-check, binary traces, SLO plane) are installed.
  - **legacy ASYNC chores** (mixed DAGs with CPU-fallback bodies, the
    input the pump cannot run): native worker threads enter Python
    once to enqueue (chore returns ASYNC) and once per completion
    callback (``pz_task_done``) — exactly two entries per task, never
    for dependency bookkeeping (the PR-3 protocol; the reference keeps
    device dispatch inside its native hot loop the same way,
    ``scheduling.c:126-153`` + ``device_gpu.c:2510-2730``).

This is the dispatch-bound regime — many small tasks — where
interpreter overhead dominates the dynamic path (round-5 A/B: ~0.5
ms/task of host-side Python bookkeeping).
"""

from __future__ import annotations

import contextlib
import gc
import types
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from ..core.compound import CompoundTaskpool
from ..core.lifecycle import AccessMode, HookReturn, DEV_CPU, DEV_TPU
from ..core.sched.wdrr import QUANTUM as WDRR_QUANTUM
from ..core.task import Chore, Task, TaskClass
from ..profiling import pins
from .graph import TaskGraph, capture, source_tile
from .ptg import CTL, PTGTaskpool, _wrap_device_body


def _drain_batch() -> int:
    from ..utils import mca_param

    return int(mca_param.register(
        "runtime", "native_drain", 256,
        help="pump-mode batch size: max ready tasks per pz_graph_pop_"
             "batch call (also floors the lifecycle-event drain buffer)"))


def _pump_window(dev) -> Tuple[int, int]:
    """``(batch, depth)`` of the pump over ``dev``: ready tasks a
    ``pop_batch`` and batches popped before the oldest is submitted.
    With the staging pipeline (``stage_depth > 1``) the pop buffer
    shrinks to ``runtime_native_drain // stage_depth``, so that one wide
    ready wave splits into ``stage_depth`` batches."""
    cap = max(1, _drain_batch())
    depth = max(1, int(getattr(dev, "stage_depth", 1) or 1))
    if depth == 1 or not hasattr(dev, "prestage_tiles"):
        return cap, 1
    return max(1, cap // depth), depth


def _conformance_on() -> bool:
    from ..utils import mca_param

    return bool(int(mca_param.register(
        "runtime", "native_conformance", 0,
        help="1 = certify every pump run's drained lifecycle-event "
             "stream against the engine-verify model (exactly-once "
             "publish/retire, dep decrements matching in-degree, "
             "happens-before drain order); divergence raises LintError "
             "with ENG014 findings.  Diagnostic mode: the capture and "
             "replay cost O(events)")))


def _new_stats() -> Dict[str, int]:
    """An executor's counters.  In pump mode ``trampoline_entries`` and
    ``completion_callbacks`` MUST stay 0 (every per-task interpreter
    entry increments one of them); ``attach_plan_*`` say how the attach
    came by its plan (dsl/attach_plan.py); ``member*`` are a compound's
    (:meth:`NativeExecutor._run_members`), 0 of a single pool."""
    return {"trampoline_entries": 0, "completion_callbacks": 0,
            "pop_batches": 0, "done_batches": 0, "pumped_tasks": 0,
            "events_drained": 0, "prefetched_batches": 0,
            "attach_plan_hits": 0, "attach_plan_misses": 0,
            "attach_plan_uncacheable": 0,
            "members_run": 0, "member_kept_tiles": 0,
            "member_kept_bytes": 0, "member_home_bytes": 0,
            "member_restaged_tiles": 0}


@contextlib.contextmanager
def _collector_paused():
    """The cyclic collector held off a stretch that allocates many
    objects and no garbage: it would only walk them, again and again
    (the bind of 11,440 tasks: 200 -> 63 ms on the sandbox's CPU)."""
    collecting = gc.isenabled()
    gc.disable()
    try:
        yield
    finally:
        if collecting:
            gc.enable()


class _TaskInfo:
    """Task stand-in for PINS subscribers on the native path: carries the
    attributes observers read (``task_class.name``, ``prof``, ``repr``)."""

    __slots__ = ("task_class", "prof", "_r")

    def __init__(self, cname: str, detail: Any):
        self.task_class = types.SimpleNamespace(name=cname)
        self.prof: Dict[str, Any] = {}
        self._r = f"{cname}{detail}"

    def __repr__(self) -> str:
        return self._r


class _NativePoolShim:
    """Stand-in taskpool for native-dispatched device tasks: carries the
    failure contract the device layer needs (``failed`` checked before
    every dispatch; ``_force_fail`` called by ``remote_dep._fail_pool``
    on unrecoverable device errors) and aborts the native run so workers
    cannot hang on completions that will never arrive."""

    def __init__(self, executor: "NativeExecutor", name: str):
        self._ex = executor
        self.name = name
        #: the taskpool's id: what every span of this solve carries as
        #: ``pool`` (the device module reads it off its tasks' pool)
        self.taskpool_id = executor.taskpool.taskpool_id
        self.failed = False
        self.fail_reason: Optional[str] = None
        self.context = None
        #: the attach plan's table of next uses, which the pool's tasks
        #: index by ``_tpu_next`` (the device module's victim order)
        self.next_use: Tuple[int, ...] = ()

    def _force_fail(self) -> bool:
        if self.failed:
            return False
        self.failed = True
        if self.fail_reason is None:  # _fail_pool threads the root cause in
            self.fail_reason = "device submit/epilog failed (see error log)"
        ng = getattr(self._ex, "_ng", None)
        if ng is not None:
            ng.fail()  # release the native workers
        return True

    def task_done(self, task=None) -> None:
        pass  # quiescence is the native engine's, not a termdet's


class _NativeDeviceTask(Task):
    """Task instance handed to the device manager from the native path:
    a real :class:`Task` (the device layer's staging, wave-signature and
    epilog code read its slots unchanged) plus the native task id its
    completion must signal and the PINS opt-in marker."""

    __slots__ = ("native_id", "pins_exec", "_wbs")

    def __init__(self, pool, tclass, locals_, priority):
        super().__init__(pool, tclass, locals_, priority)
        self.native_id = -1
        #: (source Data, home Data) pairs the pump loop lands at retire
        #: (pre-resolved cross-tile write-backs; empty in the common case)
        self._wbs: List[Tuple[Any, Any]] = []
        #: tells TpuDevice to fire EXEC_BEGIN/END (with wave metadata in
        #: ``prof``) around the actual device dispatch: on the native
        #: path no scheduling core wraps the hook, so without this the
        #: trace shows a host-gap hole where device waves ran
        self.pins_exec = True


class _EventDrain:
    """Batched publisher for the native lifecycle-event buffer: maps the
    engine's (kind, a, b) records onto the existing PINS sites so
    hb-check, the binary tracer and the SLO plane order native-scheduled
    runs — with ZERO per-task interpreter work on the hot path (one
    drain per pump batch).  Kind mapping:

    * ``EVT_DEP_DEC``  -> :data:`pins.DEP_DECREMENT` with tracker
      ``("native", graph.hb_token)`` (one record per native dep-counter
      decrement, ``ready`` flagging the release that armed the task);
    * ``EVT_PUBLISH``  -> :data:`pins.SCHEDULE_BEGIN` with a 1-task
      batch (the native SchedQ push — ``task_publish`` in hb terms);
    * ``EVT_RETIRE``   -> :data:`pins.NATIVE_TASK_DONE` (same payload
      the legacy ``task_done`` path fires, double-completes included).
    """

    def __init__(self, ng, pump_index: Dict[int, Any], cap: int,
                 capture: Optional[List[Tuple[int, int, int]]] = None):
        import ctypes

        self.ng = ng
        self.index = pump_index
        #: when set (runtime_native_conformance), every drained record
        #: is retained raw for the post-quiescence model replay
        self.capture = capture
        n = max(1024, cap * 4)
        self.k = (ctypes.c_int32 * n)()
        self.a = (ctypes.c_int64 * n)()
        self.b = (ctypes.c_int64 * n)()

    def drain(self) -> int:
        from ..core.deps import fire_native_dep_dec

        ng = self.ng
        k, a, b = self.k, self.a, self.b
        dep_on = pins.active(pins.DEP_DECREMENT)
        sched_on = pins.active(pins.SCHEDULE_BEGIN)
        done_on = pins.active(pins.NATIVE_TASK_DONE)
        token = ng.hb_token
        total = 0
        while True:
            n = ng.events_drain(k, a, b)
            if n == 0:
                return total
            total += n
            if self.capture is not None:
                self.capture.extend(
                    (int(k[i]), int(a[i]), int(b[i])) for i in range(n))
            for i in range(n):
                kind = k[i]
                if kind == ng.EVT_DEP_DEC:
                    if dep_on:
                        fire_native_dep_dec(token, int(a[i]), bool(b[i]))
                elif kind == ng.EVT_PUBLISH:
                    if sched_on:
                        t = self.index.get(a[i])
                        if t is not None:
                            pins.fire(pins.SCHEDULE_BEGIN, None, (t,))
                elif done_on:
                    pins.fire(pins.NATIVE_TASK_DONE, None, {
                        "graph": token, "task": int(a[i]),
                        "accepted": bool(b[i])})
            if n < len(k):
                return total


def _pump_failure(shims) -> Optional[str]:
    for s in shims:
        if s is not None and s.failed:
            return s.fail_reason or "device submit/epilog failed"
    return None


def _pump_loop(ng, dev, pump_index: Dict[int, Any], stats: Dict[str, int],
               shims, ev: Optional[_EventDrain] = None,
               retire_cb=None, pool: int = 0, first_submit=None) -> int:
    """The zero-interpreter hot loop, shared by :class:`NativeExecutor`
    and :class:`NativeServeExecutor`.  Per iteration: ONE ``pop_batch``
    ctypes call returns up to ``runtime_native_drain`` ready native ids,
    the device manager dispatches them (wave batching intact, completion
    deferred), rare cross-tile write-backs land, the batch retires
    through :func:`..core.scheduling.retire_native` (COMPLETE_EXEC pins
    only), and ONE ``done_batch`` call runs every dep decrement /
    successor push / quiescence count natively.  Python cost is
    O(batches), not O(tasks).

    When the device carries the staging pipeline (``stage_depth > 1``),
    the pump keeps a WINDOW of up to ``stage_depth`` popped-but-not-yet
    -submitted batches: each freshly popped batch's input tiles are
    handed to the device's transfer lane (``prestage_batch``) the moment
    it is popped, so batch N+1's host->device transfers overlap batch
    N's compute (ROADMAP 5(b) double buffering).  The pump looks first
    (``prestage_tiles``): a batch whose tiles are all resident costs the
    lane its ``dev:stage_in`` span and nothing else, and the pump does
    not wait for it.  To keep the window
    meaningful when the whole ready frontier fits one ``pop_batch``, the
    pop buffer shrinks to ``cap // stage_depth``: one wide ready wave
    splits into ``stage_depth`` chunks and pipelines INTRA-wave.  A
    prestage failure is non-fatal — the submit path restages the tile
    synchronously and fails loudly if the data is truly bad.

    Every step is one ``pins.span`` per BATCH (``pump:pop``,
    ``pump:stage_wait``, ``pump:land``, ``pump:retire``, ``pump:done``,
    ``pump:events``; ``dev:submit_batch`` is the device module's),
    carrying ``pool`` (0 when several pools share the pump), ``rank`` and
    the batch's number ``batch``, which the transfer lane's
    ``dev:stage_in`` repeats on its thread.  ``first_submit`` is called
    once, before the first batch is handed to the device (a compound's
    ``pump:member_gap`` ends there)."""
    import ctypes
    from collections import deque

    from ..core import scheduling
    from ..data.data import land_into_home

    chunk, depth = _pump_window(dev)
    lane = None
    if depth > 1:
        from ..device.staging import StageLane
        lane = StageLane(dev)
    free = deque((ctypes.c_int64 * chunk)() for _ in range(depth))
    window: deque = deque()  # (buf, n, batch, stage_job|None, seq)
    rank = getattr(dev.context, "rank", 0)
    seq = 0  # number of the next batch handed to the window
    done = 0

    def prestage(batch, tiles):
        """The lane's job for ``batch`` (numbered ``seq``): the tiles
        the pump found missing, which may be none."""
        if lane is None:
            return None
        if tiles:
            stats["prefetched_batches"] += 1
        else:
            dev.stats["prestage_skipped"] += 1
        return lane.stage(batch, seq, tiles)
    try:
        while True:
            # fill the prefetch window: pop ready batches and kick their
            # stage-in transfers before the oldest batch submits
            while free and len(window) < depth:
                buf = free.popleft()
                with pins.span("pump:pop", pool=pool, rank=rank,
                               batch=seq) as sp:
                    n = ng.pop_batch(buf)
                    batch = [pump_index[buf[i]] for i in range(n)]
                    sp.note(n=n)
                if n == 0:
                    free.appendleft(buf)
                    break
                stats["pop_batches"] += 1
                stats["pumped_tasks"] += n
                tiles, nbytes = (dev.prestage_tiles(batch)
                                 if lane is not None else ((), 0))
                if (tiles and not window and free and n >= 4
                        and nbytes >= dev.stage_split_bytes):
                    # the whole ready frontier fit ONE buffer, the
                    # window is otherwise idle, and there is REAL
                    # transfer work to hide: re-slice the batch across
                    # the free slots so the lane prestages slot k+1
                    # while slot k computes.  Without the re-slice
                    # every prestage completes before its own submit
                    # starts and the double buffer degenerates to
                    # synchronous staging; without the bytes gate the
                    # split would shrink vmappable waves on dispatch-
                    # bound runs for no transfer win.
                    ids = [buf[i] for i in range(n)]
                    bufs = [buf] + [free.popleft() for _ in range(depth - 1)]
                    per = (n + len(bufs) - 1) // len(bufs)
                    off = 0
                    for b in bufs:
                        k = min(per, n - off)
                        if k <= 0:
                            free.append(b)
                            continue
                        for i in range(k):
                            b[i] = ids[off + i]
                        sub = batch[off:off + k]
                        off += k
                        window.append((b, k, sub, prestage(
                            sub, dev.prestage_tiles(sub)[0]), seq))
                        seq += 1
                    continue
                window.append((buf, n, batch, prestage(batch, tiles), seq))
                seq += 1
            if not window:
                why = _pump_failure(shims)
                if why is not None:
                    raise RuntimeError(f"native device run failed: {why}")
                if ng.quiesced():
                    break
                raise RuntimeError(
                    f"native pump stalled: ready queue empty with {done} "
                    f"retired and {ng.sched_pending()} queued "
                    "(cycle or missing commit?)")
            buf, n, batch, job, b = window.popleft()
            if job is not None:
                with pins.span("pump:stage_wait", pool=pool, rank=rank,
                               batch=b, n=n):
                    # (the span is one a batch for whoever counts them;
                    # a job without tiles is nothing to wait for)
                    if job.tiles:
                        job.wait()  # logs prestage errors; submit restages
            if first_submit is not None:
                first_submit()
                first_submit = None
            dev.submit_batch(batch, batch_no=b)
            why = _pump_failure(shims)
            if why is not None:
                raise RuntimeError(f"native device run failed: {why}")
            with pins.span("pump:land", pool=pool, rank=rank, batch=b, n=n):
                for t in batch:
                    for (src, home) in t._wbs:
                        land_into_home(home, src.newest_copy().payload)
            with pins.span("pump:retire", pool=pool, rank=rank, batch=b,
                           n=n):
                scheduling.retire_native(batch, dev)
            with pins.span("pump:done", pool=pool, rank=rank, batch=b, n=n):
                done += ng.done_batch(buf, n)
                stats["done_batches"] += 1
                free.append(buf)
                if retire_cb is not None:
                    retire_cb(batch)
            if ev is not None:
                with pins.span("pump:events", pool=pool, rank=rank,
                               batch=b):
                    stats["events_drained"] += ev.drain()
    finally:
        if lane is not None:
            lane.close()
    if ev is not None:
        stats["events_drained"] += ev.drain()
    return done


class NativeExecutor:
    """Run a PTG taskpool's full DAG on the native engine.

    ``NativeExecutor(tp).run(nthreads=4)`` executes every task and applies
    the declared write-backs to the backing collections, exactly like the
    dynamic runtime's CPU path.  The taskpool must be unstarted (never
    attached to a Context).

    ``native_device=True`` routes every task class carrying an
    accelerator BODY through the :class:`~parsec_tpu.device.tpu.TpuDevice`
    manager (wave batching, lanes, LRU residency intact): the native
    worker's trampoline only *enqueues* the task (chore returns ASYNC)
    and the device manager's completion callback signals
    ``pz_task_done`` — dependency release never re-enters the
    interpreter.  Classes without an accelerator BODY fall back to their
    CPU body through the Data staging discipline (mixed DAGs stay
    coherent across host/device copies).  Pass ``device=`` to reuse one
    device instance (and its jit cache) across executors.

    A :class:`~parsec_tpu.core.compound.CompoundTaskpool` of unstarted PTG
    taskpools (``compose(a, b, c)``) runs its members in order, member
    *i+1*'s first task after member *i*'s last has retired
    (``parsec_compose``), under ONE device, residency and jit cache: every
    member is planned (each by its own attach-plan key) and bound before
    :meth:`run`, a member's end neither detaches nor flushes, and of all
    members only the versions that no LATER member rewrites go home, as
    they become final.  A tile that member *i* wrote stays on the device,
    dirty and the newest version, until member *i+1*'s staging walk finds
    it there.  ``stats["member*"]`` and the ``pump:member`` /
    ``pump:member_gap`` spans say whether that held (``docs/TRACING.md``).
    :meth:`run` returns the tasks run over all members; :meth:`close`
    does what it does for one pool, once.
    """

    def __init__(self, tp: PTGTaskpool, *, graph: Optional[TaskGraph] = None,
                 native_device: bool = False, device=None,
                 fusion: Optional[str] = None,
                 _shared_graph=None, _tenant: int = 0, _compound=None):
        from .. import native

        if not native.available():
            raise RuntimeError(
                f"native core unavailable: {native.build_error()}")
        self._native = native
        self.taskpool = tp
        self.native_device = bool(native_device)
        self.device = device
        #: the device is this executor's to detach when nobody closed it
        self._own_device = device is None
        #: a compound's member: ``(the compound's executor, the ids of the
        #: tiles that later members rewrite)``; its counters are the
        #: compound's, its device the compound's to detach
        self._compound = _compound
        #: control-plane counters (the zero-entry pin reads them)
        self.stats: Dict[str, int] = _new_stats() if _compound is None \
            else _compound[0].stats
        #: a compound's executor: one executor a member, in order
        self._members: List["NativeExecutor"] = []
        #: a member's collection tiles (device path): the ones its tasks
        #: write, and all it names; what a compound counts its
        #: hand-overs by
        self._written: List[Any] = []
        self._touched: List[Any] = []
        #: serve mode (NativeServeExecutor): build into ITS shared native
        #: graph under this tenant id instead of owning one
        self._shared_graph = _shared_graph
        self._tenant = int(_tenant)
        self._pump = False          # zero-entry lifecycle configured
        self._events_on = False     # native event buffer armed at build
        #: native id -> prebuilt device task, the pump loop's dispatch map
        self._pump_index: Dict[int, _NativeDeviceTask] = {}
        self._roots: List[int] = []
        #: native-id edges as declared to the native graph, kept only
        #: under runtime_native_conformance for the post-run stream replay
        self._conformance = False
        self._edges: List[Tuple[int, int]] = []
        self._new_tiles: Dict[Tuple, np.ndarray] = {}
        #: the trampoline's bodies, by user tag (the numpy path and the
        #: legacy ASYNC-chore protocol; the pump calls none, and has none)
        self._bodies: List[Callable[[], Any]] = []
        #: the native nodes this executor declared (a fused region is
        #: one) and the id of the first
        self._n_native = 0
        self._native_base = 0
        #: supertask fusion (dsl.fusion): regions of the captured graph
        #: collapsed to ONE native node each — one device dispatch, one
        #: pz_task_done retiring N member tasks.  ``fusion=None`` reads
        #: the runtime_fusion MCA param; device dispatch only (the win
        #: is the per-task device enqueue, which CPU bodies don't pay).
        self._regions: List[Any] = []
        self._pool_shim: Optional[_NativePoolShim] = None
        if isinstance(tp, CompoundTaskpool):
            if graph is not None or _shared_graph is not None:
                raise ValueError("a compound takes no captured graph and "
                                 "is nobody's tenant: its members are "
                                 "captured one by one")
            self._ng = None
            if self.native_device and device is None:
                self.device = self._make_device()
            self._attach_members(tp, fusion)
            return
        if self.native_device:
            if device is None:
                self.device = self._make_device()
            self._pool_shim = _NativePoolShim(self, f"native:{tp.ptg.name}")
        with pins.span("attach:build", pool=tp.taskpool_id, rank=0) as sp:
            if self.native_device:
                with pins.span("attach:partition", pool=tp.taskpool_id,
                               rank=0):
                    plan, how = self._plan_for(tp, graph, fusion)
                with pins.span("attach:bind", pool=tp.taskpool_id, rank=0), \
                        _collector_paused():
                    self._bind(plan, _compound[1] if _compound else ())
                sp.note(tasks=len(plan.tasks), regions=len(plan.fused),
                        plan=how)
            else:
                # the numpy path captures and builds every time;
                # rebind() is what amortizes it there
                self.graph = graph if graph is not None \
                    else capture(tp, ranks=[0])
                self._trace_objs: Dict[Tuple, Any] = {}
                self._build()
                sp.note(tasks=len(self.graph.nodes), regions=0)

    # -- a compound: one executor a member, bound last member first ------
    def _attach_members(self, tp: CompoundTaskpool,
                        fusion: Optional[str]) -> None:
        """One executor a member of ``tp``, all over this executor's
        device and counters.  The LAST member is bound first: what a
        member sends home is what its plan says less every tile that a
        later member writes again, and that is known once the later
        members' plans are bound to their tiles."""
        def flat(pool):
            for m in pool.members:
                if isinstance(m, CompoundTaskpool):
                    yield from flat(m)
                else:
                    yield m

        pools = list(flat(tp))
        for m in pools:
            if not isinstance(m, PTGTaskpool):
                raise TypeError(
                    "a compound on the native engine is made of PTG "
                    f"taskpools: {m!r} is a {type(m).__name__}")
        held: set = set()
        members: List[NativeExecutor] = []
        try:
            for m in reversed(pools):
                members.append(NativeExecutor(
                    m, native_device=self.native_device, device=self.device,
                    fusion=fusion, _compound=(self, frozenset(held))))
                held.update(d.data_id for d in members[-1]._written)
        finally:
            # (a member that could not be built leaves the others to
            # close())
            self._members = members[::-1]

    def _plan_for(self, tp: PTGTaskpool, graph: Optional[TaskGraph],
                  fusion: Optional[str]):
        """This pool's native nodes, tasks and fused regions (the
        ``attach:partition`` span): the stored :class:`AttachPlan` of its
        shape, or a new one — captured, partitioned and resolved under
        ``attach:plan`` — which is stored when the shape has a key.
        Returns ``(plan, "hit" | "miss" | "uncacheable")``."""
        from . import attach_plan
        from .fusion import fusion_mode, fusion_max_tasks, fusion_scan_mode

        mode = fusion if fusion is not None else fusion_mode()
        if mode in ("", "off"):
            cfg: Tuple = ("off",)
        else:
            cfg = (mode, fusion_max_tasks(device=self.device),
                   fusion_scan_mode())
        #: the pump's batches, which the plan's table of next uses
        #: ranks the tasks by
        window = _pump_window(self.device)
        key = None
        if graph is None:
            # (a handed-in graph is the caller's: nothing here can vouch
            # for what it was captured from)
            try:
                key = attach_plan.plan_key(tp, (0,), cfg, window)
            except attach_plan.Uncacheable as e:
                from ..utils import debug

                debug.verbose(2, "attach", "%s: no attach plan kept (%s)",
                              tp.ptg.name, e)
        if key is not None:
            plan = attach_plan.lookup(key)
            if plan is not None:
                self.stats["attach_plan_hits"] += 1
                return plan, "hit"
        with pins.span("attach:plan", pool=tp.taskpool_id, rank=0):
            g = graph if graph is not None else capture(tp, ranks=[0])
            # (a plan that is stored nowhere is built again at every
            # solve: it gets no table of next uses, which a solve would
            # pay for each time)
            plan = attach_plan.build_plan(
                tp, g, self._partition_regions(g, cfg),
                window if key is not None else None)
        if key is None:
            self.stats["attach_plan_uncacheable"] += 1
            return plan, "uncacheable"
        plan.key = key
        attach_plan.store(plan)
        self.stats["attach_plan_misses"] += 1
        return plan, "miss"

    def _partition_regions(self, g: TaskGraph, cfg: Tuple) -> List[Any]:
        from ..utils import debug
        from .fusion import partition

        if cfg[0] == "off":
            return []
        try:
            return partition(g, self.taskpool.ptg.classes, mode=cfg[0],
                             max_tasks=cfg[1])
        except Exception as e:
            debug.warning("native fusion disabled (%s: %s)",
                          type(e).__name__, e)
            return []

    @staticmethod
    def _make_device():
        """One TpuDevice bound to a minimal single-rank context shim (the
        native engine replaces the dynamic Context; the device module
        only reads ``rank``/``nranks`` from it)."""
        from ..device.tpu import TpuDevice

        if not TpuDevice.available():
            raise RuntimeError(
                "native_device=True requires a JAX device (none available)")
        shim = types.SimpleNamespace(rank=0, nranks=1, devices=[])
        dev = TpuDevice(shim, index=1)
        dev.attach()
        return dev

    # -- tile resolution (same rules as ptg_to_dtd / xla_lower) ----------
    def _payload(self, srckey: Tuple) -> np.ndarray:
        if srckey[0] == "remote":
            # a flow chain that leaves the captured partition: this
            # single-rank executor cannot resolve it (silently handing
            # back a zeros tile would corrupt numerics) — distributed
            # captures go through dsl.native_dist.NativeDistExecutor
            raise RuntimeError(
                f"flow source {srckey[1]}/{srckey[2]} is on another rank; "
                "use NativeDistExecutor for rank-filtered captures")
        consts = self.taskpool.constants
        if srckey[0] == "data":
            _, cname, key = srckey
            d = consts[cname].data_of(*key)
            c = d.newest_copy() or d.get_copy(0)
            if c is None or c.payload is None:
                raise ValueError(f"collection tile {cname}{key} has no payload")
            return c.payload
        t = self._new_tiles.get(srckey)
        if t is None:
            # ("new", producer tid, flow): per-flow NEW shape (dep
            # [type=...] props) resolved by the taskpool
            _, (pc_name, _locs), fname = srckey
            shape, dtype = self.taskpool.new_tile_spec(pc_name, fname)
            t = self._new_tiles[srckey] = np.zeros(shape, dtype)
        return t

    def _bind(self, plan, held=()) -> None:
        """Bind ``plan`` to this pool's tiles (the ``attach:bind`` span):
        one ``data_of`` a distinct tile and one ``scratch.new`` a ``NEW``
        chain, one task object a task, the native graph from the plan's
        arrays in one call.  The first solve of a shape and the hundredth
        run this same code; nothing here reads a dependency expression.
        ``held``: the ids of the tiles that a later member of this pool's
        compound writes again.  The plan's home set is the shape's, the
        same whether the pool runs alone or composed; what a TASK sends
        home is that less the held tiles, decided here, tile by tile."""
        from ..device import scratch
        from .attach_plan import CTL_FLOW

        tp = self.taskpool
        consts = tp.constants
        classes = tp.ptg.classes
        self.graph = plan
        ng = self._shared_graph if self._shared_graph is not None \
            else self._native.NativeGraph()
        self._ng = ng
        self._conformance = _conformance_on()
        # pump mode (zero-interpreter lifecycle): decided BEFORE the
        # commit because committing pushes source tasks, and those pushes
        # must land in the configured native SchedQ
        if self._shared_graph is not None:
            # the serve executor already called sched_config("wdrr") on
            # the shared graph; a CPU-fallback body would need the
            # trampoline protocol the pump never runs
            if plan.has_cpu_bodies:
                raise RuntimeError(
                    "NativeServeExecutor requires all-device task "
                    f"classes ({tp.ptg.name} has CPU-only classes)")
            self._pump = True
        elif (not plan.has_cpu_bodies
                and getattr(self.device, "_eager", True)):
            from ..core.sched.rnd import rnd_seed

            # the schedule explorer's seed reaches the native scheduler
            # through the SAME param the Python rnd scheduler reads
            ng.sched_config(policy="prio", quantum=0, seed=rnd_seed())
            self._pump = True

        # the tiles: a collection's Data, or a scratch tile
        # (device/scratch.py: no payload, born where its first task
        # runs) with the users the plan counted
        datas: List[Any] = []
        new_spec: Dict[Tuple[str, str], Tuple] = {}
        for srckey, users in zip(plan.tiles, plan.tile_users):
            if srckey[0] == "data":
                datas.append(consts[srckey[1]].data_of(*srckey[2]))
                continue
            _, (pc_name, _locs), fname = srckey
            spec = new_spec.get((pc_name, fname))
            if spec is None:
                # per-flow NEW shape (dep [type=...] props), resolved
                # by the taskpool
                spec = new_spec[(pc_name, fname)] = \
                    tp.new_tile_spec(pc_name, fname)
            d = scratch.new(("native_new",) + tuple(srckey[1:]), *spec)
            scratch.add_users(d, users)
            datas.append(d)
        if self._compound is not None:
            self._written = [datas[s] for s in plan.written]
            self._touched = [d for d, srckey in zip(datas, plan.tiles)
                             if srckey[0] == "data"]

        # the native graph, edges and all, uncommitted
        n = self._n_native = len(plan.native)
        base = self._native_base = ng.add_bulk(
            plan.native_prio, self._tenant, plan.edge_pred, plan.edge_succ)
        self._regions = [row[0].region for row in plan.fused]
        self._roots = [base + r for r in plan.roots]
        if self._conformance:
            # the post-run replay rebuilds the DAG in native-id space
            self._edges = [(base + a, base + b) for a, b in
                           zip(plan.edge_pred, plan.edge_succ)]

        # per class: the vtable, the chore, the flow modes and the
        # body_globals' value specs (the plan keeps no constant)
        per_class = []
        for cname, on_device in zip(plan.classes, plan.device_class):
            pc = classes[cname]
            gvals = [("value", consts[g], AccessMode.VALUE)
                     for g in pc.body_globals]
            per_class.append((
                pc, TaskClass(cname),
                self._device_chore(pc) if on_device else None,
                tuple(f.mode for f in pc.flows), gvals))
        fused_classes: Dict[str, TaskClass] = {}
        ctl = ("ctl", None, CTL)
        shim = self._pool_shim
        shim.next_use = plan.next_use
        next_at = plan.next_at
        dev = self.device
        pump = self._pump
        index = self._pump_index
        #: position -> the object PINS observers see for that task
        #: (device tasks: the Task itself, a fused region's members its
        #: supertask; CPU bodies: a _TaskInfo) — the static dep-edge
        #: emitter walks this
        objs: List[Any] = [None] * len(plan.tasks)
        self._trace_objs = objs
        bodies = self._bodies
        for nid, pos in enumerate(plan.native, base):
            if pos < 0:
                row = plan.fused[~pos]
                task = self._fused_task(row, datas, fused_classes)
                for m in row[2]:
                    objs[m] = task
            else:
                ci, locs, prio, slots, values, home, wbs, donate = \
                    plan.tasks[pos]
                pc, tclass, chore, modes, gvals = per_class[ci]
                if chore is None:
                    # a CPU-fallback body needs the trampoline protocol
                    # (its presence kept the DAG out of the pump)
                    info = objs[pos] = _TaskInfo(pc.name, locs)
                    bodies.append(self._cpu_data_body(
                        pc, info, slots, values, gvals, wbs, datas))
                    continue
                task = objs[pos] = _NativeDeviceTask(shim, tclass, locs,
                                                     prio)
                task.selected_chore = chore
                # body_args in prepare_input layout: flows by declaration
                # order (CTL placeholders keep f.index alignment), then
                # values in the POSITIONAL contract order params, defs,
                # body_globals — the order _wrap_device_body zips its
                # names against (ptg.py; the dynamic path's
                # prepare_input emits the same order)
                task.body_args = [
                    ctl if s == CTL_FLOW else
                    ("data", datas[s] if s >= 0 else None, m)
                    for s, m in zip(slots, modes)]
                task.body_args += values
                task.body_args += gvals
                if held and home:
                    home = tuple(p for p in home
                                 if datas[slots[p]].data_id not in held)
                task._tpu_home = home
                task._tpu_donate = donate
                if wbs:
                    # write-backs PRE-RESOLVED to (source Data, home
                    # Data) pairs: whoever retires the task lands them
                    # without touching the taskpool
                    task._wbs = [(datas[a], datas[b]) for a, b in wbs]
            task.selected_device = dev
            task.native_id = nid
            if next_at:
                task._tpu_next = next_at[nid - base]
            index[nid] = task
            if not pump:
                # legacy ASYNC-chore protocol: the trampoline enqueues,
                # the device manager's completion signals pz_task_done
                task.on_complete = self._on_complete
                bodies.append(self._enqueue_body(task))

        if self._pump and (self._conformance
                           or pins.active(pins.DEP_DECREMENT)
                           or pins.active(pins.NATIVE_TASK_DONE)):
            # observers already installed (or conformance certification
            # requested): arm the native event buffer now so commit-time
            # source publishes are captured too
            ng.events_enable(True)
            self._events_on = True
        # commit only after EVERY edge is declared: committing a task arms
        # it, and a task whose in-edges arrive after arming would release
        # early (the commit token covers a task's own declaration window,
        # which for this whole-DAG build is the full edge pass)
        ng.commit_range(base, n)
        if self._shared_graph is None:
            ng.seal()

    def _fused_task(self, row, datas, tclasses) -> "_NativeDeviceTask":
        """The ONE prebuilt supertask of a fused region: its chore body
        is the region's jitted program (:class:`..dsl.fusion.FusedPlan`);
        one completion lands every member's cross-tile write-backs and
        retires all N members natively."""
        fp, slots, members, wbs = row
        tc = tclasses.get(fp.name)
        if tc is None:
            # bare vtable (every completion-path slot is None: successor
            # release belongs to the native engine)
            tc = tclasses[fp.name] = TaskClass(fp.name)
        task = _NativeDeviceTask(self._pool_shim, tc, (fp.region.index,),
                                 fp.priority)
        task.fused_n = len(members)
        chore = Chore(fp.device_type, hook=lambda es, task: HookReturn.ASYNC)
        chore.body_fn = fp.body_fn
        task.selected_chore = chore
        task.body_args = [
            ("data", datas[s], AccessMode(m) if m else AccessMode.IN)
            for s, m in zip(slots, fp.slot_modes)]
        task._wbs = [(datas[a], datas[b]) for a, b in wbs]
        return task

    def _device_chore(self, pc) -> Chore:
        """The Chore of a class, carrying the wrapped accelerator body
        (jit-cache identity preserved via ``_jit_key``)."""
        dev_type, fn = next(
            (dt, f) for dt, f in pc.bodies.items() if dt != DEV_CPU)
        chore = Chore(dev_type, hook=lambda es, task: HookReturn.ASYNC)
        chore.body_fn = _wrap_device_body(pc, fn)
        return chore

    # -- legacy ASYNC-chore protocol (the pump calls neither) -------------
    def _enqueue_body(self, task: "_NativeDeviceTask") -> Callable[[], Any]:
        """Enqueue-only trampoline body: hand the prebuilt Task to the
        device manager and return ASYNC.  Everything per-task beyond this
        enqueue and the completion callback runs either natively or
        inside the device manager."""
        stats = self.stats
        dev = self.device
        shim = self._pool_shim

        def body():
            stats["trampoline_entries"] += 1
            if shim.failed:
                raise RuntimeError(
                    f"native device pool failed: {shim.fail_reason}")
            dev.kernel_scheduler(None, task)
            return True  # ASYNC: pz_task_done releases the successors

        return body

    def _on_complete(self, t: Task) -> None:
        """The ONLY per-task Python on the completion side: land rare
        cross-tile write-backs, then signal the native release."""
        self.stats["completion_callbacks"] += 1
        if t._wbs:
            from ..data.data import land_into_home

            for (src, home) in t._wbs:
                land_into_home(home, src.newest_copy().payload)
        self._ng.task_done(t.native_id)

    def _cpu_data_body(self, pc, info: _TaskInfo, slots, values, gvals,
                       wbs, datas) -> Callable[[], Any]:
        """CPU-only class in a native_device DAG: run its CPU body through
        the Data staging discipline (stage_to_cpu + version bumps) so
        host and device copies stay coherent across the mixed graph."""
        from .dtd import stage_to_cpu

        fn = pc.bodies.get(DEV_CPU)
        if fn is None:
            raise ValueError(f"native_exec: class {pc.name} has no body")
        flow_specs = [(f.name, datas[s] if s >= 0 else None, f.mode)
                      for f, s in zip(pc.flows, slots) if f.mode != CTL]
        names = pc.param_names + pc.def_names + pc.body_globals
        scalars = dict(zip(names, (v[1] for v in (*values, *gvals))))
        lands = [(datas[a], datas[b]) for a, b in wbs]

        def body():
            pins.fire(pins.EXEC_BEGIN, None, info)
            kw: Dict[str, Any] = dict(scalars)
            writable = []
            for fname, data, mode in flow_specs:
                if data is None:
                    kw[fname] = None
                    continue
                arr = stage_to_cpu(data)
                data.transfer_ownership(0, mode & AccessMode.INOUT)
                kw[fname] = arr
                if mode & AccessMode.OUT:
                    writable.append(data)
            result = fn(**kw)
            if result is not None and not isinstance(result, HookReturn):
                outs = (result if isinstance(result, (tuple, list))
                        else (result,))
                for data, new in zip(writable, outs):
                    data.get_copy(0).payload = np.asarray(new)
            for data in writable:
                data.version_bump(0)
            pins.fire(pins.EXEC_END, None, info)
            pins.fire(pins.COMPLETE_EXEC_BEGIN, None, info)
            if lands:
                from ..data.data import land_into_home

                for (src, home) in lands:
                    land_into_home(home, src.newest_copy().payload)
            pins.fire(pins.COMPLETE_EXEC_END, None, info)
            return False  # synchronous: the worker completes it inline

        return body

    def _build(self) -> None:
        """The numpy path's native graph: a task, a body and a commit a
        node, an ``add_dep`` an edge."""
        g = self.graph
        ng = self._ng = self._native.NativeGraph()
        index = self._index = {}
        for tid, node in g.nodes.items():
            index[tid] = ng.add_task(priority=node.priority,
                                     user_tag=len(self._bodies))
            self._bodies.append(self._make_body(tid))
        for tid, node in g.nodes.items():
            me = index[tid]
            for (_f, succ, _sf) in node.out_edges:
                if index[succ] != me:
                    ng.add_dep(me, index[succ])
        # commit only after EVERY edge is declared: committing a task arms
        # it, and a task whose in-edges arrive after arming would release
        # early
        for nid in index.values():
            ng.commit(nid)
        ng.seal()
        self._n_native = len(self._bodies)

    def _emit_trace_edges(self) -> None:
        """Bulk dep_edge emission for trace observers: the native path
        never runs per-task release_deps in Python, so the captured DAG's
        edges are published in ONE pre-run pass through the
        RELEASE_DEPS_END site (payload shape matches the dynamic
        runtime's) — profiling.critpath gets its predecessor map without
        any hot-loop instrumentation."""
        objs = self._trace_objs
        if self.native_device:
            # (an attach plan keeps the edges by position)
            edges = enumerate(self.graph.succs)
        else:
            edges = ((tid, [s for (_f, s, _sf) in node.out_edges])
                     for tid, node in self.graph.nodes.items())
        for t, out in edges:
            if not out:
                continue
            me = objs[t]
            succs = [objs[s] for s in out if objs[s] is not me]
            if succs:
                pins.fire(pins.RELEASE_DEPS_END, None, (me, succs))

    # -- default numpy path ----------------------------------------------
    def _make_body(self, tid: Tuple) -> Callable[[], None]:
        """One node's trampoline body: its CPU BODY over in-place numpy
        tiles."""
        tp = self.taskpool
        g = self.graph
        consts = tp.constants
        cname, locs = tid
        pc = tp.ptg.classes[cname]
        # per-class invariants hoisted once (body construction runs per
        # LOCAL TASK and is a measured chunk of distributed-run startup)
        cinfo = getattr(self, "_cls_cache", None)
        if cinfo is None:
            cinfo = self._cls_cache = {}
        cached = cinfo.get(cname)
        if cached is None:
            fn = pc.bodies.get(DEV_CPU)
            if fn is None:
                raise ValueError(
                    f"native_exec: class {cname} has no CPU body")
            data_flows = [f for f in pc.flows if f.mode != CTL]
            base_scalars = {n: consts[n] for n in pc.body_globals}
            cached = cinfo[cname] = (fn, data_flows, base_scalars)
        fn, data_flows, base_scalars = cached
        node = g.nodes[tid]

        # resolve flow kwargs lazily at execution time: a flow's source
        # payload may be attached after construction, and "new" tiles are
        # shared with whichever predecessor created them
        flow_specs: List[Tuple[str, Optional[Tuple]]] = []
        for f in data_flows:
            src = node.flow_sources.get(f.name)
            if src is None and not (f.mode & AccessMode.OUT):
                flow_specs.append((f.name, None))  # unmatched IN: body gets None
            else:
                flow_specs.append((f.name, source_tile(g, tid, f.name)))
        scalars = dict(base_scalars)
        scalars.update(zip(pc.param_names, locs))
        if pc.def_names:
            env = pc.env_of(locs, consts)
            for n in pc.def_names:
                scalars[n] = env[n]
        # write-back sources are fixed at capture time: resolve the chains
        # once here, not on the hot dispatch path
        write_backs = []
        for (fname, cname2, key) in node.write_backs:
            src = source_tile(g, tid, fname)
            home = ("data", cname2, tuple(key))
            write_backs.append((src if src != home else None, cname2, tuple(key)))

        info = _TaskInfo(cname, locs)
        self._trace_objs[tid] = info

        def body() -> None:
            # PINS sites fire with es=None ("external" stream): the native
            # engine owns scheduling, but observers (task_profiler, alperf,
            # SDE, binary tracer) see the same exec/complete lifecycle as
            # on the dynamic path
            pins.fire(pins.EXEC_BEGIN, None, info)
            kw: Dict[str, Any] = dict(scalars)
            for fname, srckey in flow_specs:
                kw[fname] = None if srckey is None else self._payload(srckey)
            fn(**kw)
            pins.fire(pins.EXEC_END, None, info)
            pins.fire(pins.COMPLETE_EXEC_BEGIN, None, info)
            # write-backs run at producer completion (dynamic runtime's
            # _write_back); chain successors are DAG-ordered after us.
            # Collections resolve through self.taskpool DYNAMICALLY so a
            # rebind() onto a same-shape taskpool redirects them.
            for (src, cname2, key) in write_backs:
                if src is not None:
                    np.copyto(self._payload(("data", cname2, key)),
                              self._payload(src))
                self.taskpool.constants[cname2].data_of(*key).version_bump(0)
            pins.fire(pins.COMPLETE_EXEC_END, None, info)

        return body

    def run(self, nthreads: int = 4, _first_submit=None) -> int:
        """Execute to quiescence; returns the number of tasks run.
        Honors the ``runtime_vpmap`` MCA param: workers split into VP
        locality domains and the native steal path prefers same-VP
        victims (reference lfq hierarchy).  ``_first_submit`` (a
        compound's, for its member): called once before the first batch
        reaches the device."""
        if self._members:
            return self._run_members(nthreads)
        bodies = self._bodies
        self._apply_vpmap(nthreads)
        if pins.active(pins.RELEASE_DEPS_END):
            self._emit_trace_edges()
        if _first_submit is not None and not self._pump:
            _first_submit()  # (no pump: the run itself is the hand-over)
        if not self.native_device:
            def trampoline(_task_id: int, user_tag: int) -> None:
                bodies[user_tag]()

            n = self._ng.run(trampoline, nthreads=nthreads)
        elif self._pump:
            n = self._run_pump(_first_submit)
        else:
            def atrampoline(_task_id: int, user_tag: int):
                return bodies[user_tag]()

            try:
                n = self._ng.run_async(atrampoline, nthreads=nthreads)
            except RuntimeError:
                if self._pool_shim is not None and self._pool_shim.failed:
                    raise RuntimeError(
                        "native device run failed: "
                        f"{self._pool_shim.fail_reason}") from None
                raise
            if self._pool_shim is not None and self._pool_shim.failed:
                raise RuntimeError(
                    f"native device run failed: {self._pool_shim.fail_reason}")
        if n != self._n_native:
            raise RuntimeError(
                f"native engine retired {n}/{self._n_native} tasks")
        # fused regions collapse N graph tasks into one native node:
        # report LOGICAL task progress (callers compare against the
        # taskpool's task count; without fusion the two are equal)
        return len(self.graph.nodes)

    def _run_members(self, nthreads: int) -> int:
        """A compound's run: its members in order, each to quiescence
        before the next one's first task (``parsec_compose``), nothing
        detached or flushed in between.  ``pump:member`` is one member's
        run; its first child from the second member on is
        ``pump:member_gap``, which lasts until the member hands its first
        batch to the device and carries what the device kept for it and
        its successors (``kept_tiles``, ``kept_bytes``: the tiles that
        the members so far wrote and a later one names, where their
        newest version is on the device).  On the device path the device
        counts, while the compound runs, what should not happen: an owed
        tile's copy home, a handed-over tile staged in from the host
        (``TpuDevice.pool_boundary``)."""
        members = self._members
        dev = self.device if self.native_device else None
        stats = self.stats
        faults = ("owed_home_bytes", "handed_restaged")
        base = [dev.stats[k] for k in faults] if dev is not None else None
        written: Dict[int, Any] = {}
        #: the open ``pump:member_gap``, closed by the member's first
        #: submit (or by its end, where it submits nothing)
        gap = contextlib.ExitStack()
        total = 0
        try:
            for i, m in enumerate(members):
                pool = m.taskpool.taskpool_id
                with pins.span("pump:member", member=i, pool=pool, rank=0,
                               tasks=len(m.graph.nodes)):
                    if i:
                        sp = gap.enter_context(pins.span(
                            "pump:member_gap", member=i, pool=pool, rank=0))
                    if dev is not None:
                        # what the members so far leave for this one and
                        # the ones after it, and what those write again
                        named = {d.data_id for later in members[i:]
                                 for d in later._touched}
                        kept = dev.pool_boundary(
                            [d for did, d in written.items()
                             if did in named],
                            [d for later in members[i + 1:]
                             for d in later._written])
                        if i:
                            stats["member_kept_tiles"] += kept[0]
                            stats["member_kept_bytes"] += kept[1]
                            sp.note(kept_tiles=kept[0], kept_bytes=kept[1])
                    total += m.run(nthreads, _first_submit=gap.close)
                    gap.close()
                stats["members_run"] += 1
                written.update((d.data_id, d) for d in m._written)
        finally:
            gap.close()
            if dev is not None:
                dev.pool_boundary((), ())
                home, restaged = (dev.stats[k] for k in faults)
                stats["member_home_bytes"] += home - base[0]
                stats["member_restaged_tiles"] += restaged - base[1]
        return total

    def _run_pump(self, first_submit=None) -> int:
        """Drive the zero-interpreter lifecycle for this executor's DAG:
        see :func:`_pump_loop`.  Between graph attach (commit) and drain
        (quiescence) NO per-task Python runs — the trampoline and
        completion callbacks are never installed, and ``self.stats``
        pins it (``trampoline_entries == completion_callbacks == 0``)."""
        ng = self._ng
        drain = self._events_on or pins.active(pins.DEP_DECREMENT) \
            or pins.active(pins.NATIVE_TASK_DONE)
        if drain and not self._events_on:
            # observers installed between build and run: the commit-time
            # source publishes were never buffered — synthesize them so
            # hb still orders publish before exec for the roots
            ng.events_enable(True)
            self._events_on = True
            if pins.active(pins.SCHEDULE_BEGIN):
                for nid in self._roots:
                    t = self._pump_index.get(nid)
                    if t is not None:
                        pins.fire(pins.SCHEDULE_BEGIN, None, (t,))
        capture: Optional[List[Tuple[int, int, int]]] = \
            [] if self._conformance else None
        if self._conformance:
            drain = True
        ev = _EventDrain(ng, self._pump_index, _drain_batch(), capture) \
            if drain else None
        tp = self.taskpool

        def retire_cb(batch):
            # batched progress currency: fused supertasks retire all
            # their members at once (same rule as Taskpool.task_done)
            tp.task_done_batch(sum(
                int(getattr(t, "fused_n", 1) or 1) for t in batch))

        n = _pump_loop(ng, self.device, self._pump_index, self.stats,
                       (self._pool_shim,), ev, retire_cb,
                       pool=tp.taskpool_id, first_submit=first_submit)
        if capture is not None:
            self._certify_drain(capture)
        return n

    def _certify_drain(self, events: List[Tuple[int, int, int]]) -> None:
        """runtime_native_conformance: replay the drained lifecycle
        stream against the engine-verify model; divergence (ENG014) is
        a loud LintError — the drain lied about what the engine did."""
        from ..analysis import engine_verify
        from ..analysis.findings import LintError

        n_tasks = self._native_base + self._n_native
        dag = engine_verify.SeedDag(
            f"pump:{self.taskpool.ptg.name}", n_tasks, tuple(self._edges))
        fs = engine_verify.conformance_findings(
            dag, events, quiesced=self._ng.quiesced())
        if fs:
            raise LintError(
                f"native pump drain failed conformance ({len(fs)} "
                "finding(s))", fs)
        self.stats["conformance_events"] = len(events)

    def _apply_vpmap(self, nthreads: int) -> None:
        from ..core.context import configured_vpmap

        # (a spec that cannot be read raises, loud: a silently-flat run
        # would masquerade as a perfect-locality hierarchical
        # measurement, steals_remote == 0)
        vm = configured_vpmap(nthreads)
        if vm is not None:  # flat: no hierarchy to express
            self._ng.set_vpmap([vm.vp_of(w) for w in range(nthreads)])

    def rebind(self, tp: PTGTaskpool) -> "NativeExecutor":
        """Re-aim this executor at a SAME-SHAPE taskpool (identical task
        classes, parameter spaces, scalar globals and collection names —
        only the collections' tile contents may differ) and rewind the
        native graph for another run.  Amortizes graph capture + body
        construction across repeated runs: the iterative-solver pattern,
        where the reference reuses its compile-time generated structures
        every iteration.  Shape mismatches fail loudly — silently
        re-running the old DAG over a larger problem would factor a
        corner and report success."""
        if self.native_device:
            # device tasks bind Data objects at build time.  What rebind
            # amortizes here, the attach plan (dsl/attach_plan.py)
            # amortizes there: a fresh executor over a same-shape pool
            # only binds the stored plan to the new tiles.
            raise NotImplementedError(
                "rebind is not supported with native_device=True; build a "
                "fresh NativeExecutor(tp, native_device=True, device=dev): "
                "over a taskpool of a shape already seen it binds the "
                "stored attach plan (dsl/attach_plan.py) and captures "
                "nothing")
        self._check_same_shape(tp)
        self.taskpool = tp
        self._new_tiles.clear()
        self._ng.reset()
        for tid in self.graph.nodes:
            self._ng.commit(self._index[tid])
        return self

    def _check_same_shape(self, tp: PTGTaskpool) -> None:
        """Loud same-shape validation (a pass-1 enumeration — the cheap
        ~20% of a capture): the new taskpool's global task placement and
        scalar globals must match the captured structure exactly."""
        consts = tp.constants
        fresh = {}
        for pc in tp.ptg.classes.values():
            for loc in pc.param_space(consts):
                fresh[(pc.name, loc)] = pc.rank_of(loc, consts)
        old = getattr(self.graph, "global_ranks", None)
        if old is not None and fresh != old:
            raise ValueError(
                "rebind: taskpool shape/placement differs from the "
                f"captured structure ({len(fresh)} vs {len(old)} tasks "
                "or moved ranks) — build a fresh executor")
        old_scalars = {k: v for k, v in self.taskpool.constants.items()
                       if isinstance(v, (int, float, str, bool))}
        new_scalars = {k: v for k, v in consts.items()
                       if isinstance(v, (int, float, str, bool))}
        if old_scalars != new_scalars:
            raise ValueError(
                "rebind: scalar globals differ (bodies bake them): "
                f"{old_scalars} vs {new_scalars}")

    def close(self) -> None:
        if getattr(self, "_shared_graph", None) is not None:
            # serve child: graph and device belong to the serve executor
            self._ng = None
            return
        ng = getattr(self, "_ng", None)
        members = getattr(self, "_members", ())
        if ng is None and not members:
            # already closed (or never built): idempotent — ``__del__``
            # calls this again, possibly after the collector finalized
            # parts of a shared device the first call already flushed
            return
        for m in members:
            m.close()  # its native graph; the device is this executor's
        self._members = []
        if ng is not None:
            ng.close()
            self._ng = None
        if getattr(self, "_compound", None) is not None:
            return
        dev = getattr(self, "device", None)
        if dev is not None:
            # flush dirty device tiles home so host-side readers (e.g.
            # TiledMatrix.to_array) see final data; keep the device alive —
            # the caller may be sharing it (and its jit cache) across
            # executors.  A failed flush must be LOUD: swallowing it would
            # hand the caller pre-run host tiles with rc 0 (if another
            # exception is already unwinding, Python chains this one)
            from ..utils import debug

            try:
                dev.detach()
            except Exception as e:
                debug.error("device detach (final write-back) failed: %s", e)
                raise

    def __del__(self):  # pragma: no cover
        try:
            if not getattr(self, "_own_device", True):
                # a device that was handed in is its sharers': a
                # finalizer, on whatever thread the collector happens to
                # run, does not detach it under them (on the write-back
                # committer's own thread the detach's flush could not
                # return before its timeout, with the committer stuck
                # under everybody else's flush meanwhile)
                self.device = None
            self.close()
        except Exception:
            pass


class NativeServeExecutor:
    """Multi-tenant native pump: N unstarted all-device PTG taskpools
    share ONE native graph, ONE device (jit cache included) and ONE pump
    loop; the engine's wdrr SchedQ interleaves tenants by weight with
    exactly the semantics of ``core/sched/wdrr.py`` — per round-robin
    visit a tenant's deficit gains ``quantum x weight`` task credits, a
    drained tenant forfeits its credits and leaves the ring, and within
    a tenant pops follow (priority desc, insertion order).  A small
    tenant's tasks therefore keep retiring beside a 6000-task dpotrf
    backlog: the PR 9 serving-plane fairness contract, preserved under
    native pop with zero interpreter entries per task.

    ``weights`` maps pool position -> wdrr weight (sequence or dict;
    default 1).  :meth:`run` returns per-pool logical task counts;
    :attr:`retire_log` holds ``(pool index, retire position, seconds
    since pump start)`` per retired native task — the fairness pin and
    the per-tenant latency metrics read it.
    """

    def __init__(self, pools: List[PTGTaskpool], *, device=None,
                 weights=None, seed: int = -1):
        from .. import native
        from ..utils import mca_param

        if not native.available():
            raise RuntimeError(
                f"native core unavailable: {native.build_error()}")
        if len(pools) < 1:
            raise ValueError("NativeServeExecutor needs >= 1 taskpool")
        self._native = native
        self.ng = native.NativeGraph()
        self.device = device if device is not None \
            else NativeExecutor._make_device()
        # BEFORE any child builds: commit-time source pushes must land
        # in the configured wdrr bins
        self.ng.sched_config(policy="wdrr", quantum=WDRR_QUANTUM, seed=seed)
        self.stats: Dict[str, int] = _new_stats()
        self.children: List[NativeExecutor] = []
        self.retire_log: List[Tuple[int, int, float]] = []
        self._pos = 0
        for i, tp in enumerate(pools):
            if weights is None:
                w = 1
            elif isinstance(weights, dict):
                w = int(weights.get(i, 1))
            else:
                w = int(weights[i])
            self.ng.set_tenant_weight(i + 1, w)
            self.children.append(NativeExecutor(
                tp, native_device=True, device=self.device,
                _shared_graph=self.ng, _tenant=i + 1))
        self.ng.seal()
        self._pump_index: Dict[int, _NativeDeviceTask] = {}
        self._tenant_of: Dict[int, int] = {}
        for i, ch in enumerate(self.children):
            self._pump_index.update(ch._pump_index)
            for nid in ch._pump_index:
                self._tenant_of[nid] = i
            # the union pump owns the counters (each child's attach has
            # counted its plan); children share the dict so their legacy
            # paths (never taken) still count
            for k, v in ch.stats.items():
                self.stats[k] += v
            ch.stats = self.stats

    def run(self) -> List[int]:
        """Pump the union DAG to quiescence; returns per-pool logical
        task counts (fused regions expanded)."""
        import time

        if pins.active(pins.RELEASE_DEPS_END):
            for ch in self.children:
                ch._emit_trace_edges()
        ng = self.ng
        events_on = any(ch._events_on for ch in self.children)
        drain = events_on or pins.active(pins.DEP_DECREMENT) \
            or pins.active(pins.NATIVE_TASK_DONE)
        if drain and not events_on:
            ng.events_enable(True)
            if pins.active(pins.SCHEDULE_BEGIN):
                for ch in self.children:
                    for nid in ch._roots:
                        t = self._pump_index.get(nid)
                        if t is not None:
                            pins.fire(pins.SCHEDULE_BEGIN, None, (t,))
        ev = _EventDrain(ng, self._pump_index, _drain_batch()) \
            if drain else None
        tenant_of = self._tenant_of
        log = self.retire_log
        t0 = time.perf_counter()

        children = self.children

        def retire_cb(batch):
            now = time.perf_counter() - t0
            done = [0] * len(children)
            for t in batch:
                tenant = tenant_of[t.native_id]
                self._pos += 1
                log.append((tenant, self._pos, now))
                done[tenant] += int(getattr(t, "fused_n", 1) or 1)
            for i, k in enumerate(done):
                if k:  # per-tenant progress currency, one call per pool
                    children[i].taskpool.task_done_batch(k)

        n = _pump_loop(ng, self.device, self._pump_index, self.stats,
                       [ch._pool_shim for ch in self.children], ev,
                       retire_cb)
        expected = sum(ch._n_native for ch in self.children)
        if n != expected:
            raise RuntimeError(
                f"native serve pump retired {n}/{expected} tasks")
        return [len(ch.graph.nodes) for ch in self.children]

    def close(self) -> None:
        for ch in getattr(self, "children", ()):
            ch.close()  # no-op on graph/device: both are shared
        ng = getattr(self, "ng", None)
        if ng is not None:
            ng.close()
            self.ng = None
        dev = getattr(self, "device", None)
        if dev is not None:
            from ..utils import debug

            try:
                dev.detach()
            except Exception as e:
                debug.error("device detach (final write-back) failed: %s", e)
                raise

    def __del__(self):  # pragma: no cover
        try:
            self.close()
        except Exception:
            pass


def run_native(tp, *, nthreads: int = 4,
               native_device: bool = False, device=None) -> int:
    """One-shot: capture + native execution of ``tp``.  With
    ``native_device=True`` accelerator BODYs dispatch through the
    TpuDevice machinery driven by the native scheduler (pump mode —
    zero interpreter entries per task — or the legacy ASYNC-chore
    protocol; see :class:`NativeExecutor`).  Passing a LIST of taskpools
    runs them as wdrr tenants of one shared native graph
    (:class:`NativeServeExecutor`) and returns per-pool task counts."""
    if isinstance(tp, (list, tuple)):
        sx = NativeServeExecutor(list(tp), device=device)
        try:
            return sx.run()
        finally:
            sx.close()
    ex = NativeExecutor(tp, native_device=native_device, device=device)
    try:
        return ex.run(nthreads=nthreads)
    finally:
        ex.close()
