"""DTD — Dynamic Task Discovery front-end.

Reference: ``/root/reference/parsec/interfaces/dtd/`` — sequential-looking
task insertion (``parsec_dtd_insert_task``, ``insert_function.h:281``) with
per-argument access flags (``insert_function.h:53-72``); dependencies are
inferred at insert time from per-tile ``last_writer`` / reader tracking under
a tile lock (``insert_function.c:2812-2860``, tile struct
``insert_function_internal.h:199-209``); insertion is throttled by a window
so the DAG in flight stays bounded (window/threshold MCA knobs); task
classes are found-or-created from the body+signature
(``insert_function.c:193,942,2387``).

Multi-rank: every rank runs the same insert sequence (SPMD, reference
semantics); a task whose affinity tile is remote becomes a *shadow task*
that only advances the per-tile version (epoch) tracking. Producer ranks
insert send tasks, consumer ranks insert recv tasks — matched pairs keyed
by (tile, epoch), carried over the comm engine's TAG_DTD channel.

Differences from the reference, by design:
* WAR hazards are serialized as dependencies instead of broken by data
  renaming (``overlap_strategies.c``) in multi-rank runs; single-rank
  runs rename (fresh writer buffer) like the reference.
* Bodies may mutate numpy payloads in place (reference semantics) **or**
  return replacement arrays (functional style, required for JAX device
  execution): a non-None return rebinds the writable flows in order.

A tile goes home when it is flushed, and not before: a task's output
stays where the task ran (``Task._tpu_home = ()`` tells the device
module that no version of it is the committer's), a host reader pulls
what it needs (:func:`stage_to_cpu`), and ``data_flush`` / ``flush_all``
start the copies home of the newest versions together
(``Device.flush_home``).  A tile the user never flushes is handed to its
device's committer when the closed pool terminates, and a tile the
residency evicts is written home first, so nothing is lost at
``detach``.

What a trace shows of it (``docs/TRACING.md`` "DTD"): ``core:dtd_insert``
a task, ``wait:dtd_window`` while the inserter is held at the full
window, ``core:dtd_wait``, ``core:dtd_flush``; the counters are
:meth:`DTDTaskpool.counters`.

Usage::

    dtd = DTDTaskpool(ctx)
    dtd.insert_task(gemm_body,
                    (A.data_of(i, k), IN),
                    (B.data_of(k, j), IN),
                    (C.data_of(i, j), INOUT | AFFINITY),
                    alpha)                     # bare value => VALUE
    dtd.flush_all()
    dtd.wait()
"""

from __future__ import annotations

import threading
import time
import types
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from ..core.lifecycle import AccessMode, HookReturn, DEV_CPU, DEV_TPU
from ..core.task import Chore, Flow, Task, TaskClass
from ..core.taskpool import Taskpool
from ..data.data import Coherency, Data
from ..profiling import pins
from ..utils import debug, mca_param

IN = AccessMode.IN
OUT = AccessMode.OUT
INOUT = AccessMode.INOUT
CTL = AccessMode.CTL
VALUE = AccessMode.VALUE
SCRATCH = AccessMode.SCRATCH
ATOMIC_WRITE = AccessMode.ATOMIC_WRITE
AFFINITY = AccessMode.AFFINITY
DONT_TRACK = AccessMode.DONT_TRACK

# the same flags as plain ints, for insert_task: an ``IntFlag``'s ``&`` is
# a Python-level call (half of an insertion's time before these)
_IN, _OUT, _CTL, _SCRATCH, _VALUE = (
    int(IN), int(OUT), int(CTL), int(SCRATCH), int(VALUE))
_ATOMIC, _AFFINITY, _DONT_TRACK = (
    int(ATOMIC_WRITE), int(AFFINITY), int(DONT_TRACK))
_WRITES = _OUT | _ATOMIC


class _TileState:
    """Per-Data dependency tracking (reference dtd tile,
    ``insert_function_internal.h:199-209``).

    ``current`` is the buffer holding the tile's latest logical version —
    it diverges from the home ``data`` when a WAR hazard is broken by
    renaming (reference ``overlap_strategies.c``): pending readers keep the
    old buffer while the writer proceeds on a fresh one."""

    __slots__ = ("lock", "last_writer", "readers", "atomic", "data", "current",
                 "renames", "epoch", "writer_rank", "have_local", "sent")

    def __init__(self, data: Optional[Data] = None) -> None:
        self.lock = threading.Lock()
        self.last_writer: Optional[Task] = None
        self.readers: List[Task] = []
        #: pending commutative writers (ATOMIC_WRITE): unordered among
        #: themselves, ordered against readers and exclusive writers
        self.atomic: List[Task] = []
        self.data = data
        self.current: Optional[Data] = data
        self.renames = 0
        # -- multi-rank (shadow-task protocol) fields --------------------
        #: logical version counter, advanced by every exclusive write; all
        #: ranks compute the same sequence from the SPMD insert stream
        self.epoch = 0
        #: rank that produced (owns) the current epoch's content
        self.writer_rank = 0
        #: True when the current epoch's content is materialized locally
        #: (we produced it, we hold the home tile, or a recv deposited it)
        self.have_local = True
        #: (epoch, dst_rank) versions already shipped from this rank
        self.sent: set = set()


class _DTDTaskState:
    """Successor bookkeeping attached to each inserted task."""

    __slots__ = ("lock", "pending", "successors", "completed", "gen", "args")

    def __init__(self) -> None:
        self.lock = threading.Lock()
        # starts at 1: the "insertion in progress" dependency released at
        # the end of insert_task (avoids racing preds completing mid-insert)
        self.pending = 1
        self.successors: List[Task] = []
        self.completed = False
        #: untied-task support: a body returning a generator runs in slices,
        #: the worker is released between them (reference dtd_test_untie.c)
        self.gen = None
        self.args: Optional[List[Any]] = None


def copy_home(src: Data, dst: Data) -> None:
    """Copy ``src``'s newest version into ``dst``'s CPU copy and bump its
    version (shared by WAR-rename copies and flush-home)."""
    arr = stage_to_cpu(src)
    c = dst.get_copy(0)
    if c is None:
        dst.attach_copy(0, np.array(arr))
    else:
        c.payload = np.array(arr)
    dst.version_bump(0)


def stage_to_cpu(data: Data) -> np.ndarray:
    """Materialize the newest version of ``data`` as the CPU copy."""
    newest = data.newest_copy()
    if data.scratch is not None and (newest is None
                                     or newest.payload is None):
        # a scratch tile nobody has written: it is born here, zeroed
        from ..device import scratch

        return scratch.host_zeros(data)
    if newest is None:
        raise RuntimeError(f"{data!r} has no valid copy")
    if newest.device_index != 0:
        # a flush landed this very version at home: no second copy
        hc = data.get_copy(0)
        if hc is not None and hc.payload is not None \
                and hc.coherency is not Coherency.INVALID \
                and hc.version >= newest.version:
            newest = hc
    if newest.device_index == 0:
        if isinstance(newest.payload, np.ndarray):
            return newest.payload
        # a device-capable fabric can deposit a jax.Array at the host
        # slot (remote_dep flow payload, ptg._deposit_payload): CPU
        # bodies mutate in place, so normalize to a writable ndarray
        host = np.asarray(newest.payload)
        if not host.flags.writeable:
            host = host.copy()
        newest.payload = host
        return host
    host = np.asarray(newest.payload)
    if not host.flags.writeable:
        host = host.copy()  # D2H of a jax.Array is a read-only view
    c = data.attach_copy(0, host)
    c.version = newest.version
    return host


def window_params() -> Tuple[int, int]:
    """``(dtd_window_size, dtd_threshold_size)``: the insertion throttle
    of every inserter (this pool and ``dtd_native``'s streaming one)."""
    return (
        mca_param.register(
            "dtd", "window_size", 2048,
            help="max in-flight inserted tasks before the inserter helps "
                 "execute"),
        mca_param.register(
            "dtd", "threshold_size", 1024,
            help="in-flight level the inserter drains down to when the "
                 "window fills"))


class DTDTaskpool(Taskpool):
    """Reference ``parsec_dtd_taskpool_new`` (insert_function.h:332)."""

    def __init__(self, context=None, name: str = "dtd", *, auto_add: bool = True):
        super().__init__(name=name)
        self.taskpool_type = Taskpool.TYPE_DTD
        self._classes: Dict[Any, TaskClass] = {}
        self._tiles: Dict[int, _TileState] = {}
        self._tiles_lock = threading.Lock()
        self._inserted = 0
        self._retired = 0
        self._quiesce = threading.Condition()
        self._open = True
        self.window, self.threshold = window_params()
        self._war_rename = mca_param.register(
            "dtd", "war_rename", True,
            help="break WAR hazards by renaming (fresh writer buffer) instead of serializing")
        self._rename_tc: Optional[TaskClass] = None
        #: what :meth:`counters` reports (``dtd_*``): dependency edges
        #: found at insertion, WAR renames, fills of the window with the
        #: seconds the inserter was held and the tasks it executed or
        #: handed over meanwhile, tiles flushed home
        self._edges = 0
        self._renames = 0
        self._stalls = 0
        self._stall_s = 0.0
        self._helped = 0
        self._flushed = 0
        #: the pool's birth and the return of its newest insertion
        self._t_born = self._t_inserted = time.perf_counter()
        # -- multi-rank state (shadow-task protocol) ---------------------
        #: (wire_key, epoch) -> {"payload": arr|None, "task": recv Task|None}
        self._recv: Dict[Tuple[Any, int], Dict[str, Any]] = {}
        self._recv_lock = threading.Lock()
        self._send_tc: Optional[TaskClass] = None
        self._recv_tc: Optional[TaskClass] = None
        self._comm_seq = 0
        if context is not None and auto_add:
            context.add_taskpool(self)

    def counters(self) -> Dict[str, float]:
        """The pool's own counts, cumulative since its birth:
        ``dtd_inserted`` tasks (the runtime's own copy and communication
        tasks among them), ``dtd_edges`` dependencies found at
        insertion, ``dtd_renames`` WAR hazards broken by a fresh buffer,
        ``dtd_window_stalls`` fills of the window and
        ``dtd_window_stall_s`` seconds the inserter was held there,
        ``dtd_helped`` tasks it executed or handed to a device
        meanwhile, ``dtd_flushed_tiles`` tiles brought home by
        ``data_flush`` / ``flush_all``, ``dtd_insert_done_s`` seconds
        from the pool's creation to the return of its newest insertion
        (discovery's end, once the user inserts no more)."""
        return {"dtd_inserted": self._inserted, "dtd_edges": self._edges,
                "dtd_renames": self._renames,
                "dtd_window_stalls": self._stalls,
                "dtd_window_stall_s": self._stall_s,
                "dtd_helped": self._helped,
                "dtd_flushed_tiles": self._flushed,
                "dtd_insert_done_s": self._t_inserted - self._t_born}

    def attached(self, context) -> None:
        super().attached(context)
        # hold the "insertion open" runtime action so local termdet cannot
        # fire while the user may still insert (released by close()).
        self.tdm.taskpool_addto_runtime_actions(self, 1)

    # -----------------------------------------------------------------
    # task classes
    # -----------------------------------------------------------------
    def _class_of(
        self,
        bodies: Dict[str, Callable],
        modes: Tuple[int, ...],
        name: Optional[str],
    ) -> TaskClass:
        """``modes``: the arguments' access modes, as ints."""
        key = (tuple((d, id(f)) for d, f in sorted(bodies.items())), modes, name)
        tc = self._classes.get(key)
        if tc is not None:
            return tc
        flows = [
            Flow(f"arg{i}", AccessMode(m & ~(_AFFINITY | _DONT_TRACK)), i)
            for i, m in enumerate(modes)
        ]
        cname = name or next(
            (getattr(b, "__name__", "dtd_task") for b in bodies.values()), "dtd_task")
        tc = TaskClass(cname, flows=flows)
        for dev_type, fn in bodies.items():
            chore = Chore(dev_type, self._make_hook(dev_type, fn))
            if dev_type != DEV_CPU:
                chore.body_fn = fn
            tc.add_chore(chore)
        tc.release_deps = self._release_deps
        self._classes[key] = tc
        self.add_task_class(tc)
        return tc

    def _make_hook(self, dev_type: str, fn: Callable):
        if dev_type == DEV_CPU:
            def cpu_hook(es, task, _fn=fn):
                state: _DTDTaskState = task.user
                if state.gen is not None:
                    # untied resume: run the next slice on whichever worker
                    # picked the task up (reference untied-task semantics)
                    try:
                        next(state.gen)
                        return HookReturn.AGAIN
                    except StopIteration as si:
                        state.gen = None
                        self._commit_outputs(task, state.args, si.value)
                        return HookReturn.DONE
                args = self._resolve_cpu_args(task)
                result = _fn(*args)
                if isinstance(result, types.GeneratorType):
                    state.gen, state.args = result, args
                    try:
                        next(state.gen)
                        return HookReturn.AGAIN
                    except StopIteration as si:
                        state.gen = None
                        self._commit_outputs(task, args, si.value)
                        return HookReturn.DONE
                self._commit_outputs(task, args, result)
                return HookReturn.DONE

            return cpu_hook

        def accel_hook(es, task, _fn=fn):
            # accelerator chores are driven by the device module's
            # kernel_scheduler; it stages data and invokes fn on-device
            return task.selected_device.kernel_scheduler(es, task)

        return accel_hook

    # -----------------------------------------------------------------
    # body argument plumbing (CPU path)
    # -----------------------------------------------------------------
    def _resolve_cpu_args(self, task: Task) -> List[Any]:
        args = []
        for spec in task.body_args:
            kind, payload, mode = spec
            if kind == "data":
                arr = stage_to_cpu(payload)
                eff = AccessMode.INOUT if (mode & AccessMode.ATOMIC_WRITE) else (mode & AccessMode.INOUT)
                payload.transfer_ownership(0, eff)
                args.append(arr)
            elif kind == "scratch":
                shape, dtype = payload
                args.append(np.empty(shape, dtype))
            elif kind == "value":
                args.append(payload)
            # kind "ctl": dependency only, no body argument
        return args

    def _commit_outputs(self, task: Task, args: List[Any], result: Any) -> None:
        """In-place mutation needs only version bumps; a returned tuple
        rebinds writable flows in order."""
        writable = [
            (i, spec) for i, spec in enumerate(task.body_args)
            if spec[0] == "data" and (spec[2] & (AccessMode.OUT | AccessMode.ATOMIC_WRITE))
        ]
        if result is not None:
            outs = result if isinstance(result, (tuple, list)) else (result,)
            if len(outs) != len(writable):
                raise ValueError(
                    f"{task!r}: body returned {len(outs)} outputs for "
                    f"{len(writable)} writable flows")
            for (i, spec), new in zip(writable, outs):
                if spec[2] & AccessMode.ATOMIC_WRITE:
                    # concurrent atomic writers each computed from their own
                    # snapshot; rebinding would lose peer updates — atomic
                    # bodies must mutate in place
                    raise ValueError(
                        f"{task!r}: ATOMIC_WRITE flows require in-place "
                        "mutation, not a returned replacement array")
                copy = spec[1].get_copy(0)
                copy.payload = np.asarray(new)
        for i, spec in writable:
            spec[1].version_bump(0)

    # -----------------------------------------------------------------
    # insertion & dependency inference
    # -----------------------------------------------------------------
    @staticmethod
    def _rank_of_data(data: Data) -> Optional[int]:
        dc = data.collection
        if dc is None or dc.nodes <= 1:
            return None
        key = data.key if isinstance(data.key, tuple) else (data.key,)
        return dc.rank_of(*key)

    @staticmethod
    def _wire_key(data: Data) -> Any:
        """Rank-stable tile identity: (collection name, canonical key)."""
        dc = data.collection
        return (dc.name, data.key) if dc is not None else None

    def _tile_state(self, data: Data) -> _TileState:
        with self._tiles_lock:
            st = self._tiles.get(data.data_id)
            if st is None:
                st = self._tiles[data.data_id] = _TileState(data)
                if self.context is not None and self.context.nranks > 1:
                    owner = self._rank_of_data(data)
                    owner = self.context.rank if owner is None else owner
                    st.writer_rank = owner
                    st.have_local = owner == self.context.rank
            return st

    def insert_task(
        self,
        body: Union[Callable, Dict[str, Callable]],
        *args: Any,
        priority: int = 0,
        name: Optional[str] = None,
    ) -> Optional[Task]:
        """Reference ``parsec_dtd_insert_task`` (insert_function.h:281).

        ``args`` entries:
          * ``(Data, AccessMode)``        — tracked dataflow argument
          * ``((shape, dtype), SCRATCH)`` — per-task scratch buffer
          * ``(value, VALUE)`` or bare value — captured by value

        Returns the inserted :class:`Task`, or ``None`` when the task's
        affinity places it on another rank (shadow insertion — the
        reference's remote tasks are likewise not handed back).

        One ``core:dtd_insert`` span a call (``cls``; ``deps``: the
        dependency edges found; ``ready``: inserted with none left).  A
        full window holds the caller AFTER it, in ``wait:dtd_window``.
        """
        with pins.span("core:dtd_insert") as sp:
            task = self._insert(body, args, priority, name, sp)
        self._throttle_window()
        self._t_inserted = time.perf_counter()
        return task

    def _insert(self, body, args, priority: int, name: Optional[str],
                sp) -> Optional[Task]:
        """:meth:`insert_task` inside its span."""
        if not self._open:
            raise RuntimeError("taskpool closed for insertion")
        if self.failed:
            raise RuntimeError(
                "taskpool was aborted; tasks inserted now would be "
                "silently discarded")
        if self.context is None:
            raise RuntimeError("DTD taskpool must be attached to a context before insertion")
        bodies = body if isinstance(body, dict) else {DEV_CPU: body}
        nranks = self.context.nranks
        myrank = self.context.rank

        specs: List[Tuple[str, Any, AccessMode]] = []
        modes: List[int] = []   # the arguments' modes as ints
        affinity_data: Optional[Data] = None
        for a in args:
            if isinstance(a, tuple) and len(a) == 2 and isinstance(a[1], AccessMode):
                val, mode = a
            else:
                val, mode = a, VALUE
            m = int(mode)
            if m & _SCRATCH:
                specs.append(("scratch", val, mode))
            elif m & _CTL and isinstance(val, Data):
                # control-only dependency on a tile: tracked like a reader,
                # but contributes no body argument
                specs.append(("ctl", val, mode))
            elif m & _VALUE or not isinstance(val, Data):
                specs.append(("value", val, VALUE))
                m = _VALUE
            else:
                specs.append(("data", val, mode))
                if m & _AFFINITY and affinity_data is None:
                    affinity_data = val
            modes.append(m)

        # rank placement (owner computes, reference PARSEC_AFFINITY flag):
        # the task executes on the rank owning the AFFINITY-tagged tile
        # (fallback: the first collection-backed tracked tile). Every rank
        # runs the same insert sequence; remote tasks are *shadow* tasks —
        # tracked for dependency/version inference, never executed locally.
        exec_rank = myrank
        if nranks > 1:
            pdata = affinity_data
            if pdata is None:
                pdata = next(
                    (d for (k, d, m) in specs
                     if k in ("data", "ctl") and not (m & DONT_TRACK)
                     and d.collection is not None and d.collection.nodes > 1),
                    None)
            if pdata is not None:
                r = self._rank_of_data(pdata)
                if r is not None:
                    exec_rank = r

        if nranks > 1 and exec_rank != myrank:
            self._track_shadow(specs, exec_rank)
            return None

        tc = self._class_of(bodies, tuple(modes), name)
        task = Task(self, tc, (self._inserted,), priority)
        task.body_args = specs
        #: no output of an inserted task is the device committer's: a
        #: tile goes home at its flush (module docstring)
        task._tpu_home = ()
        #: the read-write tiles whose version this task is the last to
        #: read (below): the device module may write the new one over it
        donate: List[int] = []
        state = _DTDTaskState()
        task.user = state
        task.on_complete = self._task_retired
        deps = 0

        # dependency inference per tracked data argument (CTL args track
        # like readers: they order after the last writer). Multi-rank runs
        # serialize WAR hazards (renaming is a single-rank optimization:
        # cross-rank consistency is keyed by tile epoch, which must map
        # 1:1 onto the home buffer).
        rename_on = bool(self._war_rename) and nranks == 1
        for i, (kind, data, mode) in enumerate(specs):
            m = modes[i]
            if kind not in ("data", "ctl") or (m & _DONT_TRACK):
                continue
            st = self._tile_state(data)
            copy_src = copy_dst = None
            copy_preds: List[Task] = []
            with st.lock:
                st.readers = [r for r in st.readers if not r.user.completed]
                st.atomic = [w for w in st.atomic if not w.user.completed]
                if nranks > 1:
                    # content of the current epoch must be materialized
                    # locally before any consuming local task can run
                    needs_in = bool(m & (_IN | _ATOMIC)) or not (m & _OUT)
                    if needs_in and not st.have_local:
                        self._ensure_recv_locked(st, st.epoch)
                buf = st.current if st.current is not None else data
                last = [st.last_writer] if st.last_writer is not None else []
                if (m & _ATOMIC) and nranks == 1:
                    # commutative writer: after readers + exclusive writer,
                    # unordered among atomic peers
                    for p in st.readers + last:
                        if p is not task:
                            deps += self._add_edge(p, task, state)
                    st.atomic.append(task)
                elif m & _WRITES:
                    # exclusive writer (OUT/INOUT; multi-rank also routes
                    # ATOMIC_WRITE here — commutativity is a local
                    # optimization, cross-rank epochs need a total order)
                    pending = [r for r in st.readers + st.atomic if r is not task]
                    if kind == "data" and m & _IN and nranks == 1:
                        # an exclusive writer is ordered behind every
                        # reader of the version it overwrites (or takes a
                        # renamed copy that is its alone), and whoever is
                        # inserted later reads ITS version: nobody else
                        # consumes the one it reads
                        donate.append(i)
                    if rename_on and kind == "data" and pending:
                        # WAR hazard: rename (overlap_strategies.c) — the
                        # writer proceeds on a fresh buffer while pending
                        # readers/atomics keep the old one
                        st.renames += 1
                        self._renames += 1
                        newd = Data((data.key, "war", st.renames),
                                    shape=buf.shape, dtype=buf.dtype)
                        if m & _IN:
                            # INOUT: the new buffer needs the old contents —
                            # a copy task ordered after the old buffer's
                            # producers (but NOT after its readers)
                            copy_src, copy_dst = buf, newd
                            copy_preds = [p for p in last + st.atomic if p is not task]
                        else:
                            self._attach_blank(newd, buf)
                        st.current = newd
                        st.last_writer = task
                        st.readers = []
                        st.atomic = []
                        buf = newd
                    else:
                        for p in pending + last:
                            if p is not task:
                                deps += self._add_edge(p, task, state)
                        st.last_writer = task
                        st.readers = []
                        st.atomic = []
                    if nranks > 1:
                        st.epoch += 1
                        st.writer_rank = myrank
                        st.have_local = True
                else:  # reader: after exclusive writer + atomic writers
                    for p in st.atomic + last:
                        if p is not task:
                            deps += self._add_edge(p, task, state)
                    st.readers.append(task)
            if kind == "data":
                specs[i] = (kind, buf, mode)  # bind the version's buffer
            if copy_src is not None:
                cpy = self._insert_rename_copy(copy_src, copy_dst, copy_preds)
                deps += self._add_edge(cpy, task, state)

        task._tpu_donate = tuple(donate)
        self._edges += deps
        with self._quiesce:
            self._inserted += 1
        # release the insertion-in-progress dependency
        ready = False
        with state.lock:
            state.pending -= 1
            ready = state.pending == 0
        if ready:
            es = self.context.current_es()
            self.context.schedule([task], es=es)
        sp.note(cls=tc.name, deps=deps, ready=int(ready))
        return task

    @staticmethod
    def _attach_blank(newd: Data, like: Data) -> None:
        """Allocate a pure-OUT rename target shaped like the old buffer."""
        c = like.newest_copy()
        p = c.payload if c is not None else None
        if p is not None:
            # (its shape and dtype, not its value: the tile may live on a
            # device, and this runs under the tile's lock)
            arr = np.zeros(p.shape, p.dtype)
        else:
            arr = np.zeros(like.shape or (1,), like.dtype or np.float64)
        newd.attach_copy(0, arr)

    def _rename_class(self) -> TaskClass:
        if self._rename_tc is None:
            def copy_hook(es, t):
                src, dst = t.body_args
                copy_home(src, dst)
                return HookReturn.DONE

            tc = TaskClass("war_rename_copy", chores=[Chore(DEV_CPU, copy_hook)])
            tc.release_deps = self._release_deps
            self._rename_tc = tc
            self.add_task_class(tc)
        return self._rename_tc

    def _insert_rename_copy(self, src: Data, dst: Data, preds: List[Task]) -> Task:
        """Internal insertion of the INOUT-rename copy task: reads the old
        buffer's final version into the writer's fresh buffer; ordered after
        the old buffer's producers only (readers run concurrently)."""
        t = Task(self, self._rename_class(), (self._inserted,), priority=0)
        t.body_args = (src, dst)
        st = _DTDTaskState()
        t.user = st
        t.on_complete = self._task_retired
        for p in preds:
            self._add_edge(p, t, st)
        with self._quiesce:
            self._inserted += 1
        ready = False
        with st.lock:
            st.pending -= 1
            ready = st.pending == 0
        if ready:
            self.context.schedule([t], es=self.context.current_es())
        return t

    # -----------------------------------------------------------------
    # multi-rank shadow-task protocol
    #
    # Reference: dtd remote tasks (insert_function.c — tasks whose
    # affinity rank is remote still walk the tile lists so every rank
    # infers matching communication from the same SPMD insert stream).
    # Cross-rank consistency is keyed by (tile, epoch): the producing
    # rank inserts a *send task* per consuming rank (ordered after the
    # local producer like a reader), the consuming rank inserts a *recv
    # task* (ordered after local buffer users like a writer — the
    # deposit overwrites the local buffer). Local tile lists only ever
    # hold local tasks; no cross-rank WAR edges are needed because each
    # rank mutates its own copy of the tile.
    # -----------------------------------------------------------------
    def _track_shadow(self, specs, exec_rank: int) -> None:
        """Bookkeeping for a task that executes on another rank."""
        myrank = self.context.rank
        for kind, data, mode in specs:
            if kind not in ("data", "ctl") or (mode & DONT_TRACK):
                continue
            st = self._tile_state(data)
            is_excl = bool(mode & (AccessMode.OUT | AccessMode.ATOMIC_WRITE))
            needs_in = bool(mode & (AccessMode.IN | AccessMode.ATOMIC_WRITE)) or not is_excl
            with st.lock:
                if needs_in and st.writer_rank == myrank:
                    self._insert_send_locked(st, st.epoch, exec_rank)
                if is_excl:
                    st.epoch += 1
                    st.writer_rank = exec_rank
                    st.have_local = False
                    # local reader/writer lists are kept: they encode WAR
                    # on the *local* buffer, consumed by the next local
                    # producer (_ensure_recv_locked or a local writer)

    def _comm_task(self, tc: TaskClass, body_args, preds: List[Task],
                   extra_pending: int = 0) -> Task:
        """Insert an internal communication task (send/recv); counted and
        retired like any inserted task so wait()/termdet see it."""
        self._comm_seq += 1
        t = Task(self, tc, (tc.name, self._comm_seq), priority=1 << 20)
        t.body_args = body_args
        state = _DTDTaskState()
        state.pending += extra_pending
        t.user = state
        t.on_complete = self._task_retired
        for p in preds:
            self._add_edge(p, t, state)
        with self._quiesce:
            self._inserted += 1
        ready = False
        with state.lock:
            state.pending -= 1  # release the insertion-in-progress dep
            ready = state.pending == 0
        if ready:
            self.context.schedule([t], es=self.context.current_es())
        return t

    def _send_class(self) -> TaskClass:
        if self._send_tc is None:
            def send_hook(es, t):
                data, wkey, epoch, dst = t.body_args
                # snapshot: the send retires (releasing its WAR edge) before
                # the wire serializes / the remote GET arrives — the next
                # local writer must not be able to mutate the shipped bytes
                arr = np.array(stage_to_cpu(data))
                self.context.comm.remote_dep.send_dtd(self, wkey, epoch, arr, dst)
                return HookReturn.DONE

            tc = TaskClass("dtd_send", chores=[Chore(DEV_CPU, send_hook)])
            tc.release_deps = self._release_deps
            self._send_tc = tc
            self.add_task_class(tc)
        return self._send_tc

    def _recv_class(self) -> TaskClass:
        if self._recv_tc is None:
            def recv_hook(es, t):
                data, wkey, epoch = t.body_args
                with self._recv_lock:
                    entry = self._recv.pop((wkey, epoch))
                buf = entry["payload"]
                c = data.get_copy(0)
                if c is None:
                    data.attach_copy(0, np.array(buf))
                else:
                    c.payload = np.array(buf)
                data.version_bump(0)
                return HookReturn.DONE

            tc = TaskClass("dtd_recv", chores=[Chore(DEV_CPU, recv_hook)])
            tc.release_deps = self._release_deps
            self._recv_tc = tc
            self.add_task_class(tc)
        return self._recv_tc

    def _insert_send_locked(self, st: _TileState, epoch: int, dst: int) -> None:
        """Ship (tile, epoch) to rank dst once; ordered after the local
        producer like a reader (tile lock held)."""
        if (epoch, dst) in st.sent:
            return
        st.sent.add((epoch, dst))
        wkey = self._wire_key(st.data)
        if wkey is None:
            raise RuntimeError(
                f"{st.data!r}: cross-rank DTD flow needs a collection-backed tile")
        preds = list(st.atomic)
        if st.last_writer is not None:
            preds.append(st.last_writer)
        t = self._comm_task(self._send_class(), (st.data, wkey, epoch, dst), preds)
        st.readers.append(t)

    def _ensure_recv_locked(self, st: _TileState, epoch: int) -> Task:
        """Create the recv task that deposits (tile, epoch) into the local
        buffer; it becomes the tile's local producer (tile lock held)."""
        wkey = self._wire_key(st.data)
        if wkey is None:
            raise RuntimeError(
                f"{st.data!r}: cross-rank DTD flow needs a collection-backed tile")
        with self._recv_lock:
            entry = self._recv.get((wkey, epoch))
            if entry is None:
                entry = self._recv[(wkey, epoch)] = {"payload": None, "task": None}
            arrived = entry["payload"] is not None
            # WAR: the deposit overwrites the local buffer — order after
            # every local task still using it
            preds = st.readers + st.atomic
            if st.last_writer is not None:
                preds.append(st.last_writer)
            t = self._comm_task(self._recv_class(), (st.data, wkey, epoch),
                                preds, extra_pending=0 if arrived else 1)
            entry["task"] = t
        st.last_writer = t
        st.readers = []
        st.atomic = []
        st.have_local = True
        return t

    def dtd_incoming(self, wkey, epoch: int, payload) -> None:
        """AM deliver (runs on the comm/progress thread): park or release."""
        task = None
        with self._recv_lock:
            entry = self._recv.get((wkey, epoch))
            if entry is None:
                self._recv[(wkey, epoch)] = {"payload": payload, "task": None}
            else:
                entry["payload"] = payload
                task = entry["task"]
        if task is not None:
            state: _DTDTaskState = task.user
            with state.lock:
                state.pending -= 1
                ready = state.pending == 0
            if ready:
                self.context.schedule([task])
        with self._quiesce:
            self._quiesce.notify_all()

    @staticmethod
    def _add_edge(pred: Task, succ: Task, succ_state: "_DTDTaskState") -> bool:
        """``succ`` runs after ``pred``; False where there is nothing to
        wait for (``pred`` has completed, or the edge is there)."""
        # bump pending BEFORE publishing the edge: a predecessor completing
        # between publish and bump would double-schedule the successor. The
        # insertion-in-progress dependency keeps pending >= 1 throughout, so
        # the rollback below can never release the task early.
        with succ_state.lock:
            succ_state.pending += 1
        pstate: _DTDTaskState = pred.user
        added = False
        with pstate.lock:
            if not pstate.completed and succ not in pstate.successors:
                pstate.successors.append(succ)
                added = True
        if not added:  # pred already done, or duplicate edge
            with succ_state.lock:
                succ_state.pending -= 1
        return added

    def _release_deps(self, es, task: Task) -> List[Task]:
        state: _DTDTaskState = task.user
        with state.lock:
            state.completed = True
            succs = list(state.successors)
            state.successors = []
        ready = []
        for s in succs:
            sstate: _DTDTaskState = s.user
            with sstate.lock:
                sstate.pending -= 1
                if sstate.pending == 0:
                    ready.append(s)
        return ready

    def _task_retired(self, task: Task) -> None:
        with self._quiesce:
            self._retired += 1
            self._quiesce.notify_all()

    def _throttle_window(self) -> None:
        """Bound in-flight tasks (reference window throttling): the inserter
        thread helps execute until the backlog drains to the threshold.
        One ``wait:dtd_window`` event a fill of the window
        (``in_flight`` when it began; ``helped``: the tasks the inserter
        executed meanwhile — an accelerator task it takes is handed to
        its device's queue, or makes the inserter that device's manager
        until the queue is empty)."""
        if self.context is None:
            return
        in_flight = self._inserted - self._retired
        if in_flight < self.window:
            return
        self.context.start()
        helped = 0
        t0 = time.perf_counter()
        with pins.wait("dtd_window", in_flight=in_flight) as w:
            while not self.failed:  # (aborted: the backlog never drains)
                with self._quiesce:
                    if self._inserted - self._retired <= self.threshold:
                        break
                if self.context.help_execute_one():
                    helped += 1
                    continue
                # the backlog may be recv tasks blocked on remote arrivals:
                # drain the comm engine or a full window deadlocks the rank
                self.context._progress_comm()
                with self._quiesce:
                    if self._inserted - self._retired > self.threshold:
                        self._quiesce.wait(0.001)
            w.note(helped=helped)
        self._stalls += 1
        self._stall_s += time.perf_counter() - t0
        self._helped += helped

    # -----------------------------------------------------------------
    # quiescence / flush
    # -----------------------------------------------------------------
    def wait(self, timeout: Optional[float] = None) -> bool:
        """Wait until every task inserted so far retired; the pool remains
        open for more insertion (reference ``parsec_taskpool_wait``)."""
        if self.context is not None:
            self.context.start()
        with pins.span("core:dtd_wait") as sp:
            done, helped = self._wait(timeout)
            sp.note(done=int(done), helped=helped)
        return done

    def _wait(self, timeout: Optional[float]) -> Tuple[bool, int]:
        """:meth:`wait` inside its ``core:dtd_wait`` span: whether the
        pool quiesced, and the tasks the caller executed meanwhile.  The
        stretches in which it finds nothing to execute are the span's
        ``dtd:parked`` children (a sleep of up to a millisecond each,
        ended by the next retirement): nobody schedules there, so they
        are no ``core:*`` span's self time."""
        deadline = (time.monotonic() + timeout) if timeout is not None else None
        helped = 0
        while True:
            if self.failed:
                return False, helped  # abort(): discarded tasks never retire
            with self._quiesce:
                if self._retired >= self._inserted:
                    return True, helped
                if deadline is not None and time.monotonic() > deadline:
                    return False, helped
            if self.context is not None and self.context.help_execute_one():
                helped += 1
                continue
            with pins.span("dtd:parked"):
                if self.context is not None:
                    # drive the comm engine: pending recv tasks need
                    # arrivals
                    self.context._progress_comm()
                with self._quiesce:
                    if self._retired >= self._inserted:
                        return True, helped
                    self._quiesce.wait(0.001)

    def data_flush(self, data: Data) -> None:
        """Push the final version of ``data`` home to its owner rank
        (reference ``parsec_dtd_data_flush``, insert_function.h:351-360).

        Single-rank: bring the newest version home — through the device
        that holds it (``Device.flush_home``), or copied back from a
        rename buffer if WAR renaming redirected the tile — and drop
        tracking state; the caller has waited for the tile's tasks
        (``flush_all`` does). Multi-rank: asynchronous like the
        reference — inserts the home-bound send on the producing rank and
        the matching recv on the owner; completed by ``wait()``. All ranks
        must flush the same tiles (SPMD, as they inserted)."""
        if self.context is not None and self.context.nranks > 1:
            with self._tiles_lock:
                st = self._tiles.get(data.data_id)
            if st is None:
                return
            myrank = self.context.rank
            owner = self._rank_of_data(data)
            owner = myrank if owner is None else owner
            with st.lock:
                if st.writer_rank == myrank and owner != myrank:
                    self._insert_send_locked(st, st.epoch, owner)
                elif owner == myrank and not st.have_local:
                    self._ensure_recv_locked(st, st.epoch)
            return
        self._flush_home([data])

    def _send_home(self, datas: List[Data], wait: bool) -> int:
        """The tiles of ``datas`` whose newest version lives on a device
        go home TOGETHER, a device at a time (``Device.flush_home``: every
        copy started before one is waited for).  Returns their bytes."""
        by_dev: Dict[int, List[Data]] = {}
        nbytes = 0
        for d in datas:
            c = d.newest_copy()
            # (a scratch tile has no home: whoever wants it pulls it)
            if c is not None and c.device_index != 0 \
                    and c.payload is not None and d.scratch is None:
                by_dev.setdefault(c.device_index, []).append(d)
                nbytes += c.nbytes
        for idx, group in by_dev.items():
            self.context.devices[idx].flush_home(group, wait=wait)
        return nbytes

    def _flush_home(self, datas: List[Data]) -> None:
        """The single-rank flush of ``datas``, under one ``core:dtd_flush``
        span (``n`` tiles; ``bytes`` that crossed from a device)."""
        with pins.span("core:dtd_flush", n=len(datas)) as sp:
            with self._tiles_lock:
                states = [self._tiles.get(d.data_id) for d in datas]
            plain: List[Data] = []
            for d, st in zip(datas, states):
                cur = st.current if st is not None else None
                if cur is not None and cur is not d:
                    copy_home(cur, d)  # the tile's value is a rename buffer's
                else:
                    plain.append(d)
            nbytes = self._send_home(plain, wait=True)
            for d in plain:
                # what no device brought home (a module without a flush
                # of its own, a scratch tile, a payload a fabric left at
                # the host slot)
                stage_to_cpu(d)
            with self._tiles_lock:
                for d in datas:
                    self._tiles.pop(d.data_id, None)
            self._flushed += len(datas)
            sp.note(bytes=nbytes)

    def flush_all(self, collection=None) -> None:
        """Reference ``parsec_dtd_data_flush_all``: flush every tracked tile
        home (of one collection, or all).  Single-rank: waits for the
        pool, then ONE flush of them all."""
        multirank = self.context is not None and self.context.nranks > 1
        if not multirank:
            self.wait()
        with self._tiles_lock:
            states = [st for st in self._tiles.values()
                      if st.data is not None and (
                          collection is None
                          or st.data.collection is collection)]
        if not multirank:
            self._flush_home([st.data for st in states])
            return
        for st in states:
            self.data_flush(st.data)
        self.wait()
        myrank = self.context.rank
        for st in states:
            owner = self._rank_of_data(st.data)
            if owner is None or owner == myrank:
                stage_to_cpu(st.data)  # materialize home tiles on CPU
            with self._tiles_lock:
                self._tiles.pop(st.data.data_id, None)

    def close(self) -> None:
        """End insertion; after this, ``context.wait()`` can terminate the
        pool.  A tile that was never flushed goes to its device's
        committer when the pool terminates (:meth:`_termination_detected`)."""
        if self._open:
            self._open = False
            self.tdm.taskpool_addto_runtime_actions(self, -1)

    def _termination_detected(self, tp) -> None:
        """The closed pool's last task has retired: the tiles the user
        never flushed are handed to their devices' committers, without a
        wait — they land at the device's ``flush()`` or ``detach``, as a
        version did when every one of them was the committer's."""
        if not self.failed and self.context is not None \
                and self.context.nranks == 1:
            with self._tiles_lock:
                left = [st.data for st in self._tiles.values()
                        if st.data is not None and (
                            st.current is None or st.current is st.data)]
            try:
                self._send_home(left, wait=False)
            except Exception as e:  # the committer died: detach says so
                debug.warning("dtd: unflushed tiles not handed home: %s", e)
        super()._termination_detected(tp)
