"""Context: worker threads, scheduler installation, taskpool lifecycle.

Mirrors ``/root/reference/parsec/parsec.c`` (``parsec_init``,
``parsec_fini``) and the context half of ``scheduling.c``
(``parsec_context_add_taskpool`` :832, ``parsec_context_start`` :935,
``parsec_context_wait`` :961, worker loop ``__parsec_context_wait`` :694).

Threading model: ``nb_cores`` execution streams; stream 0 belongs to the
thread calling :meth:`Context.wait` (the reference's master), streams 1..n-1
get dedicated worker threads created at init.  Workers park on a condition
variable with exponential-backoff timed waits when idle (the reference uses
exponential nanosleep, ``scheduling.c:768-771``).
"""

from __future__ import annotations

import os
import threading
from typing import Callable, Dict, List, Optional

from ..profiling import jobtrace
from ..utils import debug, mca_param, open_component
from . import scheduling
from .lifecycle import HookReturn
from .task import Task
from .taskpool import Taskpool

#: max seconds an idle worker sleeps between scheduler polls (reference
#: exponential nanosleep cap, scheduling.c:768-771).  Every work source
#: notifies the cv (schedule_ready, taskpool termination, comm arrivals),
#: so the cap only bounds staleness of the POLLED fallbacks
#: (progress_comm).  It must be generous: each idle wake runs a scheduler
#: select under the GIL, and at a 1 ms cap a handful of idle threads
#: measurably slows an active worker's async device dispatch (5x on
#: jit-call enqueue) — the exact hot path the device manager lives on.
IDLE_BACKOFF_MAX = 0.02


def configured_vpmap(nb_workers: int):
    """The ``runtime_vpmap`` parameter's map of ``nb_workers`` workers
    into virtual processes (reference vpmap.c + bindthread.c), ``None``
    for ``flat``; a spec that cannot be read raises ``ValueError``."""
    from ..utils.binding import VPMap

    spec = str(mca_param.register(
        "runtime", "vpmap", "flat",
        help="vp map: flat | nb:<k> | explicit '0,1;2,3' worker lists"))
    try:
        if spec.startswith("nb:"):
            k = int(spec[3:])
            if k < 1:
                raise ValueError("vp count must be >= 1")
            return VPMap.from_nb_vps(nb_workers, k)
        if ";" in spec or "," in spec:
            return VPMap.from_spec(spec)
    except Exception as e:
        raise ValueError(f"invalid runtime_vpmap {spec!r}: {e}") from e
    return None


class ExecutionStream:
    """Per-worker state (reference ``parsec_execution_stream_t``)."""

    __slots__ = ("worker_id", "vp_id", "context", "next_task", "stats", "sched_obj", "profile",
                 "managing")

    def __init__(self, worker_id: int, context: "Context", vp_id: int = 0):
        self.worker_id = worker_id
        self.vp_id = vp_id
        self.context = context
        self.next_task: Optional[Task] = None
        self.stats: Dict[str, int] = {"executed": 0, "selected": 0, "steals": 0}
        self.sched_obj = None  # scheduler-private
        self.profile = None    # profiling stream
        #: the accelerator device whose manager this stream's thread is,
        #: while it is (``kernel_scheduler`` sets and clears it): of what
        #: the thread releases then, the device is asked first
        #: (``keep_released``; ``scheduling.schedule_ready``)
        self.managing = None


class Context:
    """The runtime instance (reference ``parsec_context_t``)."""

    def __init__(
        self,
        nb_cores: Optional[int] = None,
        *,
        scheduler: Optional[str] = None,
        devices: Optional[List[str]] = None,
        rank: int = 0,
        nranks: int = 1,
        comm=None,
        accelerators: int = 1,
    ):
        """``accelerators``: how many chips this context drives (DPLASMA's
        ``-g``): as many instances of the accelerator module, ``devices ==
        [cpu, acc_1 .. acc_g]``, instance ``i`` on
        ``jax.local_devices()[i - 1]`` (with several ranks, on the rank's
        slice of them), under this one scheduler; more than there are
        chips raises here.  1 is one module, bound by the rank."""
        # opt-in runtime checkers, installed BEFORE any runtime lock or
        # thread exists so they observe the whole context lifetime:
        # PARSEC_TPU_HBCHECK=1|strict — happens-before race recorder
        # (reported at fini); PARSEC_TPU_LOCKDEP=1 — lock-order checker
        # (locks created from here on are tracked)
        if os.environ.get("PARSEC_TPU_HBCHECK", "0") not in ("", "0"):
            from ..analysis import hb as _hb

            _hb.ensure_live()
        if os.environ.get("PARSEC_TPU_LOCKDEP", "0") not in ("", "0"):
            from ..analysis import lockdep as _lockdep

            _lockdep.install()
        # PARSEC_TPU_ABI_CHECK=1|strict — lint the native library's ABI
        # against the declarative spec (native.abi) before any ctypes
        # call crosses it: a stale or drifted libparsec_core.so corrupts
        # silently at the boundary, so catch it at startup (strict
        # raises; 1 prints the ENG findings and continues)
        abi_mode = os.environ.get("PARSEC_TPU_ABI_CHECK", "0").strip().lower()
        if abi_mode not in ("", "0"):
            self._abi_check(strict=abi_mode == "strict")
        if nb_cores is None:
            nb_cores = mca_param.register(
                "runtime", "num_cores", min(os.cpu_count() or 1, 8),
                help="number of worker execution streams",
            )
        self.nb_workers = max(1, int(nb_cores))
        if int(accelerators) < 1:
            raise ValueError(f"accelerators={accelerators}: at least one")
        self.accelerators = int(accelerators)
        #: tasks by the criterion that placed them among several eligible
        #: accelerators (``device.select_best_device``); all 0 with one
        self.stats: Dict[str, int] = {
            "selected_by_owner": 0, "selected_by_advice": 0,
            "selected_by_bytes": 0, "selected_by_load": 0}
        self.rank = rank
        self.nranks = nranks
        self.comm = comm  # comm engine (None = single process)

        # executable cache: persistent AOT compile cache + the cross-rank
        # compile-once-ship-serialized channel (a TAG_CTL "compile" op on
        # multi-rank meshes).  Created BEFORE devices attach — the device
        # layer reads cache warmth to decide whether the multi-rank
        # wave-batching auto-disable can be lifted.
        from .. import compile_cache as _cc

        self.compile_cache = _cc.for_context(self)

        sched_name = scheduler or str(mca_param.register(
            "mca", "sched", "", help="scheduler component selection")) or None
        self.scheduler = open_component("sched", sched_name)
        self.scheduler.install(self)

        from ..utils.binding import VPMap, available_cores

        try:
            self.vpmap = configured_vpmap(self.nb_workers) \
                or VPMap.flat(self.nb_workers)
        except ValueError as e:
            debug.fatal("%s", e)
        self._bind_threads = mca_param.register(
            "runtime", "bind_threads", False,
            help="pin worker threads to cores round-robin")
        self._cores = available_cores()

        self.streams: List[ExecutionStream] = [
            ExecutionStream(i, self, vp_id=self.vpmap.vp_of(i)) for i in range(self.nb_workers)
        ]
        for es in self.streams:
            self.scheduler.flow_init(es)

        # (before the devices: a module with a manager thread of its own
        # names that thread's stream here)
        self._tls = threading.local()

        # devices (device 0 = CPU; accelerators attach next)
        from ..device import device as devmod

        self.devices = devmod.attach_devices(self, devices,
                                             self.accelerators)

        self._cv = threading.Condition()
        #: exclusive ownership of execution stream 0 (the "master" stream):
        #: contended between a wait()-ing thread and non-worker helpers
        self._es0_lock = threading.Lock()
        self._taskpools: Dict[int, Taskpool] = {}
        self._active_taskpools = 0
        self._started = False
        self._shutdown = False
        self._fini_cbs = []
        self._abort_reason = None

        self._threads: List[threading.Thread] = []
        for es in self.streams[1:]:
            t = threading.Thread(target=self._worker_main, args=(es,), name=f"parsec-worker-{es.worker_id}", daemon=True)
            t.start()
            self._threads.append(t)
        debug.verbose(3, "core", "context up: %d workers, sched=%s, devices=%s",
                      self.nb_workers, self.scheduler.mca_name,
                      [d.name for d in self.devices])
        if self.comm is not None:
            self.comm.attach_context(self)
        # opt-in health plane (installed LAST: the watchdog's heartbeat
        # channel and the exporter's comm gauges need the attached comm
        # engine).  PARSEC_TPU_FLIGHT=1 — always-on bounded flight
        # recorder (rank-routed ring of trace events, dumped on body
        # failure / watchdog firing / "tools flightdump");
        # PARSEC_TPU_HEALTH=1|<port> — HTTP exporter serving /metrics,
        # /status, /healthz, /flightdump (a numeric port is offset by
        # rank so in-process meshes don't collide);
        # PARSEC_TPU_WATCHDOG=1|strict — stall watchdog (strict fails
        # stalled pools with the OBS diagnosis instead of hanging).
        self.flight = None
        self.health = None
        self.watchdog = None
        fl = os.environ.get("PARSEC_TPU_FLIGHT", "0")
        if fl not in ("", "0"):
            from ..profiling.flight import FlightRecorder

            self.flight = FlightRecorder(
                nranks=1, base_rank=self.rank, context=self).install()
        hp = os.environ.get("PARSEC_TPU_HEALTH", "")
        if hp not in ("", "0"):
            from ..profiling.health import HealthServer

            port = int(hp) + self.rank if hp.isdigit() and hp != "1" else 0
            self.health = HealthServer(self, port=port).start()
        wd = os.environ.get("PARSEC_TPU_WATCHDOG", "0")
        if wd not in ("", "0"):
            from ..profiling.health import Watchdog

            self.watchdog = Watchdog(
                self, strict=(wd.strip().lower() == "strict")).start()
        # PARSEC_TPU_SLO=1 — SLO plane (profiling.slo): mergeable
        # latency histograms (per-class exec, coll segments, comm RTT,
        # job latency/queue delay when a serving plane attaches) +
        # straggler digests.  A RuntimeService installs one on its
        # context by default; standalone contexts opt in here.
        self.slo = None
        if os.environ.get("PARSEC_TPU_SLO", "0") not in ("", "0"):
            from ..profiling.slo import SloPlane

            self.slo = SloPlane(self)

    # ------------------------------------------------------------------
    # taskpool lifecycle
    # ------------------------------------------------------------------
    def _abi_check(self, strict: bool) -> None:
        """PARSEC_TPU_ABI_CHECK startup lint: certify the built native
        library against the declarative ABI spec (ENG001-ENG006) before
        the engine is used.  A missing library is not a finding — the
        pure-Python fallback never crosses the boundary."""
        from ..analysis.findings import LintError, errors_of
        from ..native import _SRC_DIR, lib_path
        from ..native import abi as _abi

        lib = lib_path()
        if not os.path.exists(lib):
            return
        findings = _abi.abi_findings(lib, _SRC_DIR)
        for f in findings:
            debug.warning("abi-check: %s", f)
        if strict and errors_of(findings):
            raise LintError(
                f"PARSEC_TPU_ABI_CHECK=strict: {lib} drifted from "
                f"the ABI spec ({len(findings)} finding(s))", findings)

    def add_taskpool(self, tp: Taskpool) -> None:
        """Reference ``parsec_context_add_taskpool`` (scheduling.c:832):
        register, notify comm layer, run the startup hook, enqueue the
        initially-ready tasks."""
        from ..profiling import pins

        with pins.span("attach:build", pool=tp.taskpool_id, rank=self.rank):
            self._add_taskpool(tp)

    def _add_taskpool(self, tp: Taskpool) -> None:
        # Distributed termdet monitors (fourcounter) bind to the comm
        # engine and are driven from the idle loop (_progress_comm); one
        # distributed monitor per CE at a time — the TERMDET tag and
        # piggyback channel are single-slot.  The slot decision happens
        # FIRST, before the pool is registered anywhere: a refusal must
        # not leave a zombie half-registration, and a tdm swap must
        # happen before attached() counts into it or the comm layer can
        # deliver for it (no lost updates).
        if self.comm is not None:
            tdm = tp.tdm
            if hasattr(tdm, "bind") and getattr(tdm, "ce", None) is None:
                with self._cv:  # atomic slot claim across adder threads
                    claimed = getattr(self.comm, "_termdet_bound",
                                      None) is None
                    if claimed:
                        self.comm._termdet_bound = tdm
                if claimed:
                    tdm.bind(self.comm)
                elif getattr(tp, "auto_count", False):
                    # an UNBOUND fourcounter monitor has no wave driver
                    # and can never declare termination, and dynamic
                    # discovery (DTD) NEEDS the four-counter protocol to
                    # see in-flight remote activations — refuse loudly
                    # rather than risk premature quiescence or a wait()
                    # that always runs to its timeout
                    raise RuntimeError(
                        f"taskpool {tp.name}: comm engine already "
                        "carries a distributed termdet monitor and "
                        "this pool's task count is dynamically "
                        "discovered — one fourcounter pool at a time "
                        "(wait for the bound pool to finish first)")
                else:
                    # front-ends that manage their own accounting (PTG:
                    # pre-counted local tasks + write-back runtime
                    # actions, auto_count=False) are correct under local
                    # termdet — that IS the default distributed path
                    from .termdet import TermDetLocal

                    debug.warning(
                        "taskpool %s: comm engine already carries a "
                        "distributed termdet monitor; falling back to "
                        "local termdet (one fourcounter pool at a time)",
                        tp.name)
                    fresh = TermDetLocal()
                    fresh.monitor_taskpool(tp, tp._termination_detected)
                    tp.tdm = fresh
        with self._cv:
            self._taskpools[tp.taskpool_id] = tp
            self._active_taskpools += 1
        tp.attached(self)
        if tp.on_enqueue is not None:
            tp.on_enqueue(tp)
        if self.comm is not None:
            self.comm.new_taskpool(tp)
        # hold a runtime action across ready+startup so an empty-looking pool
        # cannot declare termination before its startup tasks are accounted
        tp.tdm.taskpool_addto_runtime_actions(tp, 1)
        tp.tdm.taskpool_ready(tp)
        startup = tp.startup(self)
        if startup:
            scheduling.schedule_ready(self, None, startup)
        tp.tdm.taskpool_addto_runtime_actions(tp, -1)
        self._notify_work()

    def _taskpool_terminated(self, tp: Taskpool) -> None:
        with self._cv:
            if tp.taskpool_id in self._taskpools:
                del self._taskpools[tp.taskpool_id]
                self._active_taskpools -= 1
            self._cv.notify_all()

    def abort(self, reason: str = "") -> None:
        """Cancel all outstanding work (reference ``parsec_abort``,
        ``runtime.h:236`` — softened: the process survives).  Every
        active taskpool terminates as FAILED (its ``wait()`` returns
        False), waiters wake immediately, and the context stays usable
        for new taskpools.  Already-queued tasks of aborted pools are
        discarded lazily at selection time (``_next_task``) — the
        scheduler structures are never reset here, because workers may be
        inside ``select()`` concurrently.  The last abort reason stays
        readable as ``ctx._abort_reason``."""
        with self._cv:
            self._abort_reason = reason or "aborted"
            pools = list(self._taskpools.values())
        debug.warning("context abort: %s (%d active taskpools)",
                      self._abort_reason, len(pools))
        for tp in pools:
            # atomic against a concurrent normal termination (the pool's
            # _term_lock): whichever side wins, on_complete fires at most
            # once and never after a successful cancellation
            if tp._force_fail():
                self._taskpool_terminated(tp)
        with self._cv:
            self._cv.notify_all()

    # ------------------------------------------------------------------
    # start / wait / test
    # ------------------------------------------------------------------
    def start(self) -> None:
        with self._cv:
            self._started = True
            self._cv.notify_all()

    def test(self) -> bool:
        """Non-blocking: True when no active taskpools remain."""
        self._progress_comm()
        with self._cv:
            return self._active_taskpools == 0

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Master joins the work loop until all taskpools quiesce."""
        self.start()
        return self._participate(lambda: self._active_taskpools == 0, timeout)

    def wait_taskpool(self, tp: Taskpool, timeout: Optional[float] = None) -> bool:
        self.start()
        return self._participate(lambda: tp.is_done(), timeout)

    def _participate(self, done: Callable[[], bool], timeout: Optional[float] = None) -> bool:
        import time

        es = self.current_es()
        own_es0 = False
        if es is None:
            # claim stream 0; if another thread drives it, wait passively
            own_es0 = self._es0_lock.acquire(blocking=False)
            es = self.streams[0] if own_es0 else None
            if own_es0:
                self._tls.es = es
        deadline = (time.monotonic() + timeout) if timeout is not None else None
        backoff = 1e-6
        try:
            while True:
                with self._cv:
                    if done():
                        return True
                    if deadline is not None and time.monotonic() >= deadline:
                        return False
                task = self._next_task(es) if es is not None else None
                if task is not None:
                    backoff = 1e-6
                    self._run_task(es, task)
                    continue
                self._progress_comm()
                with self._cv:
                    if done():
                        return True
                    self._cv.wait(backoff)
                backoff = min(backoff * 2, IDLE_BACKOFF_MAX)
        finally:
            if own_es0:
                self._tls.es = None
                self._es0_lock.release()

    # ------------------------------------------------------------------
    # worker internals
    # ------------------------------------------------------------------
    def _next_task(self, es: ExecutionStream) -> Optional[Task]:
        task = es.next_task
        if task is not None:
            es.next_task = None
            if not task.taskpool.failed:
                return task
            # the kept-next fast path must honor an abort too — an
            # in-flight predecessor may have stashed a successor of the
            # cancelled DAG here after abort() ran
        from ..profiling import pins

        with pins.span("core:select", es, rank=self.rank) as sp:
            task = self.scheduler.select(es)
            sp.end(task)
        # a task of an aborted pool may linger in a queue (its release was
        # in flight during the abort's scheduler reset): discard, don't run
        while task is not None and task.taskpool.failed:
            task = self.scheduler.select(es)
        if task is not None:
            es.stats["selected"] += 1
        return task

    def _worker_main(self, es: ExecutionStream) -> None:
        self._tls.es = es
        if self._bind_threads:
            from ..utils.binding import bind_current_thread

            bind_current_thread(self.vpmap.core_for(es.worker_id, self._cores))
        backoff = 1e-6
        while True:
            with self._cv:
                if self._shutdown:
                    return
                if not self._started or self._active_taskpools == 0:
                    self._cv.wait(0.05)
                    continue
            task = self._next_task(es)
            if task is None:
                with self._cv:
                    if self._shutdown:
                        return
                    self._cv.wait(backoff)
                backoff = min(backoff * 2, IDLE_BACKOFF_MAX)
                continue
            backoff = 1e-6
            self._run_task(es, task)

    def _run_task(self, es: ExecutionStream, task: Task) -> None:
        """Progress one task.  A raising body FAILS the pool — loudly
        and immediately, exactly like a device submit failure (round-4
        discipline, ``device/tpu.py _fail_task_pool``; reference
        hook-ERROR is fatal, ``scheduling.c:512``): ``wait()`` returns
        False at once, the pool leaves the active set, and its remaining
        queued tasks are discarded by ``_next_task`` (abort semantics) —
        they would only have consumed the failed task's stale data.  The
        old contain-and-continue policy let a raising producer forward
        its UNMODIFIED input downstream and report success (found by the
        dtt_pingpong port, round 5).

        With nranks > 1 the failure is broadcast through
        ``remote_dep._fail_pool_everywhere`` so healthy peer ranks abort
        fast instead of blocking until their full wait() timeout — the
        abort path discriminates parked / completed / live pools per
        rank, so a peer that never
        instantiated the pool parks the abort and a peer that already
        finished drops it.  Single-rank (or comm-less) contexts keep the
        local fail."""
        es.stats["executed"] += 1
        # job trace context for anything the body triggers on THIS
        # thread (collectives, executable-cache compiles + bcasts):
        # restore the previous value on exit so a nested
        # help_execute_one (DTD window throttling) hands the outer
        # task its context back
        prev_trace = jobtrace.current()
        jobtrace.set_current(getattr(task.taskpool, "trace_id", 0))
        try:
            scheduling.task_progress(self, es, task)
        except debug.FatalError:
            raise
        except Exception as e:
            debug.error("worker %d: task %r raised: %s", es.worker_id, task, e)
            import traceback

            traceback.print_exc()
            from ..comm.remote_dep import fail_pool_for_context

            why = f"task {task!r} body raised: {type(e).__name__}: {e}"
            fail_pool_for_context(self, task.taskpool, why)
            # incident artifacts: snapshot the flight recorder(s) so the
            # failure ships with the last N runtime events per rank
            # (no-op unless PARSEC_TPU_FLIGHT installed one; never raises)
            from ..profiling import flight as _flight

            _flight.dump_on_failure(why)
            # do NOT run the completion side: release_deps would forward
            # the failed task's stale payloads to REMOTE successors (and
            # write stale data back to remote home tiles) — healthy peer
            # ranks would consume them before discovering the loss.  The
            # pool is already force-terminated, so nothing waits on its
            # counters; just retire the task for the bookkeeping.  A
            # device-manager hook may have ALREADY completed this task
            # before raising on someone else's behalf — task.retired
            # guards that.
            if not task.retired:
                task.taskpool.task_done(task)
        finally:
            jobtrace.set_current(prev_trace)

    def _notify_work(self) -> None:
        with self._cv:
            self._cv.notify_all()

    def _progress_comm(self) -> None:
        if self.comm is not None:
            self.comm.progress_nonblocking()
            tdm = getattr(self.comm, "_termdet_bound", None)
            if tdm is not None:
                tdm.idle_progress()  # rank 0 wave driver (rate-limited)

    def current_es(self) -> Optional[ExecutionStream]:
        return getattr(self._tls, "es", None)

    def help_execute_one(self) -> bool:
        """Execute one ready task on the calling thread if safely possible
        (used by DTD window throttling). Worker threads use their own
        stream; other threads borrow stream 0 under its ownership lock.
        Returns True if a task ran."""
        es = self.current_es()
        if es is not None:
            task = self._next_task(es)
            if task is not None:
                self._run_task(es, task)
                return True
            return False
        if not self._es0_lock.acquire(blocking=False):
            return False  # someone else drives stream 0; let them progress
        try:
            es = self.streams[0]
            self._tls.es = es
            task = self._next_task(es)
            if task is not None:
                self._run_task(es, task)
                return True
            return False
        finally:
            self._tls.es = None
            self._es0_lock.release()

    # ------------------------------------------------------------------
    def schedule(self, tasks, es: Optional[ExecutionStream] = None, distance: int = 0) -> None:
        """Public entry to make externally-built tasks runnable."""
        if isinstance(tasks, Task):
            tasks = [tasks]
        scheduling.schedule_ready(self, es, tasks, distance)

    def flush(self, timeout: float = 300.0) -> None:
        """Every device module's write-backs are home (``TpuDevice.flush``
        of each): host tiles are current while the devices stay
        attached."""
        for dev in self.devices:
            flush = getattr(dev, "flush", None)
            if flush is not None:
                flush(timeout=timeout)

    def on_fini(self, cb) -> None:
        """Register a teardown callback, run at the start of :meth:`fini`
        while worker statistics are still intact (reference: PINS modules
        report at thread-fini time)."""
        self._fini_cbs.append(cb)

    def fini(self) -> None:
        """Reference ``parsec_fini``: drain and tear down."""
        # health plane first: the watchdog must not diagnose the
        # teardown as a stall, and the exporter must stop serving a
        # context whose structures are being dismantled
        for attr in ("watchdog", "health"):
            obj = getattr(self, attr, None)
            if obj is not None:
                try:
                    obj.stop()
                except Exception as e:
                    debug.warning("%s stop failed: %s", attr, e)
                setattr(self, attr, None)
        fl = getattr(self, "flight", None)
        if fl is not None:
            fl.uninstall()
            self.flight = None
        slo = getattr(self, "slo", None)
        if slo is not None:
            slo.uninstall()
            self.slo = None
        for cb in getattr(self, "_fini_cbs", []):
            try:
                cb()
            except Exception as e:  # teardown reports must not mask fini
                debug.warning("on_fini callback failed: %s", e)
        self._fini_cbs = []
        with self._cv:
            self._shutdown = True
            self._cv.notify_all()
        for t in self._threads:
            t.join(timeout=5)
        self._threads.clear()
        if self.comm is not None:
            self.comm.detach_context(self)
        from ..device import device as devmod

        devmod.detach_devices(self)
        self.scheduler.remove(self)
        # env-driven checker reports (no-ops unless PARSEC_TPU_HBCHECK /
        # PARSEC_TPU_LOCKDEP installed them): findings land on the
        # context for callers, are logged as warnings, and strict
        # hb-check raises
        if os.environ.get("PARSEC_TPU_HBCHECK", "0") not in ("", "0"):
            from ..analysis import hb as _hb

            self.hb_findings = _hb.live_report()
        if os.environ.get("PARSEC_TPU_LOCKDEP", "0") not in ("", "0"):
            from ..analysis import lockdep as _lockdep

            chk = _lockdep.checker()
            if chk is not None:
                self.lock_findings = chk.findings()
                for f in self.lock_findings:
                    debug.warning("lockdep: %s", f)
        debug.verbose(3, "core", "context down")

    # context manager sugar
    def __enter__(self) -> "Context":
        return self

    def __exit__(self, *exc) -> None:
        self.fini()
