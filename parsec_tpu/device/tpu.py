"""TPU device module: JAX/PJRT-backed accelerator execution.

This is the TPU-native re-design of the reference's generic GPU layer
(``/root/reference/parsec/mca/device/device_gpu.{c,h}`` + ``cuda`` module):

* **manager-thread model** — the first worker submitting a task becomes the
  device manager and drives the state machine until the queues drain;
  later workers enqueue and leave with ASYNC
  (``device_gpu.c:2542-2557``);
* **stage_in → exec → stage_out → epilog** pipeline phases
  (``device_gpu.c:2015,2166,2343``);
* **HBM residency with dual LRU** — clean vs dirty (owned) resident tiles,
  eviction with write-back (``device_gpu.h:240-243``); the reference's
  ``zone_malloc`` slab is replaced by byte-budget accounting against the
  PJRT allocator, which owns real HBM placement;
* **one in-order device queue** — JAX dispatch is asynchronous and runs
  one queue in order; a task completes at dispatch, or (with
  ``tpu_eager_complete=0``) from one in-order queue of computations
  polled via ``jax.Array.is_ready()`` (the reference's per-stream event
  queues, ``parsec_device_progress_stream``, ``device_gpu.c:1879-1999``).

The module is four boxes whose arrows point one way::

    tpu.py  (manager loop / submit_batch, signatures, program cache,
      |      THE staging walk, THE commit, detach)
      +--> residency.py   (lock, dual LRU, accounting, reserve / evict /
      |                    drop, advise)         imports nothing of tpu.py
      +--> staging.py     (transfer lane, committer, AND the write-back
      |                    halves)               imports nothing of tpu.py
      +--> value_args.py, scratch.py   (pure: FlowPlan, ValuePlan,
                                        scratch tiles)

One path per job: every task — a chunk of a wave or one that goes out
alone — is staged by ``_stage_chunk`` (by its signature's
:class:`FlowPlan`) and committed by ``_commit_chunk``; every tile that
has to come from the host goes through ``staging.StageIn.batch``.

Several accelerators under one context (``Context(accelerators=g)``): an
instance of this module per chip (``place``), each with a manager thread
of its own (``_manager_main``), its own residency, lanes, committer and
view of the live executables.  A peer's newest copy of a tile is a source
of the staging walk, chip to chip (``StageIn._land``, ``Data.hold_source``);
a commit here drops the peers' older copies (``_supersede``); a donation
asks the tile whether a peer's landing holds its array (``_not_sole``).

Departures from the reference, by TPU design:
* no device pointers — payloads are ``jax.Array``s; "allocation" is
  ``device_put`` and "free" is dropping the reference;
* task bodies are **functional**: a TPU chore body maps input arrays to
  fresh output arrays (XLA semantics), instead of mutating tile memory;
  outputs rebind the device copies of writable flows in declaration order.
  Where the task's builder knows that a read-write flow's input version
  has no other consumer (``Task._tpu_donate``: the pump's attach plan, a
  DTD insertion, a PTG pool on the ``Context`` route) and the staging walk finds
  nobody else holding its array, the program is compiled with that
  argument DONATED: XLA writes the output over the input's buffer, and a
  call allocates nothing for it (``_stage_chunk``, "Donation");
* kernels are jit-compiled once per (body, shapes, dtypes) by XLA and
  cached — the analogue of the reference's per-task-class dyld/cubin
  function lookup (``device_cuda_module.c`` find_function).  Compiles
  route through the context's :mod:`~parsec_tpu.compile_cache`: a
  persistent on-disk executable store plus, on multi-rank meshes, a
  compile-once-ship-serialized broadcast — so neither a process restart
  nor an N-rank mesh multiplies the XLA cold-start cost.
"""

from __future__ import annotations

import collections
import contextlib
import threading
import time
import weakref
from typing import Any, Deque, Dict, Iterable, List, Tuple

import jax
import jax.numpy as jnp

from ..core.lifecycle import AccessMode, HookReturn, DEV_TPU
from ..core.task import Task
from ..profiling import pins
from ..utils import debug, mca_param, register_component
from ..compile_cache import argsig
from ..data.data import Coherency, Data
from . import scratch
from .device import Device
from .residency import Residency, native_zone
from .staging import (HostWriter, StageIn, WritebackCommitter,
                      out_of_memory, span_id, stage_depth_param)
from .value_args import (ABSENT, HOOKED, LATE, PLACEHOLDER, READ, SCRATCH,
                         VALUE, FlowPlan, ValuePlan)


_OUT = int(AccessMode.OUT)


def _placeholders_at(dev_args) -> Tuple[int, ...]:
    """Positions of a staged argument list that stand for a tile there
    was nothing to stage for (a ``FlowPlan`` ``PLACEHOLDER``): part of a
    program's local key, since ``argsig`` reads such a stand-in as the
    array it is not."""
    return tuple(i for i, a in enumerate(dev_args)
                 if isinstance(a, jax.ShapeDtypeStruct))


def _no_lap(name: str) -> None:
    """``lap`` of a span that is not there (a commit from the poll, a
    task submitted by a caller that opened none)."""


def _pool_of(task: Task) -> int:
    """The ``pool`` a span of ``task`` carries: its taskpool's id (0 for
    a stand-in pool that has none)."""
    return getattr(task.taskpool, "taskpool_id", 0)


#: the pump's intra-wave split threshold: a lone ready batch is
#: re-sliced across the prefetch window (intra-wave double buffering)
#: only when its prestage would move at least this many host->device
#: bytes — splitting shrinks vmappable waves, so it must buy real
#: transfer overlap; below it a lone ready frontier ships as one wave
STAGE_SPLIT_BYTES = 256 << 10


#: one task as ``_stage_chunk`` leaves it: the task, its staged argument
#: list, its ``(position in body_args, tile)`` outputs
_Staged = Tuple[Task, List[Any], List[Tuple[int, Data]]]


#: one task whose commit waits for its program (``tpu_eager_complete=0``;
#: the analogue of a recorded stream event), as ``_commit_chunk`` takes it:
#: ``(_Staged without its arguments, outputs, stage_out hooks, donated)``
_InFlight = Tuple[_Staged, List[Any], Any, bool]


@register_component("device")
class TpuDevice(Device):
    """One JAX device (TPU chip; CPU backend in tests) as a task executor."""

    mca_name = "tpu"
    mca_priority = 50
    device_type = DEV_TPU
    per_accelerator = True

    @classmethod
    def available(cls) -> bool:
        # a backend JAX was told to use and cannot initialize RAISES
        # here: skipping the module would silently run every device
        # chore on the host
        return len(jax.local_devices()) > 0

    def __init__(self, context, index, place=None, of=1):
        """``place``: this module's place among the ``of`` accelerators
        of a context that drives several (``Context(accelerators=g)``),
        from 0; None for the one module of a context that drives one."""
        super().__init__(context, index)
        self._place = place
        #: "a fallback ran" counters — each is a slower path taken in
        #: place of the intended one, 0 on a healthy run
        self.stats.update(wave_fallbacks=0, submit_retries=0,
                          stage_batch_fallbacks=0)
        #: of ``wave_tasks``, those whose program ran them as ONE batched
        #: kernel (the body names the form: ``_batched``)
        self.stats["wave_tasks_batched"] = 0
        #: tasks' value arguments by what became of them
        #: (device/value_args.py)
        self.stats.update(value_args_dropped=0, value_args_packed=0,
                          value_args_positional=0, tile_args_dropped=0)
        #: tile operands handed to device programs, and those of them
        #: that were the same resident array as an earlier operand of
        #: the same program (a wave of a stencil's generation passes
        #: every tile to up to five of its tasks)
        self.stats.update(tile_args_passed=0, tile_args_repeated=0)
        #: tile operands DONATED to their program (a read-write flow's
        #: input version that nobody else reads: its output is written
        #: where it stands), and tasks that went out under the functional
        #: program because the staging walk found somebody else holding
        #: such a tile's array (0 on a healthy run); and the tasks
        #: committed whose builder did not say which inputs are the
        #: task's alone (``Task._tpu_donate`` None, not ``()``: nothing
        #: could be donated whoever held what)
        self.stats.update(tile_args_donated=0, donation_refused=0,
                          commits_donate_unknown=0)
        #: several accelerators under one context: copies here that a
        #: peer module's commit of a newer version dropped (nothing
        #: written home), and donations refused because a peer's landing
        #: held the array (of ``donation_refused``)
        self.stats.update(peer_copies_dropped=0, peer_holds_refused=0)
        #: wave programs whose width the byte bound set
        #: (``Residency.chunk_limit``), and the bytes of tiles read that
        #: the bound did not count, born here as they were
        #: (:meth:`_born_here`: only of waves that it looks into)
        self.stats.update(chunks_cut_by_bytes=0, chunk_bytes_born_here=0)
        #: calls of device programs: of the executable its ``_jit_cache``
        #: entry holds, and through the cache by the arguments' signature
        #: (an entry's first call, every call of a ``_static_values``
        #: body; :meth:`_dispatch`)
        self.stats.update(calls_bound=0, calls_signed=0)
        #: scratch tiles (device/scratch.py): first written / dropped
        #: with their last user on this device, and the bytes of them
        #: that crossed the host after all (0 unless one was evicted or
        #: a CPU body wrote it); the most bytes of them alive at once
        #: (born here and not yet freed: what a DAG's width costs)
        self.stats.update(scratch_tiles_born=0, scratch_tiles_freed=0,
                          scratch_bytes_in=0, scratch_bytes_out=0,
                          scratch_bytes_peak=0)
        self._scratch_live = 0
        #: who committed the outputs: chunks by the wave epilog, tasks
        #: one by one (together: ``executed_tasks``); and the pump's
        #: batches whose tiles were all resident, with nothing in them
        #: for the transfer lane to move; and the tasks committed whose
        #: builder did not say which outputs are last versions
        #: (``Task._tpu_home`` None: every output went to the committer)
        self.stats.update(wave_commits=0, task_commits=0,
                          prestage_skipped=0, commits_home_unknown=0)
        #: wave programs called, and outputs that a flow's custom
        #: ``stage_out`` hook transformed before their commit
        self.stats.update(wave_submits=0, custom_stage_out=0)
        #: copies home started at hand-over, for a version the task's
        #: builder knows to be the tile's last, and those of them that
        #: were the version the committer's drain collected
        #: (device/staging.py)
        self.stats.update(wb_started_early=0, wb_early_hits=0)
        #: tasks that this device's manager released, by the way they
        #: took from there: into this queue on the manager's own thread,
        #: or through the scheduler and a worker (:meth:`keep_released`)
        self.stats.update(handed_direct=0, handed_sched=0)
        #: precisions (``TiledMatrix(tile_dtype=...)``): tiles a body
        #: marked ``_converts`` wrote (a lower-precision twin made ONCE,
        #: where its source is produced) and their bytes; reads of such a
        #: twin by later tasks (each would have been a conversion of its
        #: own, had the readers converted); distinct wave signatures
        #: since the device was attached (a flow's dtype is part of a
        #: signature: what the precisions cost the batching)
        self.stats.update(convert_tiles=0, convert_bytes=0,
                          convert_shared_hits=0, wave_signatures=0)
        #: data_id of every converted twin made, and the signatures seen,
        #: since the device was attached (both forgotten at detach)
        self._converted: set = set()
        self._sigs_seen: set = set()
        #: one :class:`FlowPlan` per distinct list of flows a wave
        #: signature names (bounded by the task classes' layouts)
        self._flow_plans: Dict[Any, FlowPlan] = {}
        # rank → chip binding: each rank's runtime drives its OWN device
        # (reference: one CUDA module instance per visible GPU with
        # per-rank visibility, device_gpu.c).  Only process-addressable
        # devices qualify — jax.local_devices(), never the global list: on
        # multi-host, jax.devices() includes chips other processes own and
        # device_put onto them raises.  Ranks are laid out host-major
        # (ranks r..r+k on one host), so rank % local-count is the local
        # slot; tpu_device_index overrides for exotic layouts.
        devs = jax.local_devices()
        pref = mca_param.register(
            "device", "tpu_device_index", -1,
            help="local JAX device index this rank binds "
                 "(-1 = rank % local device count)")
        if place is None:
            jidx = pref if pref >= 0 else getattr(context, "rank", 0)
            self.jdev = devs[jidx % len(devs)]
        else:
            # several accelerators under one context: the chip is the
            # module's place among them, in the rank's slice of the
            # process's chips; never a chip twice
            jidx = getattr(context, "rank", 0) * of + place
            if jidx >= len(devs):
                raise RuntimeError(
                    f"accelerator {place + 1} of {of} (rank "
                    f"{getattr(context, 'rank', 0)}) asks for local device "
                    f"{jidx}; JAX reports {len(devs)}")
            self.jdev = devs[jidx]
        # budget: 85% of what PJRT says the chip has.  The CPU backend
        # reports no limit and gets a nominal 4 GiB; a TPU that reports
        # none is an error — eviction would be steered by a made-up size
        budget = mca_param.register(
            "device", "tpu_hbm_budget_mb", 0,
            help="HBM bytes (MB) managed for resident tiles (0=auto)")
        if budget:
            budget *= 1 << 20
        else:
            limit = (self.jdev.memory_stats() or {}).get("bytes_limit", 0)
            if not limit and self.jdev.platform == "tpu":
                raise RuntimeError(
                    f"{self.jdev}: memory_stats() reports no bytes_limit; "
                    "set device_tpu_hbm_budget_mb explicitly")
            budget = int(limit * 0.85) if limit else 4 << 30
        #: what this module's spans carry (``docs/TRACING.md``): the
        #: context's rank, and the pool and number of the newest batch
        #: submitted — a span on the committer thread names them as the
        #: batch that caused it
        self._rank = getattr(context, "rank", 0)
        self._span_pool = 0
        self._span_batch = 0
        #: device index used in Data.copies — assigned at attach
        self.data_index = index
        self.gflops_rating = 100.0  # strongly favour the MXU for eligible tasks

        #: reference gpu_device->mutex collapses to a boolean here: flipped
        #: under _lock together with the pending-queue append, closing the
        #: window where two workers could both become manager
        self._manager_active = False
        self._lock = threading.Lock()
        self._pending: Deque[Task] = collections.deque()
        #: computations whose commit waits for the program, in submit
        #: order (``tpu_eager_complete=0``): JAX executes one device
        #: queue in order, so one queue is what there is to poll
        self._deferred: Deque[_InFlight] = collections.deque()
        #: what the manager's completions released and this device alone
        #: can run (:meth:`keep_released`), until the manager hands it
        #: over between two drains; the manager's thread alone touches it
        self._released: List[Task] = []
        #: what the manager's loop read of its own time since the last
        #: task span, while a profiler session runs: ``hand_us``,
        #: ``handed``, ``units_us``, for the first ``dev:wave`` /
        #: ``dev:submit_one`` of the drain to carry (empty otherwise,
        #: and on the pump path)
        self._drain_stamp: Dict[str, Any] = {}
        #: eager completion: a single-controller JAX device queue already
        #: orders computations by data dependencies, so successor release
        #: does not need to wait for device events — the runtime completes
        #: the task at dispatch and the whole DAG streams asynchronously
        #: (one sync at taskpool wait). 0 restores reference-style event
        #: polling (device_gpu.c:1879-1999), which pays a full
        #: host<->device round-trip per completion.
        self._eager = bool(mca_param.register(
            "device", "tpu_eager_complete", 1,
            help="complete device tasks at dispatch; 0 = poll lane events"))
        #: wave batching: when the manager drains a ready wave of
        #: same-class tasks (same body, same arg signature, no
        #: donation/static-values/custom staging), submit the whole wave
        #: as ONE jitted multi-body program — one device enqueue and one
        #: pass of host-side dispatch per wave instead of one per task
        #: (the reference amortizes via per-stream in-order queues,
        #: device_gpu.c:1879-1999).  Waves decompose into power-of-2
        #: chunks so the compile cache stays bounded.  Value = minimum
        #: group size; 0 disables.
        self._wave_min = mca_param.register(
            "device", "tpu_wave_batch", 2,
            help="min same-signature ready-wave size batched into one "
                 "program (0 disables wave batching)")
        #: the executable cache this device compiles through (persistent
        #: disk store + cross-rank compile broadcast; compile_cache.py)
        self._ccache = getattr(context, "compile_cache", None)
        if self._ccache is None:
            from .. import compile_cache as _cc

            self._ccache = _cc.default_cache()
        #: body -> content fingerprint memo.  WEAK keys: an id()-keyed
        #: dict here poisons the persistent cache — a body fingerprinted
        #: just before a _jit_cache local-key HIT is never retained, so
        #: a later different-content body can land on the recycled id
        #: and inherit the stale fingerprint (= a wrong executable
        #: served with plausible shapes; seen as bf16-class numerics in
        #: an f32 run).  Weak keys die with the body instead.
        self._body_fp: "weakref.WeakKeyDictionary" = \
            weakref.WeakKeyDictionary()
        if (self._wave_min
                and getattr(self.jdev, "platform", "") == "cpu"
                and getattr(context, "nranks", 1) > 1):
            try:
                explicit = mca_param.source("device", "tpu_wave_batch") \
                    != "default"
            except KeyError:
                explicit = False
            if not explicit and not self._ccache.warm:
                # multi-rank CPU emulation (N in-process ranks on virtual
                # CPU devices): wave batching amortizes a device-enqueue
                # RPC that does not exist here, while every (kernel, wave
                # size) pair costs a fresh XLA compile PER RANK — on the
                # 8-rank dpotrf bench that tripled wall clock.  Real TPU
                # (and single-rank CPU, where the compile set is paid
                # once) keep the default; set the MCA param to force
                # either way.  A WARM executable cache lifts the
                # workaround: wave programs reload from the disk store
                # (and new ones ship serialized to peers), so the
                # per-rank explosion the auto-disable dodged is gone.
                self._wave_min = 0
        self._jit_cache: Dict[Any, Any] = {}
        #: a signature names donated flows only where the commit follows
        #: the call at once (eager completion: until its commit a donated
        #: tile's copy here is a deleted array) and no peer RANK may hold
        #: the array (device-capable fabrics ship ``jax.Array``s uncopied).
        #: A peer MODULE of this context may: its landing holds the array
        #: under the tile's own lock, and ``_not_sole`` asks there
        self._may_donate = self._eager \
            and getattr(context, "nranks", 1) <= 1
        #: data_ids the transfer lane pinned for the batch being
        #: submitted: those pins are this batch's own
        self._ahead_ids: frozenset = frozenset()
        # -- residency and the staging pipeline ---------------------------
        #: the write-back halves (device/staging.py) and the resident
        #: tiles with their accounting (device/residency.py); the lock
        #: order is residency.py's: _lock -> _res.lock -> Data.lock
        self._wb = HostWriter(self.data_index, self.stats, self.name,
                              self._rank,
                              adopt=self.jdev.platform != "cpu")
        self._res = Residency(self.data_index, budget, self.stats,
                              self._writeback_evict,
                              zone=native_zone(self.jdev.platform),
                              span=self._span)
        #: pump batch number -> the tiles the transfer lane staged (and
        #: pinned) ahead of it: unpinned once the batch is submitted
        self._prestaged: Dict[int, List[Data]] = {}
        self._h2d = StageIn(self._res, self._wb, self.jdev, self.stats,
                            self._span)
        #: pipeline depth (runtime_stage_depth): 1 = synchronous
        #: transfers (no prefetch lane, no committer — the A/B OFF arm);
        #: >= 2 arms the prefetch window and the write-back committer
        self.stage_depth = stage_depth_param()
        #: the pump's intra-wave split threshold, read by the pump
        #: from the device it drives
        self.stage_split_bytes = STAGE_SPLIT_BYTES
        self._committer = None
        #: several accelerators under one context: each module has a
        #: manager thread of its own (:meth:`_manager_main`), with an
        #: execution stream that is no worker's; whoever brings a task
        #: queues it and wakes that thread
        self._thread = None
        self._wake = threading.Event()
        self._stop = False

    def attach(self) -> None:
        if self._place is None:
            return
        from ..core.context import ExecutionStream

        ctx = self.context
        es = ExecutionStream(ctx.nb_workers + self._place, ctx)
        es.managing = self
        self._thread = threading.Thread(
            target=self._manager_main, args=(es,),
            name=f"dev-manager:{self.name}", daemon=True)
        self._thread.start()

    def _manager_main(self, es) -> None:
        """The manager thread of one of several accelerators: asleep
        until a task is queued (:meth:`kernel_scheduler`), then the
        manager's loop until the queues have run dry.  Nothing a task
        does gets out of that loop (a failing submit fails its pool
        there); what does is logged, fails the pools of what is queued,
        and the thread goes on."""
        self.context._tls.es = es
        while True:
            self._wake.wait()
            if self._stop:
                return
            self._wake.clear()
            with self._lock:
                if not self._pending and not self._deferred:
                    continue
                self._manager_active = True
            try:
                self._manager_loop(es)
            except BaseException as e:
                debug.error("manager of %s: %r", self.name, e)
                import traceback

                traceback.print_exc()
                with self._lock:
                    self._manager_active = False
                    orphans = list(self._pending) + self._released
                    self._pending.clear()
                    self._released = []
                for task in orphans:
                    if not getattr(task.taskpool, "failed", False):
                        self._fail_task_pool(
                            task, f"manager of {self.name} raised: {e!r}")

    def _span(self, name: str, **info):
        """A ``pins.span`` of this module, with the ``pool``, ``rank``
        and ``dev`` (the module's index in ``context.devices``) every
        span carries."""
        return pins.span(name, pool=self._span_pool, rank=self._rank,
                         dev=self.index, **info)

    @property
    def hbm_budget(self) -> int:
        return self._res.budget

    @hbm_budget.setter
    def hbm_budget(self, value: int) -> None:
        self._res.budget = value  # rebuilds the zone, migrating slots

    @property
    def hbm_used(self) -> int:
        return self._res.used

    @property
    def _zone(self):
        """The native zone allocator, or None where the byte counter
        accounts (the drivers count that as a fallback on a chip)."""
        return self._res.zone

    # ------------------------------------------------------------------
    # entry point from the scheduling core (chore hook delegates here)
    # ------------------------------------------------------------------
    def kernel_scheduler(self, es, task: Task) -> HookReturn:
        """Reference ``parsec_device_kernel_scheduler``
        (device_gpu.c:2510-2730).  The manager's own thread comes here
        too, with a task that one of its completions released and that
        only this device can run (:meth:`keep_released`): it queues the
        task for its next drain."""
        task._tpu_enq = time.perf_counter_ns()  # ready-queue wait starts
        if es is not None and es.managing is self:
            # (no lock round: nobody but this thread takes from the
            # queue, and the check that ends its loop is its own)
            task._tpu_direct = True
            self._pending.append(task)
            return HookReturn.ASYNC
        with pins.held(self._lock, "dev_lock"):
            self._pending.append(task)
            if self._manager_active:
                return HookReturn.ASYNC  # a manager is already running
            if self._thread is not None:
                # one of several accelerators: its own thread manages it,
                # never the worker, or the peer's manager, that brought
                # the task
                self._wake.set()
                return HookReturn.ASYNC
            self._manager_active = True
        # this worker becomes the manager
        if es is not None:
            es.managing = self
        try:
            self._manager_loop(es)
        except BaseException:
            # let another worker take over the still-queued work instead of
            # deadlocking every future device task behind a dead manager
            with self._lock:
                self._manager_active = False
            raise
        finally:
            if es is not None:
                es.managing = None
        return HookReturn.ASYNC  # completions were issued by the manager

    def keep_released(self, task: Task) -> bool:
        """``scheduling.schedule_ready`` on the thread that is this
        device's manager: one of its completions released ``task``.
        Where the task's class can run on this device alone, the device
        keeps it (True) and its manager hands it over itself
        (:meth:`_hand_over`): no push to the scheduler, no wake-up, no
        worker carries it here.  False sends the task through the
        scheduler: a class that a CPU or another device can run (a DTD
        comm task among them), a failed pool's task (``_next_task``
        discards it), a device switched off."""
        if self.enabled and not task.taskpool.failed \
                and task.task_class.only_device_type() == self.device_type:
            self.stats["handed_direct"] += 1
            self._released.append(task)
            return True
        self.stats["handed_sched"] += 1
        return False

    def _hand_over(self, es) -> int:
        """The tasks this thread's completions released and the device
        kept, progressed as a worker would progress them
        (``Context._run_task``: ``core:prepare_input``, the selection,
        the chore hook) — which ends in :meth:`kernel_scheduler` queueing
        each for the next drain.  Between two drains and under no span
        of this module: a ``prepare_input`` that raises fails the
        task's own pool there, and no commit knows of it.  Returns how
        many it progressed."""
        released, self._released = self._released, []
        for task in released:
            self.context._run_task(es, task)
        return len(released)

    def _manager_loop(self, es) -> None:
        # phase: check_in_deps + exec — submit everything pending.
        # The drained batch is grouped into same-signature WAVES first
        # (one jitted multi-body program per wave — one enqueue RPC
        # instead of one per task); everything else goes per-task.
        while True:
            # (a profiler session: the hand-over and the bucketing run
            # under no span, so their time rides on the drain's first
            # task span: docs/TRACING.md "Laps")
            began_ns = time.perf_counter_ns() if pins.tracing() else 0
            handed = self._hand_over(es)
            drained: List[Task] = []
            with pins.held(self._lock, "dev_lock"):
                while self._pending:
                    drained.append(self._pending.popleft())
            drained_ns = time.perf_counter_ns()  # ready-queue wait ends
            self._span_batch += 1
            units = self._units_of(drained)
            if began_ns:
                self._stamp_drain(
                    handed, drained_ns - began_ns,
                    time.perf_counter_ns() - drained_ns)
            # completions issued below run release_deps inline: a
            # coalescing window batches every activation this drained
            # batch produces into one frame per destination rank (the
            # "all activations of one progress cycle" aggregation of the
            # eager/rendezvous protocol; no-op without a comm engine)
            comm = getattr(self.context, "comm", None)
            win = comm.coalesce() if comm is not None \
                else contextlib.nullcontext()
            with win:
                self._submit_units(units, es, True, drained_ns)
            # phase: get_data_out — retire ready computations in order
            with self._span("dev:poll"):
                progressed = self._poll_deferred(es)
            with pins.held(self._lock, "dev_lock"):
                if not self._pending and not self._deferred \
                        and not self._released:
                    self._manager_active = False
                    return
            if not progressed and self._deferred:
                # nothing completed this spin: block on the oldest event
                # (the reference polls events; jax lets us wait cheaply)
                with self._span("dev:block"):
                    try:
                        self._deferred[0][1][0].block_until_ready()
                    except Exception:
                        pass

    def _stamp_drain(self, handed: int, hand_ns: int, units_ns: int) -> None:
        """What the next task span carries of the manager's loop: the
        time from before the hand-over to the queue taken (``hand_us``,
        ``Context._run_task`` and its ``core:prepare_input`` included)
        for ``handed`` tasks, and from there to :meth:`_units_of` back
        (``units_us``).  A drain that opens no task span leaves its
        share to the next."""
        stamp = self._drain_stamp or dict(hand_us=0.0, handed=0, units_us=0.0)
        stamp["hand_us"] += hand_ns / 1e3
        stamp["handed"] += handed
        stamp["units_us"] += units_ns / 1e3
        self._drain_stamp = stamp

    def _take_stamp(self) -> Dict[str, Any]:
        """The drain's stamp, for the task span that is about to open
        (empty, and nothing done, unless a session runs)."""
        stamp = self._drain_stamp
        if stamp:
            self._drain_stamp = {}
        return stamp

    def _units_of(self, tasks: List[Task]) -> List[Tuple[str, Any]]:
        """One O(n) bucketing pass: the signature computed ONCE per task,
        waves emitted in arrival order of their first member; tasks of a
        pool that already failed are discarded, never executed."""
        units: List[Tuple[str, Any]] = []
        buckets: Dict[Any, List[Task]] = {}
        for task in tasks:
            if getattr(task.taskpool, "failed", False):
                continue
            sig = self._signature_of(task) if self._wave_min > 0 else None
            if sig is None or sig[0] is None:
                units.append(("single", task))
                continue
            key = (id(task.taskpool), sig)
            group = buckets.get(key)
            if group is None:
                group = buckets[key] = []
                units.append(("wave", group))
            group.append(task)
        return units

    def _submit_units(self, units: List[Tuple[str, Any]], es,
                      complete: bool, drained_ns: int = 0) -> None:
        for kind, item in units:
            if kind == "single":
                self._submit_one(item, es, complete, drained_ns)
                continue
            group = item
            if len(group) >= max(2, self._wave_min):
                try:
                    self._submit_wave(group, es, complete, drained_ns)
                    continue
                except Exception as e:
                    # only pre-dispatch failures escape _submit_wave
                    # (staging/trace/enqueue — no task side effects
                    # yet); per-task epilog/completion errors are
                    # contained inside it with a loud pool fail
                    if self._no_memory(group[0], e):
                        continue
                    self.stats["wave_fallbacks"] += 1
                    debug.warning(
                        "wave submit of %d tasks failed (%s); "
                        "falling back per-task", len(group), e)
            for t in group:
                if not getattr(t, "_tpu_completed", False) \
                        and not getattr(t.taskpool, "failed", False):
                    self._submit_one(t, es, complete, drained_ns)

    # ------------------------------------------------------------------
    # pump-mode batch dispatch (native scheduler, zero-entry lifecycle)
    # ------------------------------------------------------------------
    def submit_batch(self, tasks: List[Task], es=None,
                     batch_no: int = 0) -> None:
        """Dispatch one native-popped ready batch synchronously WITHOUT
        per-task completion: the pump loop (dsl.native_exec) retires the
        whole batch afterwards with one ``pz_graph_done_batch`` call, so
        successor release happens in the native engine, not here.  The
        execution side — staging, wave grouping, JIT dispatch, epilog,
        failure discipline — is the manager loop's, reused with
        ``complete=False``; only ``scheduling.complete_execution`` /
        ``on_complete`` are skipped.  ``batch_no`` is the pump's number
        for the batch: the ``dev:submit_batch`` span carries it."""
        if tasks:
            self._span_pool = _pool_of(tasks[0])
        self._span_batch = batch_no
        ahead = self._prestaged.get(batch_no)
        if ahead:
            self._ahead_ids = frozenset(d.data_id for d in ahead)
        with self._span("dev:submit_batch", batch=batch_no,
                        n=len(tasks)) as sp:
            try:
                units = self._units_of(tasks)
                sp.lap("units")
                self._submit_units(units, es, False)
            finally:
                ahead = self._prestaged.pop(batch_no, None)
                if ahead:
                    self._res.unpin(ahead)
                    self._ahead_ids = frozenset()
                sp.lap("waves")
            # a transient-submit retry re-queues through ``_pending``
            # (the manager loop's channel); there is no manager in pump
            # mode, so drain retries here before handing the batch back
            # for retirement
            while True:
                with pins.held(self._lock, "dev_lock"):
                    if not self._pending:
                        sp.lap("retry")
                        return
                    retry = list(self._pending)
                    self._pending.clear()
                for t in retry:
                    if not getattr(t, "_tpu_completed", False) \
                            and not getattr(t.taskpool, "failed", False):
                        self._submit_one(t, es, complete=False)

    @staticmethod
    def _fire_exec(task: Task, site: str, wave: int = 0) -> None:
        """EXEC_BEGIN/END for NATIVE-dispatched tasks (opt-in via the
        ``pins_exec`` marker): on the dynamic path the scheduling core
        wraps the chore hook in EXEC pins, but on the native path no
        Python scheduling core exists — without these fires the trace
        shows a host-gap hole exactly where device waves ran, and
        ``profiling.critpath`` cannot attribute them.  Wave metadata
        (chunk size; 0 = per-task submit) rides ``task.prof`` so
        observers can tell batched dispatch from singles."""
        if getattr(task, "pins_exec", False) and pins.active(site):
            task.prof["wave"] = wave
            pins.fire(site, None, task)

    def _content_fp(self, body) -> str:
        """Content fingerprint of a body callable, memoized while the
        body object is alive (weak keys — see the _body_fp comment for
        why id() keys are a correctness bug, not a style choice)."""
        from ..compile_cache import code_fingerprint

        try:
            fp = self._body_fp.get(body)
        except TypeError:  # unhashable/unweakrefable body
            return code_fingerprint(body)
        if fp is None:
            fp = code_fingerprint(body)
            try:
                self._body_fp[body] = fp
            except TypeError:
                pass
        return fp

    def _cached_jit(self, local_key, build):
        """One compile path for every device program: the in-device
        ``_jit_cache`` keeps the fast id-keyed lookup the dispatch loop
        had, while the executable cache behind it adds the persistent
        disk store and the cross-rank compile broadcast.  An entry is
        ``(program, ValuePlan or None, executable or None)``;
        ``build()`` gives a new one's ``(content key, function, donated
        positions, plan)``.  The ``dev:jit`` span notes ``miss=1`` on a
        program's first use by this device; the compile or load itself
        comes at its first call (:meth:`_dispatch`), as a ``cc:compile``
        span under ``dev:dispatch``."""
        with self._span("dev:jit") as sp:
            entry = self._jit_cache.get(local_key)
            if entry is None:
                sp.note(miss=1)
                content_key, fn, donate, plan = build()
                # (one of several accelerators: its own view of the live
                # executables, which are compiled for its chip; the one
                # module of a context makes the call it always made)
                view = {} if self._place is None else {"place": self._place}
                entry = self._jit_cache[local_key] = (self._ccache.jit(
                    fn, key=content_key, donate_argnums=tuple(donate),
                    **view), plan, None)
        return entry

    def _dispatch(self, local_key, entry, flat):
        """THE call of a device program, under its ``dev:dispatch``
        span.  An entry whose local key holds its arguments' signature
        has ONE signature for its whole life (the tasks of a chunk share
        an interned ``FlowPlan``, and the key names ``cnt`` and the
        placeholders), so its first call asks the cache for it
        (``compile_cache._CachedFunction.call``: ``argsig``, the layers,
        ``cc:compile``) and the entry keeps the executable that gave:
        every later call is ``executable(*flat)`` (``bound=1`` on the
        span).  An entry without a plan is a ``_static_values``
        program's: its key says nothing of the shapes, every call asks.
        What the cache's own call would retry through the plain jit
        (``_CachedFunction.retryable``) unbinds the entry and goes that
        way, once; anything else raises as it did."""
        program, plan, exe = entry
        with self._span("dev:dispatch") as sp:
            if exe is not None:
                try:
                    outs = exe(*flat)
                except Exception as e:
                    if not program.retryable(exe, e):
                        raise
                    self._jit_cache[local_key] = (program, plan, None)
                else:
                    # a call that needed no compile is a hit, as the
                    # cache's own look-up counts it
                    self._ccache.stats["hits_mem"] += 1
                    self.stats["calls_bound"] += 1
                    sp.note(bound=1)
                    return outs
            outs, exe = program.call(flat)
            if plan is not None:
                self._jit_cache[local_key] = (program, plan, exe)
            self.stats["calls_signed"] += 1
            sp.note(bound=0)
        return outs

    @staticmethod
    def _value_plan(task: Task, body, dev_args) -> ValuePlan:
        """The :class:`ValuePlan` of the program that runs ``body`` on
        tasks staged like ``task``."""
        return ValuePlan(body, dev_args, sum(
            1 for s in task.body_args or () if s[0] == "value"))

    def _count_values(self, plan: ValuePlan, ntasks: int, flat,
                      nouts: int = 0, don: int = 0) -> Dict[str, int]:
        """``ntasks`` tasks went out under ``plan`` with the program's
        arguments ``flat``: the counters, and the same for the
        ``dev:wave`` / ``dev:submit_one`` span (returned) with ``outs``,
        the outputs its epilog commits; ``rep`` counts the tile operands
        that are an earlier operand's array again, ``don`` those the
        program was given to write its outputs over."""
        drop, pack, pos, tdrop = (
            plan.dropped * ntasks, plan.packed * ntasks,
            plan.positional * ntasks, plan.tiles_dropped * ntasks)
        tiles = [id(a) for a in flat if isinstance(a, jax.Array)]
        rep = len(tiles) - len(set(tiles))
        self.stats["value_args_dropped"] += drop
        self.stats["value_args_packed"] += pack
        self.stats["value_args_positional"] += pos
        self.stats["tile_args_dropped"] += tdrop
        self.stats["tile_args_passed"] += len(tiles)
        self.stats["tile_args_repeated"] += rep
        self.stats["tile_args_donated"] += don
        return dict(vdrop=drop, vpack=pack, vpos=pos, tdrop=tdrop,
                    outs=nouts, rep=rep, don=don)

    def _count_converts(self, staged: List[_Staged], outs) -> None:
        """A program of a body marked ``_converts`` went out: its outputs
        are lower-precision twins, each made once for all its readers."""
        self.stats["convert_tiles"] += len(outs)
        self.stats["convert_bytes"] += sum(o.nbytes for o in outs)
        self._converted.update(data.data_id for (_t, _a, ospecs) in staged
                               for (_pos, data) in ospecs)

    def _submit_one(self, task: Task, es, complete: bool = True,
                    drained_ns: int = 0) -> None:
        """Per-task submit with the retry/fail-loudly discipline."""
        self._span_pool = _pool_of(task)
        waited = (drained_ns - task._tpu_enq) // 1000 if drained_ns else 0
        stamp = self._take_stamp()
        try:
            sig = self._signature_of(task)
            with self._span("dev:submit_one", cls=task.task_class.name, n=1,
                            batch=self._span_batch, waited_us=waited,
                            direct=int(task._tpu_direct),
                            dtypes=sig[1].dtypes if sig else "",
                            **stamp) as sp:
                self._submit(task, es, complete=complete, span=sp)
                sp.lap("commit")
        except Exception as e:
            if not getattr(task, "_tpu_completed", False) \
                    and self._no_memory(task, e):
                return
            debug.error("tpu submit of %r failed: %s", task, e)
            import traceback

            traceback.print_exc()
            # eager _submit may have begun releasing successors
            # before raising — retrying or completing again would
            # double-release dependency counters: fail the pool
            if getattr(task, "_tpu_completed", False):
                self._fail_task_pool(
                    task, f"device epilog/completion raised: {e!r}")
                return
            # one retry with fresh state: a transient PJRT error
            # must not zero a run (_submit re-stages inputs from the
            # newest valid copies, so the retry starts clean).  ONLY
            # when the first attempt provably had no side effects —
            # a partially-committed epilog
            # (some output tiles rebound + version-bumped) or a
            # donated input buffer would make the retry
            # double-apply INOUT updates: silent corruption, the
            # exact mode this path exists to eliminate.
            attempts = getattr(task, "_tpu_attempts", 0) + 1
            task._tpu_attempts = attempts
            if attempts == 1 and not getattr(task, "_tpu_effects",
                                             False):
                debug.warning("retrying device submit of %r", task)
                self.stats["submit_retries"] += 1
                task._tpu_enq = time.perf_counter_ns()
                with self._lock:
                    self._pending.append(task)
                return
            # retry failed too: completing the task anyway would
            # hand successors a garbage placeholder and the pool
            # would quiesce "successfully" with wrong numerics —
            # the worst failure mode a runtime can have (reference
            # treats hook ERROR as fatal, scheduling.c:512).  Fail
            # the pool: wait() returns False, successors stay
            # unreleased.
            self._fail_task_pool(
                task, f"device submit failed after retry: {e!r}")

    def _no_memory(self, task: Task, e: BaseException) -> bool:
        """A submit failed for want of device memory — PJRT's
        ``RESOURCE_EXHAUSTED``, or a staging batch for which no room
        could be made under the budget (``staging.NoRoom``): the pool
        fails here and now.
        Neither a per-task fallback nor a retry has more memory, and a
        solve that limps on past its budget is a different result."""
        if not out_of_memory(e):
            return False
        debug.error("device out of memory submitting %r: %s", task, e)
        self._fail_task_pool(task, f"device out of memory: {e!r}")
        return True

    def _fail_task_pool(self, task: Task, why: str) -> None:
        """Device execution failed unrecoverably: fail the task's pool so
        ``wait()`` returns False.  Reference: hook ERROR is fatal
        (``scheduling.c:512``); completing with a placeholder would be
        wrong-answer-with-rc-0.

        LOCAL fail only — no cross-rank abort broadcast from the device
        layer: this rank cannot know whether the pool is instantiated on
        peers (a rank-local pool's abort would be PARKED on ranks that
        never saw the name and replayed into the next same-named healthy
        pool).  Peers of a genuinely distributed pool discover the loss
        through the payload/activation paths or their wait() timeout."""
        from ..comm.remote_dep import _fail_pool

        _fail_pool(task.taskpool, why)

    # ------------------------------------------------------------------
    # stage_in / submit
    # ------------------------------------------------------------------
    def _signature_of(self, task: Task):
        """:meth:`_wave_signature`, asked once a task: the flows of a
        ready task no longer change (whoever writes one of its tiles
        completed before it became ready), so the pump's look ahead and
        the submit share the answer."""
        sig = task._tpu_sig
        if sig is False:
            sig = task._tpu_sig = self._wave_signature(task)
            if sig is not None and sig not in self._sigs_seen:
                self._sigs_seen.add(sig)
                self.stats["wave_signatures"] += 1
        return sig

    @staticmethod
    def _wave_body_key(body):
        """What a wave signature starts with, or None for a body whose
        tasks go out alone: bodies with baked static values (per-task
        traces), bodies that name donated arguments THEMSELVES
        (``_donate_args``: the body's author vouches for whole-matrix
        in-place chains, positions no signature compares, and their
        outputs never go home) or custom staging hooks — what a wave
        donates is decided a flow at a time, by the graph and the staging
        walk, and is part of the signature (:meth:`_wave_signature`);
        fused supertasks (dsl.fusion) are
        already coarse-grained multi-body programs with their own cache
        key — re-batching them into waves would nest programs for no
        dispatch win."""
        if getattr(body, "_static_values", False) \
                or getattr(body, "_donate_args", None) \
                or getattr(body, "_stage_in", None) \
                or getattr(body, "_stage_out", None) \
                or getattr(body, "_fused_n", 0):
            return None
        return getattr(body, "_jit_key", None) or id(body)

    def _wave_signature(self, task: Task):
        """``(wave key, FlowPlan)`` of a task with a device body (None
        without one).  Two tasks with equal signatures and a key trace
        identically through a shared wave program; the key is None for
        a task that goes out alone (:meth:`_wave_body_key`; a data arg
        whose shape nothing says), and its plan still drives THE staging
        walk and THE commit.  What the body fixes is asked once a chore;
        shapes, dtypes and modes are compared as the objects they are,
        and the list of flows is interned as its :class:`FlowPlan`, so a
        signature hashes and compares by identity from then on.  The
        flows whose input version the task may donate (``_tpu_donate``,
        where this device donates at all and the body names no donated
        arguments of its own) are part of what is interned: the tasks of
        one wave donate the same positions."""
        chore = task.selected_chore
        body = chore.body_fn if chore is not None else None
        if body is None:
            return None
        memo = chore.wave_key
        if memo is None or memo[0] is not body:
            # per-flow custom staging (reference stage_in/stage_out
            # device hooks, device_gpu.h:62-94), keyed by data-arg order
            si = getattr(body, "_stage_in", None) or {}
            so = getattr(body, "_stage_out", None) or {}
            memo = chore.wave_key = (
                body, self._wave_body_key(body),
                {n: (si.get(n), so.get(n)) for n in {*si, *so}} or None,
                not getattr(body, "_donate_args", None))
        _body, wave_key, hooks, by_flow = memo
        flows: List[Any] = []
        nth = -1
        for kind, payload, mode in (task.body_args or ()):
            if kind == "data":
                nth += 1
                if payload is None:
                    flows.append(None)
                    continue
                shape, dtype = payload.shape, payload.dtype
                if payload.scratch is not None and scratch.unborn(payload):
                    # no argument of the program: never in one wave with
                    # a task whose tile of this flow has been written
                    flow = ("unborn", tuple(shape), dtype, mode)
                else:
                    if shape is None or dtype is None:
                        p = getattr(payload.newest_copy(), "payload", None)
                        shape = getattr(p, "shape", None)
                        dtype = getattr(p, "dtype", None)
                    if shape is None or dtype is None:
                        wave_key = None
                        flow = (None, None, mode)
                    else:
                        flow = (tuple(shape), dtype, mode)
                if hooks is not None and nth in hooks:
                    flow += hooks[nth]
                flows.append(flow)
            elif kind == "value":
                # traced runtime arg: the TYPE shapes the trace
                flows.append(type(payload))
            elif kind == "scratch":
                flows.append(("scratch", tuple(payload[0]), payload[1]))
            else:
                flows.append(kind)
        donate = (task._tpu_donate or ()) \
            if by_flow and self._may_donate else ()
        key = (tuple(flows), donate)
        plan = self._flow_plans.get(key)
        if plan is None:
            plan = self._flow_plans[key] = FlowPlan(*key)
        return (wave_key, plan)

    def _submit_wave(self, tasks: List[Task], es, complete: bool = True,
                     drained_ns: int = 0) -> None:
        """Submit a same-signature ready wave as one (or a few
        power-of-2) jitted multi-body programs: ONE device enqueue per
        chunk instead of one per task.

        Inputs are staged PER CHUNK, immediately before that chunk's
        dispatch: peak HBM holds one chunk's inputs plus its in-flight
        outputs, never the whole wave's — a large wave of large tiles
        must not OOM where per-task dispatch would not.  A chunk is
        bounded by BYTES as well as by the power of two: what its
        program brings onto the device stays under
        ``Residency.chunk_limit``, a share of the budget — 64 gemm tasks
        over 16 MiB tiles are 4 GiB — down to a chunk of one task.  A
        task brings at most ``FlowPlan.nbytes``, every tile it reads and
        every tile it writes; where a wave at that price is cut at all,
        each task is counted for itself (:meth:`_born_here`): a tile
        it only reads that was born on this device and is here still is
        no new memory.  What a task costs is its graph's, not the
        moment's, as long as no born-here tile is spilled: the same
        solve is then cut into the same chunks every time.  The room for
        what a chunk was counted at is had before its program is called
        (``Residency.wait_for``: by waiting for the chip where scratch
        tiles let go are still charged, never by an eviction that costs
        a copy).

        Failure containment is a PER-CHUNK invariant: a chunk's
        staging/trace/enqueue errors RAISE before any task of THAT chunk
        has side effects, so the manager's per-task fallback is safe for
        every not-yet-committed task — of a chunk that donates nothing.
        A chunk whose program was given donated tiles (the read-write
        flows of ``FlowPlan.donates``: versions that the tasks' builder
        says nobody else reads and that the staging walk found held by
        nobody else) marks its tasks ``_tpu_effects`` before the call: a
        call that raises may have consumed its inputs, so such a chunk
        is never retried task by task; it fails its pool there and then
        (:meth:`_launch`).
        Earlier chunks of the same wave may already have committed their
        epilogs by then — the fallback does not double-run them only
        because each committed task is marked ``_tpu_completed``, which
        the manager-loop fallback checks before resubmitting.  Once a
        chunk's commit begins, errors are contained HERE with a loud
        pool fail (the same discipline as ``_submit_one``'s completed
        branch): a half-committed chunk must be neither retried
        (double-apply) nor silently skipped (wait() would hang to
        timeout).

        One ``dev:wave`` span per chunk, that is per device program,
        with the children ``dev:stage_args``, ``dev:jit``,
        ``dev:dispatch`` (the host's enqueue of the program, not the
        chip's execution of it) and ``dev:epilog``; ``cut`` says what
        set the chunk's width (``bytes``: the bound; ``tasks``: the
        tasks left), ``counted`` the bytes its tasks were counted at."""
        body = tasks[0].selected_chore.body_fn
        cls = tasks[0].task_class.name
        self._span_pool = _pool_of(tasks[0])
        sig = self._signature_of(tasks[0])
        if sig is None or sig[0] is None:
            raise ValueError(f"{tasks[0]!r} cannot ride a wave")
        plan = sig[1]
        # the body OBJECT (not id(body)): an id-keyed entry outlives the
        # body it described, and a recycled id would serve a dead body's
        # wave program — keying on the object pins it alive instead
        base_key = getattr(body, "_jit_key", None) or body
        start = 0
        remaining = len(tasks)
        limit = self._res.chunk_limit
        # each task's bytes up to it, where the most a task can cost
        # would cut the wave; a wave that fits at that price is not
        # looked into
        upto = None if plan.nbytes * remaining <= limit \
            else self._born_here(tasks, plan)
        while remaining:
            if getattr(tasks[0].taskpool, "failed", False):
                return  # (a chunk that failed it stages no further one)
            # the largest power of two the tasks left and the bytes allow
            by_tasks = cnt = 1 << (remaining.bit_length() - 1)
            if upto is None:
                counted = cnt * plan.nbytes
            else:
                while cnt > 1 and upto[start + cnt] - upto[start] > limit:
                    cnt >>= 1
                counted = upto[start + cnt] - upto[start]
                if cnt < by_tasks:
                    self.stats["chunks_cut_by_bytes"] += 1
            grp = tasks[start:start + cnt]
            start += cnt
            remaining -= cnt
            waited = sum(drained_ns - t._tpu_enq
                         for t in grp) // 1000 if drained_ns else 0
            stamp = self._take_stamp()
            with self._span("dev:wave", cls=cls, n=cnt,
                            batch=self._span_batch, waited_us=waited,
                            direct=sum(t._tpu_direct for t in grp)
                            if drained_ns else 0,
                            dtypes=plan.dtypes,
                            cut="bytes" if cnt < by_tasks else "tasks",
                            counted=counted, **stamp) as sp:
                self._res.wait_for(counted)
                sp.lap("room")
                self._submit_chunk(grp, body, base_key, plan, es, complete,
                                   sp)
                sp.lap("commit")

    def _born_here(self, tasks: List[Task], plan: FlowPlan) -> List[int]:
        """What the tasks of a wave cost the chunk they ride in, as
        running sums (``upto[k]``: the first ``k`` tasks together).  A
        task costs ``plan.nbytes`` less every tile it reads, and does
        not write, that was BORN on this device and lives nowhere else:
        a scratch tile (a ``NEW`` flow's) or a tile of a collection born
        here (``Data.scratch`` is not None) whose current copy is here.
        The chunk's program brings nothing of it onto the device; it is
        there, and accounted.  Everything else counts: a tile with a
        home whether or not it is resident at this moment (that is the
        schedule's, and a program set must not depend on it), a tile
        written as a new buffer whether or not its input is donated and,
        with it, that input (a refused donation keeps both), a born-here
        tile that an eviction spilled (the walk will stage it: the one
        case where the moment decides, and ``scratch_bytes_out`` says
        when)."""
        idx = self.data_index
        static, reads = plan.nbytes, plan.read_bytes
        upto = [0]
        total = born = 0
        for task in tasks:
            specs = task.body_args
            cost = static
            for pos, nbytes in reads:
                data = specs[pos][1]
                if data.scratch is not None \
                        and data.current_copy(idx) is not None:
                    cost -= nbytes
            born += static - cost
            total += cost
            upto.append(total)
        self.stats["chunk_bytes_born_here"] += born
        return upto

    def _submit_chunk(self, grp: List[Task], body, base_key,
                      fplan: FlowPlan, es, complete: bool, wave_span) -> None:
        """One power-of-2 chunk of a wave: stage, look the program up,
        dispatch it, commit every task's outputs.  The program's
        arguments are the tasks' tiles; their values reach the bodies as
        the program's :class:`ValuePlan` says."""
        cnt = len(grp)
        cls = grp[0].task_class.name
        pinned: List[Data] = []
        try:
            self._run_chunk(grp, cnt, cls, body, base_key, fplan, es,
                            complete, wave_span, pinned)
        finally:
            self._res.unpin(pinned)

    def _run_chunk(self, grp: List[Task], cnt: int, cls: str, body,
                   base_key, fplan: FlowPlan, es, complete: bool, wave_span,
                   pinned: List[Data]) -> None:
        """:meth:`_submit_chunk` between the pins: ``pinned`` takes the
        chunk's tiles as they are staged, the caller lets go of them
        once the chunk is committed."""
        staged, refused = self._stage_span(grp, fplan, pinned)
        wave_span.lap("stage")
        if not refused:
            wave_span.note(**self._launch(staged, cls, body, base_key, fplan,
                                          fplan.donates, es, complete,
                                          wave_span))
            return
        # somebody else holds a tile that a task of the chunk would have
        # donated: those tasks leave the chunk's program and go out, as
        # they are staged, under the functional one (no donated
        # position); the others keep theirs, in powers of two
        notes: Dict[str, int] = {}
        for part, donates in (
                ([one for k, one in enumerate(staged) if k not in refused],
                 fplan.donates),
                ([staged[k] for k in sorted(refused)], ())):
            at = 0
            while at < len(part):
                n = 1 << ((len(part) - at).bit_length() - 1)
                for key, v in self._launch(
                        part[at:at + n], cls, body, base_key, fplan, donates,
                        es, complete, wave_span).items():
                    notes[key] = notes.get(key, 0) + v
                at += n
        wave_span.note(**notes)

    def _launch(self, staged: List[_Staged], cls: str, body, base_key,
                fplan: FlowPlan, donates, es, complete: bool,
                wave_span) -> Dict[str, int]:
        """ONE wave program over staged tasks: look it up, call it,
        commit every task's outputs; returns what the ``dev:wave`` span
        notes of it (:meth:`_count_values`), and laps on it the
        stretches between its children (``key``, ``flatten``, ``call``,
        ``count``; the caller closes ``commit``).  ``donates``: the flows of
        every task whose input tile the program is given to write the
        matching output over (``FlowPlan.donates``, or none: the
        functional program).  An entry keeps one set of donated
        positions for its life: they are in its local key, and in its
        content key through the executable cache's fingerprint."""
        cnt = len(staged)
        args0, nout = staged[0][1], fplan.nout
        # a body whose work is a dependent loop of small steps names the
        # form that runs a wave of it as ONE kernel (``_batched``): the
        # program then calls that form once over the stacked tiles where
        # it would have unrolled the tasks' bodies one after another
        batched = getattr(body, "_batched", None)

        def build():
            plan = self._value_plan(staged[0][0], body, args0)
            if batched is None:
                form = ()

                def _wave(*flat):
                    outs: List[Any] = []
                    for args in plan.bodies_args(flat, cnt):
                        o = body(*args)
                        outs.extend(o if isinstance(o, (tuple, list))
                                    else (o,))
                    return tuple(outs)
            else:
                # never an executable of the unrolled program for this
                # one, from this process or from the store
                form = ("batched", self._content_fp(batched))

                def _wave(*flat):
                    outs = batched(*plan.stacked_args(flat, cnt))
                    # task-major, as the unrolled program returns them
                    return tuple(o[t] for t in range(cnt) for o in outs)
            # the task class in the program's name: the device trace's
            # ``XLA Modules`` line then splits the chip's time by class
            _wave.__name__ = f"_wave_{cls}"
            return (("wave", cls, self._content_fp(body), len(args0), nout,
                     cnt) + form + plan.tag, _wave,
                    plan.donate(plan.aliased(args0, donates), cnt), plan)
        local_key = ("wave", cls, base_key, argsig(args0),
                     _placeholders_at(args0), nout, donates, cnt)
        if batched is not None:
            # (a flag, not the form: ``base_key`` names the body, and a
            # PTG wraps the form anew for every taskpool)
            local_key += ("batched",)
        entry = self._cached_jit(local_key, build)
        program, plan = entry[0], entry[1]
        wave_span.lap("key")
        flat = plan.flatten([args for (_t, args, _o) in staged])
        grp = [one[0] for one in staged]
        wave_span.lap("flatten")
        if pins.active(pins.EXEC_BEGIN):
            for t in grp:
                self._fire_exec(t, pins.EXEC_BEGIN, wave=cnt)
        don = len(program.donate)
        if not don:
            outs = self._dispatch(local_key, entry, flat)
        else:
            # a donating call that raises may have consumed its inputs:
            # no task of the chunk can be run again
            for t in grp:
                t._tpu_effects = True
            try:
                outs = self._dispatch(local_key, entry, flat)
            except Exception as e:
                debug.error("wave of %d x %s with %d donated tiles failed: "
                            "%s", cnt, cls, don, e)
                for t in grp:
                    if not getattr(t.taskpool, "failed", False):
                        self._fail_task_pool(
                            t, f"device program with donated tiles "
                               f"raised: {e!r}")
                    t._tpu_completed = True  # never resubmit
                return {}
        if pins.active(pins.EXEC_END):
            for t in grp:
                self._fire_exec(t, pins.EXEC_END, wave=cnt)
        wave_span.lap("call")
        notes = self._count_values(plan, cnt, flat, len(outs), don)
        notes["batched"] = cnt if batched is not None else 0
        if getattr(body, "_converts", False):
            self._count_converts(staged, outs)
        if len(outs) != nout * cnt:
            raise ValueError(
                f"wave of {cls}: bodies returned "
                f"{len(outs)} outputs for {nout * cnt} writable flows")
        self.stats["wave_submits"] += 1
        self.stats["wave_tasks"] = self.stats.get("wave_tasks", 0) + cnt
        self.stats["wave_tasks_batched"] += notes["batched"]
        wave_span.lap("count")
        self._finish(staged, outs, nout, es, complete)
        return notes

    def _stage_span(self, grp: List[Task], fplan: FlowPlan,
                    pinned: List[Data]) -> Tuple[List[_Staged], set]:
        """:meth:`_stage_chunk` under its ``dev:stage_args`` span."""
        with self._span("dev:stage_args") as sp:
            # host tiles, their bytes, tiles staged, residency hits
            tally = [0, 0, 0, 0]
            staged, refused = self._stage_chunk(grp, fplan, tally, pinned,
                                                sp.lap)
            sp.note(host_tiles=tally[0], bytes=tally[1], tiles=tally[2],
                    hits=tally[3])
            sp.lap("own")
        return staged, refused

    def _finish(self, staged: List[_Staged], outs, nout: int, es,
                complete: bool, *, out_hooks=None, donated: bool = False,
                alone: bool = False) -> None:
        """A program has been enqueued: commit its tasks' outputs now
        (the ``dev:epilog`` span), or — ``tpu_eager_complete=0`` — once
        the chip has run it (:meth:`_poll_deferred`)."""
        with self._span("dev:epilog") as sp:
            if self._eager:
                self._commit_chunk(staged, outs, nout, es, complete, sp,
                                   out_hooks=out_hooks, donated=donated,
                                   alone=alone)
                sp.lap("complete")
                return
            for k, one in enumerate(staged):
                if getattr(one[0].taskpool, "failed", False):
                    continue  # a sibling's failure already took the pool
                self._deferred.append((
                    (one[0], None, one[2]),
                    list(outs[k * nout:(k + 1) * nout]), out_hooks, donated))
                one[0]._tpu_completed = True  # the queue's from here on

    def _stage_chunk(self, grp: List[Task], fplan: FlowPlan,
                     tally: List[int], pinned: List[Data],
                     lap) -> Tuple[List[_Staged], set]:
        """kernel_push (reference device_gpu.c:2015-2164 stage-in
        phase) — THE staging walk: for the tasks of one chunk of a wave,
        or for one task that goes out alone, in ONE pass under ONE hold
        of the residency lock: ``(task, dev_args, out_specs)`` per task,
        by the signature's :class:`FlowPlan`.  A tile that is resident
        and current yields its payload and one LRU touch a chunk; the
        others go into the one coalesced put (``StageIn.batch``; tile
        by tile in the synchronous regime); a flow with a custom
        ``stage_in`` hook gets the hook's result; ownership moves only
        once every tile of the chunk is resident, so an error in here
        raises with no task of the chunk touched.  Every tile of the
        chunk is PINNED as it is found or staged (``pinned`` takes them:
        the caller unpins once the chunk is committed): room for one is
        never made at the expense of another, and a chunk whose tiles do
        not fit the budget together raises (``StageIn.batch``) instead
        of running past it.  ``tally`` counts for
        the ``dev:stage_args`` span: ``[tiles copied from the host,
        their bytes, tiles staged, of them found resident]``; ``lap``
        is that span's: ``walk`` (the loop over the flows), ``put`` (the
        batched put and the arguments it fills), ``sole`` (the donation
        check); the caller closes ``own`` (the ownership moves, the next
        uses, the tally).

        **Donation.**  Where the signature names donated flows
        (``fplan.donates``), the second value returned is the set of
        the chunk's tasks (by their place in it) that may NOT donate
        after all: under the same hold, each such tile's array has to be
        held by this chunk alone (:meth:`_sole_holder`).  What the walk
        stages is the same either way; a refused task goes out under the
        functional program (``donation_refused``)."""
        idx = self.data_index
        res = self._res
        steps = fplan.steps
        staged: List[_Staged] = []
        owns: List[Tuple[Data, int]] = []
        found: Dict[int, Any] = {}  # data_id -> payload on this device
        #: data_id -> [tile, (argument list, position) it still misses in]
        missing: Dict[int, List[Any]] = {}
        donates = fplan.donates
        #: data_id -> how many tile operands of the chunk it is
        reads: Dict[int, int] = {}
        refused: set = set()
        ntiles = nread = nmiss = twins = 0
        converted = self._converted
        #: data_id -> the rank of the tile's next reader after this
        #: chunk, as the tasks' pools know it (``Residency.next_uses``)
        nexts: Dict[int, int] = {}
        with pins.held(res.lock, "res_lock"):
            for task in grp:
                specs = task.body_args
                at = task._tpu_next
                uses = task.taskpool.next_use if at >= 0 else None
                args: List[Any] = []
                ospecs: List[Tuple[int, Data]] = []
                mine: List[Data] = []  # scratch tiles: one user each
                for how, pos, access, extra in steps:
                    if how == VALUE:
                        args.append(specs[pos][1])
                        continue
                    if how == SCRATCH:
                        args.append(jnp.zeros(extra[0], extra[1],
                                              device=self.jdev))
                        continue
                    if how == ABSENT:
                        args.append(None)
                        continue
                    data = specs[pos][1]
                    if data.scratch is not None:
                        mine.append(data)
                    if how == READ:
                        nread += 1
                        did = data.data_id
                        if donates:
                            reads[did] = reads.get(did, 0) + 1
                        if converted and did in converted:
                            twins += 1
                        if uses is not None:
                            use = uses[at + pos]
                            if use > nexts.get(did, -1):
                                nexts[did] = use
                        arr = found.get(did)
                        if arr is None:
                            c = data.current_copy(idx)
                            if c is not None:
                                arr = found[did] = c.payload
                                res.touch(
                                    data,
                                    dirty=c.coherency is Coherency.OWNED)
                                res.pin(data)
                                pinned.append(data)
                            else:
                                slot = missing.get(did)
                                if slot is None:
                                    slot = missing[did] = [data]
                                slot.append((args, len(args)))
                    elif how == PLACEHOLDER:
                        # a NEW flow's tile nobody has written, or a
                        # write-only flow the body overwrites (reference
                        # skips stage-in for OUT-only flows): nothing to
                        # stage and no argument of the program — the plan
                        # gives the body zeros inside the trace
                        arr = extra
                    elif how == HOOKED:
                        # custom staging: the hook's result IS the flow's
                        # device copy (pack/convert — reference
                        # stage_custom)
                        arr = self._h2d.custom(data, extra, self)
                    elif how == LATE:
                        # no shape to make a placeholder of: the tile
                        # itself, staged
                        arr = self._h2d.one(data)
                    else:  # UNPAIRED
                        raise RuntimeError(
                            f"{task!r}: stage_in on writable flow requires "
                            "a matching stage_out hook")
                    args.append(arr)
                    ntiles += 1
                    owns.append((data, access))
                    if access & _OUT:
                        ospecs.append((pos, data))
                staged.append((task, args, ospecs))
                task._tpu_scratch = mine
            lap("walk")
            if missing:
                tiles = [slot[0] for slot in missing.values()]
                # the chunk's host->device transfers as one batched put
                # (a put a tile in the synchronous regime), the room for
                # all of them made at once
                self._h2d.batch(tiles, tally, got=found, keep=pinned,
                                coalesce=self.stage_depth > 1)
                for did, slot in missing.items():
                    for args, at in slot[1:]:
                        args[at] = found[did]
                    nmiss += len(slot) - 1
            lap("put")
            if donates:
                refused = self._not_sole(staged, donates, reads)
            lap("sole")
            for data, access in owns:
                data.transfer_ownership(idx, access)
            if nexts:
                res.next_uses(nexts)
        tally[2] += ntiles
        tally[3] += nread - nmiss
        if twins:
            self.stats["convert_shared_hits"] += twins
        return staged, refused

    def _not_sole(self, staged: List[_Staged], donates,
                  reads: Dict[int, int]) -> set:
        """The tasks of a staged chunk (by their place in it) one of
        whose donated tiles somebody else holds (the caller holds the
        residency lock, and the chunk's pins are taken).  The task's
        builder said that no other TASK reads the version; what only
        this device can see is who else holds the ARRAY: the tile is an
        operand of the chunk's program more than once (``reads``: data_id
        -> times); it is pinned by more than this chunk and the lane's
        stage-in for this very batch; an eviction's victim on its way
        home with the lock free (``Residency.going_home``: taken back by
        this walk, its copy home still runs through an alias of the
        array); queued with the committer or in one of its drains (a
        last version started early among them); or the payload of
        another copy of the tile too (a device-resident arrival is
        attached as it came)."""
        res, idx = self._res, self.data_index
        pins_, going, ahead = res.pins, res.going_home, self._ahead_ids
        com = self._committer
        busy = com.holding(
            [task.body_args[pos][1].data_id
             for (task, _a, _o) in staged for (pos, _ai, _oi) in donates]) \
            if com is not None else ()
        refused = set()
        peers = bool(self.peers)
        for k, (task, args, _ospecs) in enumerate(staged):
            specs = task.body_args
            claimed: List[Data] = []
            for pos, ai, _oi in donates:
                data = specs[pos][1]
                did = data.data_id
                arr = args[ai]
                if reads[did] == 1 and did not in going \
                        and did not in busy \
                        and pins_.get(did, 0) == 1 + (did in ahead) \
                        and not any(c.payload is arr
                                    for di, c in data.copies.items()
                                    if di != idx):
                    if not peers:
                        continue
                    # a peer module's landing of this array may be
                    # between its read of the reference and its enqueue:
                    # the tile's own lock decides, once, who was first
                    if data.claim_for_donation():
                        claimed.append(data)
                        continue
                    self.stats["peer_holds_refused"] += 1
                for data in claimed:
                    data.donation_committed()
                refused.add(k)
                break
        self.stats["donation_refused"] += len(refused)
        return refused

    def _submit(self, task: Task, es=None, complete: bool = True,
                span=None) -> None:
        """Stage + body dispatch of a task that goes out alone
        (reference device_gpu.c:2015-2164); ``span`` is the caller's
        ``dev:submit_one``."""
        sig = self._signature_of(task)
        if sig is None:
            # DTD/PTG store the raw device body on the chore at build time
            raise RuntimeError(f"chore of {task!r} has no body_fn for device execution")
        body, fplan = task.selected_chore.body_fn, sig[1]
        pinned: List[Data] = []
        try:
            self._run_one(task, body, fplan, es, complete, span, pinned)
        finally:
            self._res.unpin(pinned)

    def _run_one(self, task: Task, body, fplan: FlowPlan, es,
                 complete: bool, span, pinned: List[Data]) -> None:
        """:meth:`_submit` between the pins (as :meth:`_run_chunk`)."""
        staged, refused = self._stage_span([task], fplan, pinned)
        lap = span.lap if span is not None else _no_lap
        lap("stage")
        dev_args = staged[0][1]

        base_key = getattr(body, "_jit_key", body)
        # opt-in body attributes (set by the DSL body author):
        #   _static_values — bake the task's VALUE args (its locals) into
        #     the traced program as Python constants, one compile per
        #     distinct value tuple: the per-parameter specialization that
        #     lets a body use exact static shapes (slices sized by k).
        #     The analogue of jdf2c's parameter-specialised generated code.
        #   _donate_args — donate these positional array args to XLA so
        #     in-place updates alias instead of allocating (a whole-matrix
        #     INOUT flow would otherwise hold one fresh HBM buffer per
        #     enqueued async step).  The BODY's author vouches for these
        #     (and no output of such a program goes home: below).  Every
        #     other body donates what its signature names
        #     (``fplan.donates``: the read-write flows whose input version
        #     the task's builder knows nobody else reads, checked by the
        #     staging walk), as a wave program of it does.
        donate = tuple(getattr(body, "_donate_args", ()) or ())
        by_body = bool(donate)
        if donate and getattr(self.context, "nranks", 1) > 1:
            # device-capable fabrics ship jax.Arrays UNCOPIED across
            # ranks (comm/payload.py): donating a buffer a peer may still
            # read would invalidate it under them.  Until donation is
            # remote-successor-aware, multirank runs fall back to
            # functional (non-aliasing) execution.
            donate = ()
        if getattr(body, "_static_values", False):
            # only arg-contributing kinds count ("ctl" adds no dev_arg)
            specs = [s[0] for s in (task.body_args or ())
                     if s[0] in ("data", "value", "scratch")]
            nval = specs.count("value")
            if nval and "value" in specs[:len(specs) - nval]:
                # PTG orders flows-then-values; DTD interleaves user args —
                # a suffix split would bake the WRONG args into the trace
                raise RuntimeError(
                    f"_static_values body of {task!r}: value args must "
                    "trail all data args (PTG layout); this task "
                    f"interleaves them ({specs})")
            split = len(dev_args) - nval
            # no plan on this path: a placeholder becomes zeros on the
            # device (created ON this rank's device, not the default one)
            call_args = [jnp.zeros(a.shape, a.dtype, device=self.jdev)
                         if isinstance(a, jax.ShapeDtypeStruct) else a
                         for a in dev_args[:split]]
            vals = tuple(dev_args[split:])

            def _bound(*arrs, _body=body, _vals=vals):
                return _body(*arrs, *_vals)
            local_key = (base_key, vals)
            entry = self._cached_jit(
                local_key,
                lambda: (("static", self._content_fp(body), vals),
                         _bound, donate, None))
            lap("key")  # (no plan: nothing to flatten)
        else:
            fused_n = int(getattr(body, "_fused_n", 0) or 0)
            if fused_n > 1:
                self.stats["fused_submits"] = \
                    self.stats.get("fused_submits", 0) + 1
                self.stats["fused_tasks"] = \
                    self.stats.get("fused_tasks", 0) + fused_n
                task.prof["fused_n"] = fused_n
                from ..profiling import sde

                sde.counter_add(sde.FUSION_REGIONS_DISPATCHED, 1)
                sde.counter_add(sde.FUSION_TASKS_FUSED, fused_n)
                sde.counter_add(sde.FUSION_DISPATCH_SAVED, fused_n - 1)

            def build():
                plan = self._value_plan(task, body, dev_args)

                def _one(*flat):
                    args, = plan.bodies_args(flat, 1)
                    return body(*args)
                _one.__name__ = getattr(body, "__name__", "_one")
                # fused supertasks carry an explicit content key (member
                # body fingerprints + region shape, dsl.fusion.FusedPlan.
                # digest): fingerprinting the program CLOSURE would hash
                # plan structures instead of member code, so the override
                # is the cross-process cache identity
                content_key = getattr(body, "_content_key", None) \
                    or ("body", self._content_fp(body))
                # a body with no scalar value is its own program, under
                # its own name, as it always was
                return (content_key + plan.tag, _one if plan.tag else body,
                        plan.donate(donate if by_body else
                                    plan.aliased(dev_args, donates)), plan)
            donates = () if by_body or refused else fplan.donates
            local_key = (base_key, argsig(dev_args),
                         _placeholders_at(dev_args), donates)
            entry = self._cached_jit(local_key, build)
            lap("key")
            call_args = entry[1].flatten((dev_args,))
            lap("flatten")
        # a donating call that raises may have invalidated its input
        # buffers: the task is no longer safely retryable
        don = len(entry[0].donate)
        task._tpu_effects = bool(don)
        self._fire_exec(task, pins.EXEC_BEGIN)
        outputs = self._dispatch(local_key, entry, call_args)
        self._fire_exec(task, pins.EXEC_END)
        lap("call")
        plan = entry[1]  # (None: a ``_static_values`` program)
        if plan is not None:
            notes = self._count_values(plan, 1, call_args, fplan.nout, don)
            if span is not None:
                span.note(**notes)
        if not isinstance(outputs, (tuple, list)):
            outputs = (outputs,)
        if getattr(body, "_converts", False):
            self._count_converts(staged, outputs)
        if len(outputs) != fplan.nout:
            raise ValueError(
                f"device body of {task!r} returned {len(outputs)} outputs "
                f"for {fplan.nout} writable flows")
        lap("count")
        # NOT sent home, a donating program's outputs: the successor of
        # an in-place chain consumes this very buffer, so an eager get
        # either stalls the chain behind a device->host copy of every
        # intermediate version (on the chip: 4 GiB per panel step of the
        # N=32768 segmented dpotrf, 15 s each) or loses the race and
        # reads a deleted array.  Such tiles stay dirty-resident;
        # detach/flush/eviction carry the final version home through the
        # synchronous guarded path.
        self._finish(staged, list(outputs), fplan.nout, es, complete,
                     out_hooks=fplan.out_hooks,
                     donated=by_body and bool(donate),
                     alone=True)

    # ------------------------------------------------------------------
    # async staging pipeline: prefetch lane + batched puts
    # ------------------------------------------------------------------
    def prestage_tiles(self, tasks: List[Task]) -> Tuple[List[Data], int]:
        """The pump's look ahead at a ready batch: the tiles a prestage
        of ``tasks`` would move — read by one of them and not current on
        this device — and their bytes.  A batch with none has nothing
        for the transfer lane; the bytes are the pump's intra-wave split
        heuristic (re-slicing a ready batch across the prefetch window
        only pays when there is real transfer work to hide).  One pass
        by the tasks' plans, without the residency lock: a stale read
        merely mis-sizes the hint, and the submit path stages what the
        lane did not."""
        idx = self.data_index
        seen = set()
        moving: List[Data] = []
        nbytes = 0
        for task in tasks:
            sig = self._signature_of(task)
            if sig is None:
                continue
            specs = task.body_args
            for pos in sig[1].reads:
                data = specs[pos][1]
                if data.data_id in seen:
                    continue
                seen.add(data.data_id)
                if data.current_copy(idx) is not None:
                    continue  # residency hit: no transfer
                newest = data.newest_copy()
                if newest is None or newest.payload is None:
                    continue
                moving.append(data)
                nbytes += int(getattr(newest.payload, "nbytes", 0))
        return moving, nbytes

    def prestage_batch(self, tasks: List[Task], batch_no: int,
                       tiles: List[Data]) -> None:
        """Transfer-lane half of the double-buffered pipeline: stage the
        NEXT ready batch's input tiles (``tiles``: what
        :meth:`prestage_tiles` found missing) while the current wave
        computes, so the pump's submit pass reuse-hits them.  A
        ``dev:stage_in`` span (critpath's transfer bucket; ``batch`` is
        the pump's number for the batch) and publishes the lane's clock
        into each task's hb token — stage_in happens-before exec."""
        if tasks:  # the lane runs ahead of the batch's own submit
            self._span_pool = _pool_of(tasks[0])
        with self._span("dev:stage_in", id=span_id(),
                        tiles=len(tiles), batch=batch_no) as sp:
            # a batch with nothing to move: the span, for whoever reads
            # the lane by batch, and neither the lock nor a walk
            moved = 0
            if tiles:
                # what the lane stages stays pinned until its batch is
                # submitted, so it stages no more than a quarter of the
                # budget ahead (the submit path stages the rest, a chunk
                # at a time)
                room, ahead = self._res.budget // 4, []
                for k, data in enumerate(tiles):
                    room -= int(getattr(data.newest_copy().payload,
                                        "nbytes", 0))
                    if room < 0:
                        tiles = tiles[:k]
                        break
                # (the one caller that comes without the residency
                # lock: an eviction for this room lets go of it while
                # its victims go home)
                moved = self._h2d.batch(tiles, keep=ahead, unlocked=True)
                self._prestaged[batch_no] = ahead
            sp.note(bytes=moved)
        if not tiles:
            return
        self.stats["prefetched_tiles"] = \
            self.stats.get("prefetched_tiles", 0) + len(tiles)
        if pins.active(pins.HB_STAGE_IN):
            for task in tasks:
                pins.fire(pins.HB_STAGE_IN, None, {"task": task})

    def _wb_committer(self):
        """The async write-back committer, armed lazily when the
        pipeline is on (``runtime_stage_depth`` >= 2); None in the
        synchronous regime."""
        if self.stage_depth <= 1:
            return None
        com = self._committer
        if com is None:
            com = self._committer = WritebackCommitter(self._wb)
        return com

    def flush(self, timeout: float = 300.0) -> None:
        """Hard write-back barrier: drain every deferred device->host
        commit (or re-raise the committer's sticky error).  Detach calls
        this implicitly; call it directly when host tiles must be
        current while the device stays attached — e.g. between a
        standalone ``NativeExecutor`` run and a host-side read of the
        raw tile copies.  A no-op in the synchronous regime."""
        com = self._committer
        if com is not None:
            with self._span("dev:flush"):
                com.flush(timeout=timeout)

    def flush_home(self, datas: List[Data], wait: bool = True) -> None:
        """``Device.flush_home``: the dirty copies of ``datas`` here are
        last versions that nobody sent home when they were committed (a
        DTD task's outputs: ``Task._tpu_home = ()``).  They go the way
        the pump's last versions go (``WritebackCommitter.enqueue_all``,
        ``last=True``): every copy home started now, before one is
        waited for (``wait:d2h_start``), the committer collecting them a
        batch at a time (``dev:writeback``), and with ``wait`` the
        barrier of :meth:`flush` behind them.  One guarded batch on the
        calling thread in the synchronous regime."""
        com = self._wb_committer()
        if com is None:
            self._wb.writeback_batch(datas, self._span_pool,
                                     self._span_batch)
            return
        com.enqueue_all(datas, self._span_pool, self._span_batch, last=True)
        if wait:
            self.flush()

    def _writeback_evict(self, victims: List[Data]) -> int:
        """Eviction's write-back (``Residency``'s callable): the victims
        whose copy here is the only valid one, as ONE batch on the
        thread that needs the room — every copy home started before one
        is waited for, one wait, guarded commits
        (``HostWriter.writeback_batch``, its ``dev:writeback`` span
        inside the eviction's ``dev:evict``).  Not through the committer:
        it would take the same copies one drain at a time while this
        thread waited for each tile in turn, and the version guard makes
        a copy the committer also holds (a last version on its way) land
        once whoever is first.  The caller may or may not hold the
        residency lock (``residency.py``, "An eviction and the lock":
        the lane's eviction comes without it, so that nobody waits
        behind these copies; a walk that holds it took it with
        ``pins.held(res.lock, "res_lock")``, so that whoever does wait
        leaves a ``wait:res_lock`` event naming this eviction as the
        holder): what keeps a victim from being dropped before it is
        home, or after somebody rewrote it, is the residency's own
        check at the drop.  Returns the microseconds waited."""
        t0 = time.perf_counter_ns()
        self._res.owed_home(victims)
        self._wb.writeback_batch(victims, self._span_pool, self._span_batch)
        return (time.perf_counter_ns() - t0) // 1000

    # ------------------------------------------------------------------
    # completion / stage_out / epilog
    # ------------------------------------------------------------------
    def _poll_deferred(self, es) -> bool:
        """Retire completed computations, in submit order (reference
        per-stream event polling; one stream here)."""
        progressed = False
        queue = self._deferred
        while queue:
            staged, outputs, out_hooks, donated = queue[0]
            try:
                if not all(o.is_ready() for o in outputs):
                    break
            except Exception as e:
                # the async computation itself died (device error
                # surfacing at poll): the task must NOT complete —
                # successors would consume garbage.  Fail the pool loudly.
                debug.error("tpu deferred retirement failed: %s", e)
                queue.popleft()
                self._fail_task_pool(
                    staged[0], f"device lane retirement raised: {e!r}")
                progressed = True
                continue
            queue.popleft()
            # (a commit that cannot land its outputs fails the pool and
            # completes nothing, by _commit_chunk's discipline)
            self._commit_chunk([staged], outputs, len(outputs), es, True,
                               None, out_hooks=out_hooks, donated=donated,
                               alone=True)
            progressed = True
        return progressed

    def _commit_output(self, data: Data, arr, nbytes: int,
                       bumps_heard: bool) -> None:
        """One output of one task, committed (the caller holds the
        residency lock): rebind the device copy, account its residency,
        bump the version, keep the tile resident and dirty (reference
        kernel_epilog device_gpu.c:2343 — data stays OWNED on device;
        host pulls on demand)."""
        idx = self.data_index
        if data.scratch is not None and scratch.unborn(data):
            self.stats["scratch_tiles_born"] += 1
            if data.scratch != scratch.KEPT:  # (a kept tile never dies)
                self._scratch_live += scratch.nbytes(data)
                if self._scratch_live > self.stats["scratch_bytes_peak"]:
                    self.stats["scratch_bytes_peak"] = self._scratch_live
        c = data.get_copy(idx)
        if c is None:
            c = data.attach_copy(idx, arr)
        else:
            c.payload = arr
        # the committed value is HOME-layout (stage_out already
        # unpacked): a packed stage_in marker must not survive it
        c.staged_by = None
        self._res.account(data, nbytes)
        data.version_bump(idx, bumps_heard)
        if data.peer_holds < 0:  # (claimed for this program's donation)
            data.donation_committed()
        self._res.touch(data, dirty=True)

    def _supersede(self, staged: List[_Staged]) -> None:
        """The chunk's outputs are committed here: what the peer modules
        hold of those tiles is an older version now and is dropped there
        (``Residency.drop_stale``: accounting freed, nothing written
        home).  With no lock of this module held: a peer's commit may be
        doing the same the other way."""
        wrote = [data for (_t, _a, ospecs) in staged for (_p, data) in ospecs]
        for peer in self.peers:
            theirs = [d for d in wrote if peer.data_index in d.copies]
            if theirs:
                peer._res.drop_stale(theirs)

    def _release_scratch(self, tiles, after) -> None:
        """A task that was one declared user of each of these scratch
        tiles has committed (the caller holds the residency lock): with
        the last user the tile's copy here is dropped.  Its program,
        enqueued, keeps the buffer until it has run: ``after``, an
        output of it, is how the accounting learns when."""
        for data in tiles:
            if scratch.release(data):
                self._res.release(data, after)
                self.stats["scratch_tiles_freed"] += 1
                self._scratch_live -= scratch.nbytes(data)

    def _send_home(self, going: List[Data], last: bool) -> int:
        """Hand just-committed outputs to the async committer OUTSIDE
        the residency lock (its capacity wait must not stall
        residency), in one call.  The committer
        dedups per data_id and drains on its byte watermark, so a tile
        rewritten by a later task commits its FINAL version once; the
        version guard drops anything superseded in flight.  A sticky
        committer error re-raises here and propagates to the caller's
        _fail_task_pool discipline: pool failure, not a hang.
        The callers leave out a scratch tile (it has no home to go to)
        and, where the task's builder knows the DAG (``_tpu_home``), a
        version that a later task overwrites; ``last`` says that is so
        of every task here, so ``going`` holds last versions only: their
        copies home start now, behind the programs that write them, and
        the committer collects them below its watermark
        (``WritebackCommitter.enqueue_all``).  Returns the number handed
        over."""
        com = self._wb_committer()
        if com is None or not going:
            return 0
        self._res.owed_home(going)
        com.enqueue_all(going, self._span_pool, self._span_batch, last=last)
        return len(going)

    def _commit_chunk(self, staged: List[_Staged], outs, nout: int, es,
                      complete: bool, sp, *, out_hooks=None,
                      donated: bool = False, alone: bool = False) -> None:
        """THE commit — of one chunk of a wave, or of one task that went
        out alone (``alone``: counted in ``task_commits``, not
        ``wave_commits``): a flow's custom ``stage_out`` hook transforms
        the body's output first (``out_hooks``, one a task output;
        scatter a packed subtile back — reference stage_custom.jdf);
        then ONE hold of the residency lock commits every output of
        every task (``_commit_output``) and releases the scratch tiles
        whose last user was here; outside the lock the outputs that go
        home reach the committer in ONE call (none of a ``donated``
        program: see ``_submit``); then, and only then, the tasks
        complete, in order (a task's outputs are committed before its
        ``complete_execution``).  The tools' sites fire as they did — an
        epilog a task, a bump an output, a ticket an enqueue — asked once
        a chunk whether anybody listens.  ``sp``: the ``dev:epilog``
        span, where there is one; its laps are ``hooks``, ``commit``
        (every task's outputs and scratch tiles, ONE loop: a task's
        scratch tiles are let go before the next task's outputs are
        counted, which is what ``scratch_bytes_peak`` reads), ``settle``,
        ``home``, ``zeros``, and ``complete``, which the caller closes.

        Once the commit has begun nothing here may raise: an error fails
        the pool loudly and marks the chunk's tasks completed, so that
        the per-task fallback neither retries (double-apply) nor hangs."""
        from ..core import scheduling

        # happens-before join point: the manager thread is about to
        # commit these tasks' outputs (version bumps) — hb-check must
        # order them after each task's exec, which may have run on a
        # different (worker) thread (analysis/hb.py)
        epilogs_heard = pins.active(pins.DEVICE_EPILOG_BEGIN)
        bumps_heard = pins.active(pins.DATA_VERSION_BUMP)
        res = self._res
        going: List[Data] = []
        #: the outputs the body says are exact zeros whatever it is given
        #: (``_zeros``: positions among its outputs), and of them the
        #: last versions of tiles with a home: zeros are landed there as
        #: they are, no copy from the chip is started or collected
        zeros = getattr(staged[0][0].selected_chore.body_fn, "_zeros", ())
        blank: List[Data] = []
        #: data_id -> the rank of an output's next reader, as the
        #: tasks' pools know it (``Residency.next_uses``)
        nexts: Dict[int, int] = {}
        #: every task here knows which of its outputs are last versions
        last = True
        done: List[Task] = []
        #: an output of the program, as it returned it: ready when the
        #: chip has run it
        after = outs[0] if len(outs) else None
        lap = sp.lap if sp is not None else _no_lap
        try:
            with pins.held(res.lock, "res_lock"):
                if out_hooks is not None:
                    outs = list(outs)
                    for k, so in enumerate(out_hooks * len(staged)):
                        if so is not None:
                            # commit to THIS device: a hook building from
                            # host data would otherwise land on the
                            # process default device
                            data = staged[k // nout][2][k % nout][1]
                            outs[k] = jax.device_put(
                                so(outs[k], data, self), self.jdev)
                            self.stats["custom_stage_out"] += 1
                lap("hooks")
                # the k-th output of every task has one shape: its bytes,
                # once
                sizes = [o.nbytes for o in outs[:nout]]
                for k, (task, _args, ospecs) in enumerate(staged):
                    if getattr(task.taskpool, "failed", False):
                        continue  # a sibling's failure already took the pool
                    # the commit mutates output tiles one by one (rebind
                    # + version bump): once entered, a retry would
                    # double-apply
                    task._tpu_effects = True
                    if epilogs_heard:
                        pins.fire(pins.DEVICE_EPILOG_BEGIN, None, task)
                    home = task._tpu_home
                    nx = task._tpu_next
                    uses = task.taskpool.next_use if nx >= 0 else None
                    at = k * nout
                    for j, (pos, data) in enumerate(ospecs):
                        self._commit_output(data, outs[at + j], sizes[j],
                                            bumps_heard)
                        if uses is not None:
                            # (the new version's first reader)
                            nexts[data.data_id] = uses[nx + pos]
                        if data.scratch is None \
                                and (home is None or pos in home):
                            (blank if home is not None and j in zeros
                             else going).append(data)
                    if home is None:
                        last = False
                        self.stats["commits_home_unknown"] += 1
                    if task._tpu_donate is None:
                        self.stats["commits_donate_unknown"] += 1
                    if task._tpu_scratch:
                        self._release_scratch(task._tpu_scratch, after)
                    done.append(task)
                lap("commit")
                if nexts:
                    res.next_uses(nexts)
                # outputs grew residency: re-settle under the budget
                res.settle()
            self.stats["task_commits" if alone else "wave_commits"] += 1
            if self.peers:
                self._supersede(staged)
            lap("settle")
            home = 0 if donated else self._send_home(going, last)
            lap("home")
            if blank and not donated:
                self._wb.land_zeros(blank)
            lap("zeros")
            if sp is not None:
                sp.note(n=len(done), outs=len(done) * nout, home=home,
                        blank=len(blank))
        except Exception as e:
            debug.error("device epilog of %d x %r failed: %s",
                        len(staged), staged[0][0].task_class.name, e)
            for (task, _args, _o) in staged:
                if not getattr(task.taskpool, "failed", False):
                    self._fail_task_pool(
                        task, f"device epilog/completion raised: {e!r}")
                task._tpu_completed = True  # never resubmit
            return
        for task in done:
            task._tpu_completed = True
            if not complete or getattr(task.taskpool, "failed", False):
                continue
            try:
                scheduling.complete_execution(self.context, es, task)
            except Exception as e:
                debug.error("device completion of %r failed: %s", task, e)
                self._fail_task_pool(
                    task, f"device epilog/completion raised: {e!r}")

    # ------------------------------------------------------------------
    def data_advise(self, data: Data, advice: int) -> None:
        """Reference device.h:76-78: PREFETCH stages the newest version
        into HBM ahead of first use (charged as a normal stage-in, LRU
        clean); WARMUP re-touches a resident copy so eviction passes it
        over; PREFERRED_DEVICE pins the selector (base class)."""
        from .device import ADVICE_PREFETCH, ADVICE_WARMUP

        if advice in (ADVICE_PREFETCH, ADVICE_WARMUP):
            # residency (LRU/HBM accounting) is otherwise mutated only by
            # the single active manager thread; holding _lock here keeps
            # would-be managers out (kernel_scheduler's enqueue takes it),
            # and an already-active manager means the device is busy — a
            # hint may simply be dropped then (tiles stage on demand)
            with self._lock:
                if self._manager_active:
                    return
                if advice == ADVICE_WARMUP:
                    self._res.warm(data)
                elif data.newest_copy() is not None:
                    # (nothing materialized yet: a hint, not a command)
                    self._h2d.one(data)
        else:
            super().data_advise(data, advice)

    def pool_boundary(self, handed: Iterable[Data],
                      owed: Iterable[Data]) -> Tuple[int, int]:
        """Between two pools that run one after the other over this
        device's residency (a compound's members,
        ``NativeExecutor._run_members``; ``(), ()`` before the first and
        after the last).  ``handed``: the tiles that the pools which have
        ended wrote and a pool still to come names.  Nothing is done to
        them: a dirty tile whose pool has ended stays resident and the
        newest version, the next pool's staging walk finds it and may
        donate it, as it would its own pool's.  Returns how many of them
        are so, and their bytes; from now on one that is staged in from
        the host counts in ``stats["handed_restaged"]``.  ``owed``: the
        tiles that a pool still to come rewrites; their tasks were bound
        with no home for them, and the bytes of one that goes home all
        the same (an eviction's victim) count in
        ``stats["owed_home_bytes"]``."""
        res = self._res
        handed = list(handed)
        with pins.held(res.lock, "res_lock"):
            res.handed = frozenset(d.data_id for d in handed)
            res.owed = frozenset(d.data_id for d in owed)
            here = [n for n in map(res.resident_bytes, handed) if n]
        return len(here), sum(here)

    def drop_residency(self, data: Data) -> None:
        """Release ``data``'s residency slot WITHOUT a host write-back:
        ownership of the device array passes to the caller (who already
        holds the payload).  The counterpart of the reference's
        data_advise release path for benchmark/driver code that reads a
        result and hands the buffer on — without this, every completed
        run's output stays dirty-resident until LRU pressure forces a
        full D2H write-back."""
        with self._lock:
            self._res.release(data)  # handed over, not evicted

    # ------------------------------------------------------------------
    def resident_data(self, task: Task) -> int:
        return sum(self._res.resident_bytes(spec[1])
                   for spec in task.body_args or ()
                   if spec[0] == "data" and spec[1] is not None)

    def detach(self) -> None:
        with self._span("dev:detach"):
            self._detach()

    def _detach(self) -> None:
        if self._thread is not None:
            self._stop = True
            self._wake.set()
            self._thread.join(timeout=30)
            self._thread = None
        # drain the async committer FIRST: its flush() barrier is what
        # lets host-side readers (detach, redistribute, remote sends)
        # see committed tiles.  A committer that died mid-run surfaces
        # HERE, loudly — and is discarded so a shared device (the
        # `device=` amortization pattern) gets a fresh one next run.
        com = self._committer
        if com is not None:
            try:
                com.flush()
            except Exception:
                self._committer = None
                raise
            com.close(flush=False)
            self._committer = None
        with pins.held(self._res.lock, "res_lock"):
            # flush remaining dirty tiles home as ONE batched device->host
            # get — the version guard makes tiles the
            # committer already landed a no-op, so each dirty tile
            # commits exactly once
            # (a scratch tile has no home: it is dropped, never copied)
            _n, got = self._wb.writeback_batch(
                [d for d in self._res.dirty.values() if d.scratch is None],
                self._span_pool, self._span_batch)
            if got:
                self.stats["wb_batches"] = self.stats.get("wb_batches", 0) + 1
            # the LRUs and the residency ACCOUNTING go together
            self._res.clear()
        self._converted.clear()
        self._sigs_seen.clear()
