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
* **streams as async lanes** — JAX dispatch is asynchronous; in-flight
  computations are tracked in per-lane in-order queues polled for
  completion via ``jax.Array.is_ready()``, mirroring the per-stream event
  queues (``parsec_device_progress_stream``, ``device_gpu.c:1879-1999``).

Departures from the reference, by TPU design:
* no device pointers — payloads are ``jax.Array``s; "allocation" is
  ``device_put`` and "free" is dropping the reference;
* task bodies are **functional**: a TPU chore body maps input arrays to
  fresh output arrays (XLA semantics), instead of mutating tile memory;
  outputs rebind the device copies of writable flows in declaration order;
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
from typing import Any, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.lifecycle import AccessMode, HookReturn, DEV_TPU
from ..core.task import Task
from ..profiling import pins
from ..utils import debug, mca_param, register_component
from ..compile_cache import argsig
from ..data.data import Coherency, Data, DataCopy
from . import scratch
from .device import Device
from .value_args import (ABSENT, PLACEHOLDER, SCRATCH, VALUE, FlowPlan,
                         ValuePlan)


def _unalias(arr, x, guard, jdev):
    """Rerun a host->device transfer from a throwaway copy when the
    result aliases ``guard`` (shared by :func:`private_device_put` and
    the batched stage-in path — the guard contract must be identical
    whether a tile travelled alone or coalesced)."""
    plat = getattr(jdev, "platform", None)
    if plat is None:
        try:
            plat = arr.devices().pop().platform
        except Exception:
            plat = "cpu"  # unknown: err on the safe side
    if plat != "cpu":
        return arr
    try:
        if np.shares_memory(np.asarray(arr), guard):
            priv = np.array(np.asarray(x), copy=True)
            arr = jax.device_put(priv, jdev) if jdev is not None \
                else jnp.asarray(priv)
    except Exception:
        pass
    return arr


def private_device_put(x, jdev=None, *, guard=None):
    """``jax.device_put`` whose result is guaranteed NOT to alias
    ``guard`` (a host numpy array someone retains).  On the CPU backend
    PJRT zero-copies suitably-aligned host buffers, so a DONATED
    execution of the transferred array writes straight through the
    retained memory — the caller's reference matrix, or a version-v
    host copy whose bytes must outlive the bump to v+1.  Whether a
    given buffer zero-copies depends on its heap alignment, which makes
    the clobber a per-allocation coin flip (seen as a suite flake:
    the LU reconstruct test intermittently compared against its own
    overwritten input).  When aliasing is detected the transfer reruns
    from a throwaway copy — the only memory jax then aliases is
    jax-private.  Non-CPU platforms always copy host→HBM; the check is
    skipped there (``np.asarray`` on such arrays would be a D2H pull)."""
    arr = jax.device_put(x, jdev) if jdev is not None else jnp.asarray(x)
    if guard is None:
        return arr
    return _unalias(arr, x, guard, jdev)


_OUT = int(AccessMode.OUT)


def _placeholders_at(dev_args) -> Tuple[int, ...]:
    """Positions of a staged argument list that stand for a tile there
    was nothing to stage for (``TpuDevice._placeholder``): part of a
    program's local key, since ``argsig`` reads such a stand-in as the
    array it is not."""
    return tuple(i for i, a in enumerate(dev_args)
                 if isinstance(a, jax.ShapeDtypeStruct))


def _pool_of(task: Task) -> int:
    """The ``pool`` a span of ``task`` carries: its taskpool's id (0 for
    a stand-in pool that has none)."""
    return getattr(task.taskpool, "taskpool_id", 0)


#: one task of a wave chunk as ``_stage_chunk`` leaves it: the task, its
#: staged argument list, its ``(position in body_args, tile)`` outputs
_Staged = Tuple[Task, List[Any], List[Tuple[int, Data]]]


class _InFlight:
    """One submitted computation: outputs pending on a lane (the analogue
    of a recorded stream event)."""

    __slots__ = ("task", "outputs", "out_specs", "out_hooks", "host_inputs",
                 "donated")

    def __init__(self, task: Task, outputs: List[Any],
                 out_specs: List[Tuple[int, Any]],
                 out_hooks: Optional[List[Any]] = None,
                 donated: bool = False):
        self.task = task
        self.outputs = outputs
        self.out_specs = out_specs  # (flow position in body_args, Data)
        #: per-output custom stage_out hooks (None = default commit)
        self.out_hooks = out_hooks or [None] * len(out_specs)
        #: the program aliased its outputs onto donated inputs: an
        #: in-place chain whose successor will consume these buffers
        self.donated = donated

    def ready(self) -> bool:
        return all(o.is_ready() for o in self.outputs)


@register_component("device")
class TpuDevice(Device):
    """One JAX device (TPU chip; CPU backend in tests) as a task executor."""

    mca_name = "tpu"
    mca_priority = 50
    device_type = DEV_TPU

    @classmethod
    def available(cls) -> bool:
        # a backend JAX was told to use and cannot initialize RAISES
        # here: skipping the module would silently run every device
        # chore on the host
        return len(jax.local_devices()) > 0

    def __init__(self, context, index):
        super().__init__(context, index)
        #: "a fallback ran" counters — each is a slower path taken in
        #: place of the intended one, 0 on a healthy run
        self.stats.update(wave_fallbacks=0, submit_retries=0,
                          stage_batch_fallbacks=0)
        #: tasks' value arguments by what became of them
        #: (device/value_args.py)
        self.stats.update(value_args_dropped=0, value_args_packed=0,
                          value_args_positional=0, tile_args_dropped=0)
        #: scratch tiles (device/scratch.py): first written / dropped
        #: with their last user on this device, and the bytes of them
        #: that crossed the host after all (0 unless one was evicted or
        #: a CPU body wrote it)
        self.stats.update(scratch_tiles_born=0, scratch_tiles_freed=0,
                          scratch_bytes_in=0, scratch_bytes_out=0)
        #: who committed the outputs: chunks by the wave epilog, tasks
        #: one by one (together: ``executed_tasks``); and the pump's
        #: batches whose tiles were all resident, with nothing in them
        #: for the transfer lane to move
        self.stats.update(wave_commits=0, task_commits=0,
                          prestage_skipped=0)
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
        jidx = pref if pref >= 0 else getattr(context, "rank", 0)
        self.jdev = devs[jidx % len(devs)]
        # budget: 85% of what PJRT says the chip has.  The CPU backend
        # reports no limit and gets a nominal 4 GiB; a TPU that reports
        # none is an error — eviction would be steered by a made-up size
        budget = mca_param.register(
            "device", "tpu_hbm_budget_mb", 0,
            help="HBM bytes (MB) managed for resident tiles (0=auto)")
        if budget:
            self.hbm_budget = budget * (1 << 20)
        else:
            limit = (self.jdev.memory_stats() or {}).get("bytes_limit", 0)
            if not limit and self.jdev.platform == "tpu":
                raise RuntimeError(
                    f"{self.jdev}: memory_stats() reports no bytes_limit; "
                    "set device_tpu_hbm_budget_mb explicitly")
            self.hbm_budget = int(limit * 0.85) if limit else 4 << 30
        self.hbm_used = 0
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
        #: in-order in-flight queues ("compute lanes"); JAX executes one
        #: device queue, lanes model completion-poll order
        self._nlanes = mca_param.register(
            "device", "tpu_exec_streams", 2,
            help="number of round-robin async submission lanes")
        self._lanes: List[Deque[_InFlight]] = [collections.deque() for _ in range(self._nlanes)]
        self._rr = 0
        #: eager completion: a single-controller JAX device queue already
        #: orders computations by data dependencies, so successor release
        #: does not need to wait for device events — the runtime completes
        #: the task at dispatch and the whole DAG streams asynchronously
        #: (one sync at taskpool wait). 0 restores reference-style per-lane
        #: event polling (device_gpu.c:1879-1999), which pays a full
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
        #: dual LRU of resident Data keyed by data_id (reference
        #: gpu_mem_lru / gpu_mem_owned_lru)
        self._lru_clean: "collections.OrderedDict[int, Data]" = collections.OrderedDict()
        self._lru_dirty: "collections.OrderedDict[int, Data]" = collections.OrderedDict()
        self._jit_cache: Dict[Any, Any] = {}
        #: native zone allocator models HBM segments (alignment +
        #: fragmentation) inside the budget — the reference's zone_malloc
        #: slab, offset-based since PJRT owns the real device memory
        self._zone = None
        self._offsets: Dict[int, Tuple[int, int]] = {}  # data_id -> (off, nbytes)
        self._accounted: Dict[int, int] = {}  # data_id -> accounted nbytes (non-zone)
        if mca_param.register("device", "tpu_native_zone", 1,
                              help="use the native zone allocator for HBM accounting"):
            from .. import native

            if native.available():
                self._zone = native.ZoneAllocator(self.hbm_budget)
            elif self.jdev.platform == "tpu":
                # on the CPU backend byte-counter accounting stands in
                # (the native-off CI leg); on a chip the configured
                # allocator missing is a broken installation
                raise RuntimeError(
                    "device_tpu_native_zone=1 but the native core is "
                    f"unavailable: {native.build_error()}")
        # -- async staging pipeline (device/staging.py) ------------------
        #: residency lock: LRU/zone/accounting mutations are no longer
        #: single-threaded once the transfer lane prestages wave N+1
        #: while the pump thread commits wave N's epilogs.  RLock — the
        #: stage/evict/realloc paths nest.  Order: _lock -> _res_lock ->
        #: Data.lock; the committer takes only Data.lock, so an eviction
        #: waiting on it under _res_lock cannot deadlock.
        self._res_lock = threading.RLock()
        from .staging import stage_depth_param

        #: pipeline depth (runtime_stage_depth): 1 = synchronous
        #: transfers (no prefetch lane, no committer — the A/B OFF arm);
        #: >= 2 arms the prefetch window and the write-back committer
        self.stage_depth = stage_depth_param()
        #: the pump's intra-wave split threshold: a lone ready batch is
        #: re-sliced across the prefetch window only when its prestage
        #: would move at least this many bytes — splitting shrinks
        #: vmappable waves, so it must buy real transfer overlap
        self.stage_split_bytes = max(0, int(mca_param.register(
            "runtime", "stage_split_kb", 256,
            help="min host->device bytes (KB) a ready batch must need "
                 "staged before the pump re-slices it across the "
                 "prefetch window (intra-wave double buffering)"))) << 10
        self._committer = None
        #: eviction's bounded wait for an async victim commit before the
        #: synchronous fallback (satellite: capacity wait, not a hang)
        self._wb_wait = 60.0

    def _span(self, name: str, **info):
        """A ``pins.span`` of this module, with the ``pool`` and ``rank``
        every span carries."""
        return pins.span(name, pool=self._span_pool, rank=self._rank, **info)

    @property
    def hbm_budget(self) -> int:
        return self._hbm_budget

    @hbm_budget.setter
    def hbm_budget(self, value: int) -> None:
        """Budget changes rebuild the zone, migrating live residency slots
        (slots that no longer fit fall out of segment accounting)."""
        self._hbm_budget = int(value)
        if getattr(self, "_zone", None) is None:
            return
        from .. import native

        fresh = native.ZoneAllocator(self._hbm_budget)
        migrated: Dict[int, Tuple[int, int]] = {}
        for did, (_off, nb) in self._offsets.items():
            noff = fresh.alloc(nb)
            if noff is not None:
                migrated[did] = (noff, nb)
        self._zone.close()
        self._zone = fresh
        self._offsets = migrated
        self.hbm_used = fresh.used

    # ------------------------------------------------------------------
    # entry point from the scheduling core (chore hook delegates here)
    # ------------------------------------------------------------------
    def kernel_scheduler(self, es, task: Task) -> HookReturn:
        """Reference ``parsec_device_kernel_scheduler``
        (device_gpu.c:2510-2730)."""
        task._tpu_enq = time.perf_counter_ns()  # ready-queue wait starts
        with self._lock:
            self._pending.append(task)
            if self._manager_active:
                return HookReturn.ASYNC  # a manager is already running
            self._manager_active = True
        # this worker becomes the manager
        try:
            self._manager_loop(es)
        except BaseException:
            # let another worker take over the still-queued work instead of
            # deadlocking every future device task behind a dead manager
            with self._lock:
                self._manager_active = False
            raise
        return HookReturn.ASYNC  # completions were issued by the manager

    def _manager_loop(self, es) -> None:
        # phase: check_in_deps + exec — submit everything pending.
        # The drained batch is grouped into same-signature WAVES first
        # (one jitted multi-body program per wave — one enqueue RPC
        # instead of one per task); everything else goes per-task.
        while True:
            drained: List[Task] = []
            with self._lock:
                while self._pending:
                    drained.append(self._pending.popleft())
            drained_ns = time.perf_counter_ns()  # ready-queue wait ends
            self._span_batch += 1
            units = self._units_of(drained)
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
                progressed = self._poll_lanes(es)
            with self._lock:
                if not self._pending and all(not l for l in self._lanes):
                    self._manager_active = False
                    return
            if not progressed:
                # nothing completed this spin: block on the oldest event
                # (the reference polls events; jax lets us wait cheaply)
                oldest = next((l[0] for l in self._lanes if l), None)
                if oldest is not None:
                    with self._span("dev:block"):
                        try:
                            oldest.outputs[0].block_until_ready()
                        except Exception:
                            pass

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
            if sig is None:
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
        with self._span("dev:submit_batch", batch=batch_no, n=len(tasks)):
            self._submit_units(self._units_of(tasks), es, False)
            # a transient-submit retry re-queues through ``_pending``
            # (the manager loop's channel); there is no manager in pump
            # mode, so drain retries here before handing the batch back
            # for retirement
            while True:
                with self._lock:
                    if not self._pending:
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
        ``(program, ValuePlan or None)``; ``build()`` gives a new one's
        ``(content key, function, donated positions, plan)``.  The
        ``dev:jit`` span notes ``miss=1`` on a program's first use by
        this device; the compile or load itself comes at its first call,
        as a ``cc:compile`` span under ``dev:dispatch``."""
        with self._span("dev:jit") as sp:
            entry = self._jit_cache.get(local_key)
            if entry is None:
                sp.note(miss=1)
                content_key, fn, donate, plan = build()
                entry = self._jit_cache[local_key] = (self._ccache.jit(
                    fn, key=content_key, donate_argnums=tuple(donate)),
                    plan)
        return entry

    @staticmethod
    def _value_plan(task: Task, body, dev_args) -> ValuePlan:
        """The :class:`ValuePlan` of the program that runs ``body`` on
        tasks staged like ``task``."""
        return ValuePlan(body, dev_args, sum(
            1 for s in task.body_args or () if s[0] == "value"))

    def _count_values(self, plan: ValuePlan, ntasks: int, sp,
                      nouts: int = 0) -> None:
        """``ntasks`` tasks went out under ``plan``: the counters, and
        the same four on the ``dev:wave`` / ``dev:submit_one`` span with
        ``outs``, the outputs its epilog commits."""
        drop, pack, pos, tdrop = (
            plan.dropped * ntasks, plan.packed * ntasks,
            plan.positional * ntasks, plan.tiles_dropped * ntasks)
        self.stats["value_args_dropped"] += drop
        self.stats["value_args_packed"] += pack
        self.stats["value_args_positional"] += pos
        self.stats["tile_args_dropped"] += tdrop
        if sp is not None:
            sp.note(vdrop=drop, vpack=pack, vpos=pos, tdrop=tdrop,
                    outs=nouts)

    def _submit_one(self, task: Task, es, complete: bool = True,
                    drained_ns: int = 0) -> None:
        """Per-task submit with the retry/fail-loudly discipline."""
        self._span_pool = _pool_of(task)
        waited = (drained_ns - task._tpu_enq) // 1000 if drained_ns else 0
        try:
            with self._span("dev:submit_one", cls=task.task_class.name, n=1,
                            batch=self._span_batch, waited_us=waited) as sp:
                self._submit(task, es, complete=complete, span=sp)
        except Exception as e:
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
        return sig

    @staticmethod
    def _wave_body_key(body):
        """What a wave signature starts with, or None for a body whose
        tasks go out alone: bodies with baked static values (per-task
        traces), donation (aliasing across a shared program is unsafe)
        or custom staging hooks; fused supertasks (dsl.fusion) are
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
        """Hashable batching signature ``(body key, FlowPlan)``, or None
        when the task cannot ride a wave (:meth:`_wave_body_key`; data
        args must have knowable shapes).  Two tasks with equal
        signatures trace identically through the shared wave program.
        What the body fixes is asked once a chore; shapes, dtypes and
        modes are compared as the objects they are, and the list of
        flows is interned as its :class:`FlowPlan`, so a signature
        hashes and compares by identity from then on."""
        chore = task.selected_chore
        body = chore.body_fn if chore is not None else None
        if body is None:
            return None
        memo = chore.wave_key
        if memo is None or memo[0] is not body:
            memo = chore.wave_key = (body, self._wave_body_key(body))
        if memo[1] is None:
            return None
        flows: List[Any] = []
        for kind, payload, mode in (task.body_args or ()):
            if kind == "data":
                if payload is None:
                    flows.append(None)
                    continue
                shape, dtype = payload.shape, payload.dtype
                if payload.scratch is not None and scratch.unborn(payload):
                    # no argument of the program: never in one wave with
                    # a task whose tile of this flow has been written
                    flows.append(("unborn", tuple(shape), dtype, mode))
                    continue
                if shape is None or dtype is None:
                    newest = payload.newest_copy()
                    p = getattr(newest, "payload", None)
                    shape = getattr(p, "shape", None)
                    dtype = getattr(p, "dtype", None)
                    if shape is None or dtype is None:
                        return None
                flows.append((tuple(shape), dtype, mode))
            elif kind == "value":
                # traced runtime arg: the TYPE shapes the trace
                flows.append(type(payload))
            elif kind == "scratch":
                flows.append(("scratch", tuple(payload[0]), payload[1]))
            else:
                flows.append(kind)
        key = tuple(flows)
        plan = self._flow_plans.get(key)
        if plan is None:
            plan = self._flow_plans[key] = FlowPlan(key)
        return (memo[1], plan)

    def _submit_wave(self, tasks: List[Task], es, complete: bool = True,
                     drained_ns: int = 0) -> None:
        """Submit a same-signature ready wave as one (or a few
        power-of-2) jitted multi-body programs: ONE device enqueue per
        chunk instead of one per task (round-4 VERDICT #6).

        Inputs are staged PER CHUNK, immediately before that chunk's
        dispatch: peak HBM holds one chunk's inputs plus its in-flight
        outputs, never the whole wave's — a large wave of large tiles
        must not OOM where per-task dispatch would not (ADVICE.md
        round 5, items 1-2).

        Failure containment is a PER-CHUNK invariant: a chunk's
        staging/trace/enqueue errors RAISE before any task of THAT chunk
        has side effects, so the manager's per-task fallback is safe for
        every not-yet-committed task (functional bodies, no donation).
        Earlier chunks of the same wave may already have committed their
        epilogs by then — the fallback does not double-run them only
        because each committed task is marked ``_tpu_completed``, which
        the manager-loop fallback checks before resubmitting.  Once a
        chunk's commit begins, errors are contained HERE with a loud
        pool fail (the same discipline as ``_submit_one``'s completed
        branch): a half-committed chunk must be neither retried
        (double-apply) nor silently skipped (wait() would hang to
        timeout).

        What the wave's tasks share is asked ONCE: their signature's
        :class:`FlowPlan` drives one residency pass (``_stage_chunk``)
        and one commit (``_commit_chunk``) per chunk.

        One ``dev:wave`` span per chunk, that is per device program,
        with the children ``dev:stage_args``, ``dev:jit``,
        ``dev:dispatch`` (the host's enqueue of the program, not the
        chip's execution of it) and ``dev:epilog``."""
        body = tasks[0].selected_chore.body_fn
        cls = tasks[0].task_class.name
        self._span_pool = _pool_of(tasks[0])
        sig = self._signature_of(tasks[0])
        if sig is None:
            raise ValueError(f"{tasks[0]!r} cannot ride a wave")
        plan = sig[1]
        # the body OBJECT (not id(body)): an id-keyed entry outlives the
        # body it described, and a recycled id would serve a dead body's
        # wave program — keying on the object pins it alive instead,
        # matching the per-task path below
        base_key = getattr(body, "_jit_key", None) or body
        start = 0
        remaining = len(tasks)
        while remaining:
            cnt = 1 << (remaining.bit_length() - 1)  # largest pow2 chunk
            grp = tasks[start:start + cnt]
            start += cnt
            remaining -= cnt
            waited = sum(drained_ns - t._tpu_enq
                         for t in grp) // 1000 if drained_ns else 0
            with self._span("dev:wave", cls=cls, n=cnt,
                            batch=self._span_batch, waited_us=waited) as sp:
                self._submit_chunk(grp, body, base_key, plan, es, complete,
                                   sp)

    def _submit_chunk(self, grp: List[Task], body, base_key,
                      fplan: FlowPlan, es, complete: bool, wave_span) -> None:
        """One power-of-2 chunk of a wave: stage, look the program up,
        dispatch it, commit every task's outputs.  The program's
        arguments are the tasks' tiles; their values reach the bodies as
        the program's :class:`ValuePlan` says."""
        cnt = len(grp)
        cls = grp[0].task_class.name
        with self._span("dev:stage_args") as sp:
            # host tiles, their bytes, tiles staged, residency hits
            tally = [0, 0, 0, 0]
            staged = self._stage_chunk(grp, fplan, tally)
            sp.note(host_tiles=tally[0], bytes=tally[1], tiles=tally[2],
                    hits=tally[3])
        args0, nout = staged[0][1], fplan.nout

        def build():
            plan = self._value_plan(grp[0], body, args0)

            def _wave(*flat):
                outs: List[Any] = []
                for args in plan.bodies_args(flat, cnt):
                    o = body(*args)
                    outs.extend(o if isinstance(o, (tuple, list))
                                else (o,))
                return tuple(outs)
            # the task class in the program's name: the device trace's
            # ``XLA Modules`` line then splits the chip's time by class
            _wave.__name__ = f"_wave_{cls}"
            return (("wave", cls, self._content_fp(body), len(args0), nout,
                     cnt) + plan.tag, _wave, (), plan)
        jitted, plan = self._cached_jit(
            ("wave", cls, base_key, argsig(args0), _placeholders_at(args0), nout,
             cnt), build)
        flat = plan.flatten([args for (_t, args, _o) in staged])
        if pins.active(pins.EXEC_BEGIN):
            for t in grp:
                self._fire_exec(t, pins.EXEC_BEGIN, wave=cnt)
        with self._span("dev:dispatch"):
            outs = jitted(*flat)
        if pins.active(pins.EXEC_END):
            for t in grp:
                self._fire_exec(t, pins.EXEC_END, wave=cnt)
        self._count_values(plan, cnt, wave_span, len(outs))
        if len(outs) != nout * cnt:
            raise ValueError(
                f"wave of {grp[0].task_class.name}: bodies returned "
                f"{len(outs)} outputs for {nout * cnt} writable flows")
        self.stats["wave_submits"] = self.stats.get("wave_submits", 0) + 1
        self.stats["wave_tasks"] = self.stats.get("wave_tasks", 0) + cnt
        with self._span("dev:epilog") as sp:
            if self._eager:
                self._commit_chunk(staged, outs, nout, es, complete, sp)
                return
            for k, (task, _args, ospecs) in enumerate(staged):
                if getattr(task.taskpool, "failed", False):
                    continue  # a sibling's failure already took the pool
                lane = self._lanes[self._rr % self._nlanes]
                self._rr += 1
                lane.append(_InFlight(
                    task, list(outs[k * nout:(k + 1) * nout]), ospecs))
                task._tpu_completed = True  # owned by the lane now

    def _stage_chunk(self, grp: List[Task], fplan: FlowPlan,
                     tally: List[int]) -> List[_Staged]:
        """kernel_push for one chunk of a wave, in ONE pass under ONE
        hold of the residency lock: ``(task, dev_args, out_specs)`` per
        task, by the signature's :class:`FlowPlan`.  A tile that is
        resident and current yields its payload and one LRU touch a
        chunk; the others go into the one coalesced put
        (``_stage_in_batch``; tile by tile in the synchronous regime);
        ownership moves only once every tile of the chunk is resident,
        so an error in here raises with no task of the chunk touched.
        ``tally`` counts for the ``dev:stage_args`` span: ``[tiles
        copied from the host, their bytes, tiles staged, of them found
        resident]``."""
        idx = self.data_index
        steps = fplan.steps
        staged: List[_Staged] = []
        owns: List[Tuple[Data, int]] = []
        found: Dict[int, Any] = {}  # data_id -> payload on this device
        #: data_id -> [tile, (argument list, position) it still misses in]
        missing: Dict[int, List[Any]] = {}
        ntiles = nread = nmiss = 0
        with self._res_lock:
            for task in grp:
                specs = task.body_args
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
                    if how == PLACEHOLDER:
                        arr = extra
                    else:
                        nread += 1
                        did = data.data_id
                        arr = found.get(did)
                        if arr is None:
                            c = data.current_copy(idx)
                            if c is not None:
                                arr = found[did] = c.payload
                                self._lru_touch(
                                    data,
                                    dirty=c.coherency is Coherency.OWNED)
                            else:
                                slot = missing.get(did)
                                if slot is None:
                                    slot = missing[did] = [data]
                                slot.append((args, len(args)))
                    args.append(arr)
                    ntiles += 1
                    owns.append((data, access))
                    if access & _OUT:
                        ospecs.append((pos, data))
                staged.append((task, args, ospecs))
                task._tpu_scratch = mine
            if missing:
                tiles = [slot[0] for slot in missing.values()]
                if self.stage_depth > 1:
                    # tentpole (c) of the staging pipeline: the chunk's
                    # host->device transfers as one batched put
                    self._stage_in_batch(tiles, tally)
                else:
                    for data in tiles:
                        self._stage_in(data, tally)
                for slot in missing.values():
                    arr = slot[0].get_copy(idx).payload
                    for args, at in slot[1:]:
                        args[at] = arr
                    nmiss += len(slot) - 1
            for data, access in owns:
                data.transfer_ownership(idx, access)
        tally[2] += ntiles
        tally[3] += nread - nmiss
        return staged

    def _stage_task_args(self, task: Task, body,
                         tally: Optional[List[int]] = None):
        """kernel_push: stage every flow of ``task`` onto this device and
        return ``(dev_args, out_specs, out_hooks)`` (reference
        device_gpu.c:2015-2164 stage-in phase, factored out so the wave
        path shares it).  ``tally`` counts for the ``dev:stage_args``
        span: ``[tiles copied from the host, their bytes, tiles
        staged]``."""
        # per-flow custom staging (reference stage_in/stage_out device
        # hooks, device_gpu.h:62-94), keyed by data-arg order
        si_hooks = getattr(body, "_stage_in", None) or {}
        so_hooks = getattr(body, "_stage_out", None) or {}
        dev_args: List[Any] = []
        out_specs: List[Tuple[int, Data]] = []
        out_hooks: List[Any] = []
        held: List[Data] = []  # scratch tiles: one user each, see _epilog
        data_idx = -1
        for pos, spec in enumerate(task.body_args or ()):
            kind, payload, mode = spec
            if kind == "data":
                data_idx += 1
                if payload is None:  # optional (guarded-off) flow
                    dev_args.append(None)
                    continue
                rw = mode & AccessMode.INOUT
                si = si_hooks.get(data_idx)
                if si is not None and (mode & AccessMode.OUT) \
                        and so_hooks.get(data_idx) is None:
                    # the body would compute on the PACKED representation
                    # and the epilog would commit it as the home-layout
                    # tile — silently wrong; loud is the contract
                    raise RuntimeError(
                        f"{task!r}: stage_in on writable flow requires a "
                        "matching stage_out hook")
                if payload.scratch is not None:
                    held.append(payload)
                if si is not None:
                    # custom staging: the hook's result IS the flow's
                    # device copy (pack/convert — reference stage_custom)
                    arr = self._stage_in_custom(payload, si)
                elif scratch.unborn(payload) or rw == AccessMode.OUT:
                    # a NEW flow's tile nobody has written, or a
                    # write-only flow the body overwrites (reference
                    # skips stage-in for OUT-only flows): nothing to
                    # stage and no argument of the program — the plan
                    # gives the body zeros inside the trace
                    arr = self._placeholder(payload)
                else:
                    arr = self._stage_in(payload, tally)
                if tally is not None:
                    tally[2] += 1
                payload.transfer_ownership(self.data_index, rw)
                dev_args.append(arr)
                if mode & AccessMode.OUT:
                    out_specs.append((pos, payload))
                    out_hooks.append(so_hooks.get(data_idx))
            elif kind == "value":
                dev_args.append(payload)
            elif kind == "scratch":
                shape, dtype = payload
                dev_args.append(jnp.zeros(shape, dtype, device=self.jdev))
            # other kinds (e.g. "ctl") contribute no argument
        task._tpu_scratch = held
        return dev_args, out_specs, out_hooks

    def _submit(self, task: Task, es=None, complete: bool = True,
                span=None) -> None:
        """Stage + body dispatch (reference device_gpu.c:2015-2164);
        ``span`` is the caller's ``dev:submit_one``."""
        body = task.selected_chore.body_fn
        if body is None:
            # DTD/PTG store the raw device body on the chore at build time
            raise RuntimeError(f"chore of {task!r} has no body_fn for device execution")
        with self._span("dev:stage_args") as sp:
            tally = [0, 0, 0]
            dev_args, out_specs, out_hooks = self._stage_task_args(
                task, body, tally)
            sp.note(host_tiles=tally[0], bytes=tally[1], tiles=tally[2])

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
        #     enqueued async step).
        donate = tuple(getattr(body, "_donate_args", ()) or ())
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
            arr_args = [jnp.zeros(a.shape, a.dtype, device=self.jdev)
                        if isinstance(a, jax.ShapeDtypeStruct) else a
                        for a in dev_args[:split]]
            vals = tuple(dev_args[split:])

            def _bound(*arrs, _body=body, _vals=vals):
                return _body(*arrs, *_vals)
            jitted, _ = self._cached_jit(
                (base_key, vals),
                lambda: (("static", self._content_fp(body), vals),
                         _bound, donate, None))
            # a donating call that raises may have invalidated its input
            # buffers: the task is no longer safely retryable
            task._tpu_effects = bool(donate)
            self._fire_exec(task, pins.EXEC_BEGIN)
            with self._span("dev:dispatch"):
                outputs = jitted(*arr_args)
            self._fire_exec(task, pins.EXEC_END)
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
                        plan.donate(donate), plan)
            jitted, plan = self._cached_jit(
                (base_key, argsig(dev_args), _placeholders_at(dev_args)), build)
            task._tpu_effects = bool(donate)
            self._fire_exec(task, pins.EXEC_BEGIN)
            with self._span("dev:dispatch"):
                outputs = jitted(*plan.flatten((dev_args,)))
            self._fire_exec(task, pins.EXEC_END)
            self._count_values(plan, 1, span, len(out_specs))
        if not isinstance(outputs, (tuple, list)):
            outputs = (outputs,)
        outputs = list(outputs)
        if len(outputs) != len(out_specs):
            raise ValueError(
                f"device body of {task!r} returned {len(outputs)} outputs "
                f"for {len(out_specs)} writable flows")
        inflight = _InFlight(task, outputs, out_specs, out_hooks,
                             donated=bool(donate))
        if self._eager:
            from ..core import scheduling

            # the epilog mutates output tiles one by one (rebind +
            # version bump): once entered, a retry would double-apply
            task._tpu_effects = True
            with self._span("dev:epilog") as sp:
                sp.note(n=1, outs=len(out_specs),
                        home=self._epilog(inflight))
                task._tpu_completed = True
                if complete:
                    scheduling.complete_execution(self.context, es, task)
            return
        lane = self._lanes[self._rr % self._nlanes]
        self._rr += 1
        lane.append(inflight)

    def _placeholder(self, data: Data) -> Any:
        """What stands in for a tile there is nothing to stage for: its
        shape and dtype alone (``device/value_args.py`` turns that into
        zeros inside the trace)."""
        newest = data.newest_copy()
        held = getattr(newest, "payload", None)
        shape = data.shape if data.shape is not None \
            else getattr(held, "shape", None)
        dtype = data.dtype if data.dtype is not None \
            else getattr(held, "dtype", None)
        if shape is None or dtype is None:
            return self._stage_in(data)  # shape unknown: fall back
        return jax.ShapeDtypeStruct(tuple(shape), np.dtype(dtype))

    def _stage_in_custom(self, data: Data, hook) -> Any:
        """Stage via a user hook: ``hook(data, device) -> jax.Array``.
        The hook's result becomes the flow's device copy (the reference's
        stage_in writes into the GPU copy buffer the same way); residency
        is accounted at the STAGED size, which may differ from the home
        tile's (packed subtile)."""
        with self._res_lock:
            mine = data.get_copy(self.data_index)
            newest = data.newest_copy()
            if mine is not None and newest is not None \
                    and mine.version >= newest.version and mine.payload is not None \
                    and getattr(mine, "staged_by", None) is hook:
                # reusable ONLY if this same hook produced it: a current
                # device copy staged by the default path (prefetch, a prior
                # epilog) holds the HOME representation, not the packed one
                self._lru_touch(data, dirty=mine.coherency is Coherency.OWNED)
                return mine.payload
            if mine is not None and mine.payload is not None \
                    and getattr(mine, "staged_by", None) is None:
                host = data.get_copy(0)
                if host is None or host.payload is None \
                        or host.version < mine.version:
                    # the device copy is the ONLY up-to-date home-layout
                    # replica: flush it home BEFORE the packed staging
                    # replaces it, or that data exists nowhere (and the
                    # hook itself typically reads the host copy).  A
                    # deferred commit may still be pending for this tile —
                    # the synchronous flush lands the same version first
                    # and the committer's guarded commit drops as stale.
                    self._writeback(data)
            arr = hook(data, self)
            old = mine.nbytes if (mine is not None and mine.payload is not None) else 0
            self._hbm_realloc(data, old, arr.nbytes)
            arr = jax.device_put(arr, self.jdev)
            self.stats["bytes_in"] += arr.nbytes
            self.stats["custom_stage_in"] = self.stats.get("custom_stage_in", 0) + 1
            c = data.attach_copy(self.data_index, arr)
            c.version = newest.version if newest is not None else 0
            c.staged_by = hook
            self._lru_touch(data, dirty=False)
            return arr

    def _stage_in(self, data: Data,
                  tally: Optional[List[int]] = None) -> Any:
        """Materialize the newest version of ``data`` on this device."""
        with self._res_lock:
            mine = data.get_copy(self.data_index)
            if mine is not None and getattr(mine, "staged_by", None) is not None:
                # a custom-staged PACKED representation must never be served
                # as the home layout: drop it and restage from the host copy
                # (which _stage_in_custom flushed to the same version)
                self._drop_copy(data, evicted=False)
                mine = None
            newest = data.newest_copy()
            if mine is not None and newest is not None and mine.version >= newest.version and mine.payload is not None:
                self._lru_touch(data, dirty=mine.coherency is Coherency.OWNED)
                return mine.payload
            if newest is None:
                raise RuntimeError(f"{data!r}: no valid copy to stage in")
            # re-staging over a stale device copy replaces it: account the delta
            old = mine.nbytes if (mine is not None and mine.payload is not None) else 0
            if isinstance(newest.payload, jax.Array):
                # device-resident arrival (device-capable fabric): land it
                # with a direct device_put — device-to-device, ICI-class on
                # multi-chip, no host numpy bounce (SURVEY §5.8)
                self._hbm_realloc(data, old, newest.payload.nbytes)
                arr = jax.device_put(newest.payload, self.jdev)
                self.stats["bytes_d2d"] += newest.payload.nbytes
            else:
                host = np.asarray(newest.payload)
                with self._span("dev:h2d", tiles=1, bytes=host.nbytes):
                    self._hbm_realloc(data, old, host.nbytes)
                    # guard: the host copy RETAINS this buffer at version
                    # v — a zero-copy put followed by a donating task
                    # would overwrite it in place while its version still
                    # claims v
                    arr = private_device_put(host, self.jdev, guard=host)
                self.stats["bytes_in"] += host.nbytes
                if data.scratch is not None:
                    self.stats["scratch_bytes_in"] += host.nbytes
                if tally is not None:
                    tally[0] += 1
                    tally[1] += host.nbytes
            c = data.attach_copy(self.data_index, arr)
            c.version = newest.version
            self._lru_touch(data, dirty=False)
            return arr

    # ------------------------------------------------------------------
    # async staging pipeline: prefetch lane + batched puts
    # ------------------------------------------------------------------
    def _collect_stage_tiles(self, tasks: List[Task]) -> List[Data]:
        """The unique PLAIN input tiles of ``tasks`` — flows the default
        stage-in path will serve: readable, not custom-staged (a hook's
        packed layout is the hook's business), deduplicated per tile."""
        out: List[Data] = []
        seen = set()
        for task in tasks:
            chore = task.selected_chore
            body = chore.body_fn if chore is not None else None
            si_hooks = getattr(body, "_stage_in", None) or {}
            data_idx = -1
            for spec in task.body_args or ():
                kind, payload, mode = spec
                if kind != "data":
                    continue
                data_idx += 1
                if payload is None or si_hooks.get(data_idx) is not None:
                    continue
                if (mode & AccessMode.INOUT) == AccessMode.OUT:
                    continue  # write-only: no H2D needed
                if scratch.unborn(payload):
                    continue  # a scratch tile nobody has written
                if payload.data_id in seen:
                    continue
                seen.add(payload.data_id)
                out.append(payload)
        return out

    def _stage_in_batch(self, datas: List[Data],
                        tally: Optional[List[int]] = None) -> int:
        """Batched :meth:`_stage_in`: resident tiles are touched, stale
        host-side tiles are coalesced into ONE ``jax.device_put`` call
        (tentpole (c) — one enqueue RPC for the wave's transfers instead
        of one per tile), each result re-checked against the per-tile
        aliasing guard.  Returns bytes moved host->device; the put is
        the ``dev:h2d`` span.

        The residency lock is held to decide what moves and to make room
        for it, and again to attach what arrived — NOT over the put: the
        transfer lane's put of the next batch (10 ms for 27 tiles of 1
        MiB on a v5e) used to hold the pump's staging and epilog of the
        current one for its whole length (``PERF.md`` §6, PR 27).  A tile
        that somebody else staged or wrote in between keeps their copy.
        (The pump's own call, from ``_stage_chunk``, holds the lock
        around all of it, as it always did: nobody waits for it.)"""
        moved = 0
        idx = self.data_index
        puts: List[Tuple[Data, np.ndarray, int]] = []
        with self._res_lock:
            for data in datas:
                mine = data.get_copy(idx)
                if mine is not None and getattr(mine, "staged_by", None) is not None:
                    self._drop_copy(data, evicted=False)
                    mine = None
                newest = data.newest_copy()
                if mine is not None and newest is not None \
                        and mine.version >= newest.version \
                        and mine.payload is not None:
                    self._lru_touch(
                        data, dirty=mine.coherency is Coherency.OWNED)
                    continue
                if newest is None:
                    raise RuntimeError(f"{data!r}: no valid copy to stage in")
                old = mine.nbytes if (mine is not None
                                      and mine.payload is not None) else 0
                if isinstance(newest.payload, jax.Array):
                    # device-resident arrival: direct d2d put, uncoalesced
                    self._hbm_realloc(data, old, newest.payload.nbytes)
                    arr = jax.device_put(newest.payload, self.jdev)
                    self.stats["bytes_d2d"] += newest.payload.nbytes
                    c = data.attach_copy(idx, arr)
                    c.version = newest.version
                    self._lru_touch(data, dirty=False)
                    moved += newest.payload.nbytes
                    continue
                host = np.asarray(newest.payload)
                self._hbm_realloc(data, old, host.nbytes)
                puts.append((data, host, newest.version))
        if not puts:
            return moved
        nbytes = sum(h.nbytes for (_d, h, _v) in puts)
        try:
            with self._span("dev:h2d", tiles=len(puts), bytes=nbytes):
                try:
                    arrs = jax.device_put([h for (_d, h, _v) in puts],
                                          self.jdev)
                except Exception:
                    # backend rejected the coalesced put: per-tile path
                    self.stats["stage_batch_fallbacks"] += 1
                    arrs = [private_device_put(h, self.jdev, guard=h)
                            for (_d, h, _v) in puts]
                else:
                    arrs = [_unalias(a, h, h, self.jdev)
                            for a, (_d, h, _v) in zip(arrs, puts)]
        except BaseException:
            with self._res_lock:  # the room made for what never arrived
                for (data, _h, _v) in puts:
                    mine = data.get_copy(idx)
                    if mine is None or mine.payload is None:
                        self._hbm_free(data, 0)
            raise
        if tally is not None:
            tally[0] += len(puts)
            tally[1] += nbytes
        with self._res_lock:
            for (data, host, ver), arr in zip(puts, arrs):
                self.stats["bytes_in"] += host.nbytes
                if data.scratch is not None:
                    self.stats["scratch_bytes_in"] += host.nbytes
                moved += host.nbytes
                mine = data.get_copy(idx)
                if mine is not None and mine.payload is not None \
                        and mine.version >= ver \
                        and getattr(mine, "staged_by", None) is None:
                    continue  # staged or written meanwhile: theirs stands
                c = data.attach_copy(idx, arr)
                c.version = ver
                self._lru_touch(data, dirty=False)
            self.stats["stage_batched_puts"] = \
                self.stats.get("stage_batched_puts", 0) + 1
            self.stats["stage_batched_tiles"] = \
                self.stats.get("stage_batched_tiles", 0) + len(puts)
        return moved

    def prestage_tiles(self, tasks: List[Task]) -> Tuple[List[Data], int]:
        """The pump's look ahead at a ready batch: the tiles a prestage
        of ``tasks`` would move — read by one of them and not current on
        this device — and their bytes.  A batch with none has nothing
        for the transfer lane; the bytes are the pump's intra-wave split
        heuristic (re-slicing a ready batch across the prefetch window
        only pays when there is real transfer work to hide).  One pass
        by the tasks' signatures, without the residency lock: a stale
        read merely mis-sizes the hint, and the submit path stages what
        the lane did not."""
        idx = self.data_index
        seen = set()
        alone: List[Task] = []
        tiles: List[Data] = []
        for task in tasks:
            sig = self._signature_of(task)
            if sig is None:
                alone.append(task)
                continue
            specs = task.body_args
            for pos in sig[1].reads:
                data = specs[pos][1]
                if data.data_id not in seen:
                    seen.add(data.data_id)
                    tiles.append(data)
        # a task that goes out alone (hooks, donation, ...) names its
        # plain input tiles the long way
        tiles += [d for d in self._collect_stage_tiles(alone)
                  if d.data_id not in seen]
        moving: List[Data] = []
        nbytes = 0
        for data in tiles:
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
        from .staging import _SPAN_SEQ

        if tasks:  # the lane runs ahead of the batch's own submit
            self._span_pool = _pool_of(tasks[0])
        with self._span("dev:stage_in", id=next(_SPAN_SEQ),
                        tiles=len(tiles), batch=batch_no) as sp:
            # a batch with nothing to move: the span, for whoever reads
            # the lane by batch, and neither the lock nor a walk
            sp.note(bytes=self._stage_in_batch(tiles) if tiles else 0)
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
            from .staging import WritebackCommitter

            com = self._committer = WritebackCommitter(self)
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

    # ------------------------------------------------------------------
    # HBM budget + dual LRU eviction
    # ------------------------------------------------------------------
    def _reserve(self, nbytes: int) -> None:
        """Make room: evict clean first, then write back dirty tiles
        (reference device_gpu.c:978-1120 retry/evict loops)."""
        with self._res_lock:
            guard = 0
            while self.hbm_used + nbytes > self.hbm_budget and guard < 10000:
                guard += 1
                if not self._evict_one():
                    break  # nothing evictable; trust the PJRT allocator

    def _evict_one(self) -> bool:
        with self._res_lock:
            if self._lru_clean:
                _, victim = self._lru_clean.popitem(last=False)
                mine = victim.get_copy(self.data_index)
                host = victim.get_copy(0)
                if mine is not None and (host is None or host.payload is None
                                         or host.version < mine.version):
                    # a CLEAN device copy can still be the ONLY valid copy:
                    # device-native arrivals (_deposit_payload, bytes_d2d)
                    # attach no host copy — dropping without write-back would
                    # destroy the data
                    self._writeback_evict(victim)
                self._drop_copy(victim)
                return True
            if self._lru_dirty:
                _, victim = self._lru_dirty.popitem(last=False)
                self._writeback_evict(victim)
                self._drop_copy(victim)
                return True
            return False

    def _writeback_evict(self, victim: Data) -> None:
        """Eviction write-back, routed through the async committer when
        the pipeline is on (satellite fix: the synchronous ``_writeback``
        inside ``_stage_in`` blocked the whole staging path on a D2H
        get).  The wait is a CAPACITY wait, bounded: the victim's bytes
        must exist at home before its device copy drops, so a wedged or
        failed committer falls back to the synchronous path — data
        safety first, the version guard makes the duplicate a no-op."""
        com = self._committer
        if com is not None and com.healthy:
            try:
                com.enqueue(victim, self._span_pool, self._span_batch)
            except Exception:
                # committer died between the check and the enqueue: the
                # sync fallback still flushes the victim; the sticky
                # error surfaces at the next epilog enqueue/flush
                self._writeback(victim)
                return
            if com.wait_for(victim.data_id, timeout=self._wb_wait):
                return
            debug.warning(
                "async write-back of eviction victim %r did not land in "
                "%.0fs; falling back to a synchronous flush",
                victim, self._wb_wait)
        self._writeback(victim)

    def _hbm_realloc(self, data: Data, old_nbytes: int, new_nbytes: int) -> None:
        """(Re)account ``data``'s residency slot, evicting for space. With
        the native zone, alignment + fragmentation are modelled for real:
        an allocation can fail even under budget and trigger eviction."""
        with self._res_lock:
            self._hbm_realloc_locked(data, old_nbytes, new_nbytes)

    def _hbm_realloc_locked(self, data: Data, old_nbytes: int,
                            new_nbytes: int) -> None:
        held = (self._offsets.get(data.data_id, (0, 0))[1]
                if self._zone is not None
                else self._accounted.get(data.data_id, 0))
        if new_nbytes > 0 and held == new_nbytes:
            # the same bytes rebound (an epilog's output over its input):
            # the slot stays, nothing is allocated, nobody is evicted
            return
        # the allocatee must not be its own eviction victim (either mode):
        # callers re-touch the LRU right after accounting
        self._lru_clean.pop(data.data_id, None)
        self._lru_dirty.pop(data.data_id, None)
        if self._zone is not None:
            slot = self._offsets.pop(data.data_id, None)
            if slot is not None:
                self._zone.release(slot[0])
            if new_nbytes > 0:
                guard = 0
                while True:
                    off = self._zone.alloc(new_nbytes)
                    if off is not None or guard > 10000 or not self._evict_one():
                        break
                    guard += 1
                if off is not None:
                    self._offsets[data.data_id] = (off, new_nbytes)
            self.hbm_used = self._zone.used
        else:
            # truth for what this device accounted lives in _accounted, not
            # in the caller's view: copies attached from outside (e.g. a
            # benchmark pre-placing tiles) enter the LRU via _stage_in
            # without ever being accounted, and freeing them must not
            # underflow the budget
            old_acc = self._accounted.pop(data.data_id, 0)
            self._reserve(max(0, new_nbytes - old_acc))
            self.hbm_used += new_nbytes - old_acc
            if new_nbytes > 0:
                self._accounted[data.data_id] = new_nbytes

    def _hbm_free(self, data: Data, nbytes: int) -> None:
        with self._res_lock:
            if self._zone is not None:
                slot = self._offsets.pop(data.data_id, None)
                if slot is not None:
                    self._zone.release(slot[0])
                self.hbm_used = self._zone.used
            else:
                self.hbm_used -= self._accounted.pop(data.data_id, 0)

    def _drop_copy(self, data: Data, *, evicted: bool = True) -> None:
        with self._res_lock:
            c = data.detach_copy(self.data_index)
            if c is not None:
                self._hbm_free(data, c.nbytes)
                if evicted:
                    self.stats["evictions"] += 1

    def _wb_snapshot(self, data: Data):
        """Version-guarded snapshot of a dirty device copy: returns
        ``(payload, version)`` to commit home, or None when the commit
        would be wrong or redundant.  Taken under the Data lock so a
        concurrent epilog rebind cannot tear payload from version."""
        with data.lock:
            c = data.get_copy(self.data_index)
            if c is None or c.payload is None:
                return None
            if getattr(c, "staged_by", None) is not None:
                # packed custom-staged representation: flushing it home
                # would corrupt the home tile; the host copy already holds
                # the same version in home layout (_stage_in_custom
                # pre-flushes)
                return None
            hc = data.get_copy(0)
            if hc is not None and hc.payload is not None \
                    and hc.version >= c.version:
                # the host already holds this version OR NEWER (a CPU body
                # consumed the device output and bumped past it — the mixed
                # native_device DAG shape): flushing the stale device copy
                # would roll the tile back
                return None
            return (c.payload, c.version)

    def _commit_host(self, data: Data, version: int, host) -> bool:
        """Land a D2H'd payload as the host copy at ``version``.  The
        guard re-checks under the Data lock: a newer commit that landed
        while our get was in flight wins and ours drops (stale commits
        are safe to drop — the PR 3 version guard).  Deliberately NO
        version_bump: the committed value is the same write the device
        epilog already bumped for, and a second bump would make every
        deferred commit an RT001 unordered-writer false positive."""
        if not host.flags.writeable:
            host = host.copy()  # host copies must be mutable for CPU bodies
        with data.lock:
            hc = data.get_copy(0)
            if hc is not None and hc.payload is not None \
                    and hc.version >= version:
                return False
            hc = data.attach_copy(0, host)
            hc.version = version
            hc.coherency = Coherency.SHARED
        self.stats["bytes_out"] += host.nbytes
        if data.scratch is not None:  # spilled by an eviction
            self.stats["scratch_bytes_out"] += host.nbytes
        return True

    def _d2h_batch(self, payloads: List[Any]) -> List[Optional[np.ndarray]]:
        """Batched device->host gets: ONE device sync for the whole
        batch, then the (now-ready) buffers convert without further
        blocking — the coalesced-gets half of tentpole (c).  A payload
        that a donating task consumed since it was snapshotted comes
        back as None: that version no longer exists anywhere, and the
        consumer's own output supersedes it."""
        try:
            jax.block_until_ready(payloads)
        except Exception:
            pass  # non-jax or consumed payloads: asarray below decides
        hosts: List[Optional[np.ndarray]] = []
        for p in payloads:
            try:
                hosts.append(np.asarray(p))
            except RuntimeError:
                if not (isinstance(p, jax.Array) and p.is_deleted()):
                    raise
                hosts.append(None)
        return hosts

    def _writeback(self, data: Data) -> None:
        """Synchronous write-back-to-rest of a dirty tile (reference w2r
        tasks, ``parsec_gpu_create_w2r_task``); the pipeline's deferred
        path shares its snapshot/commit halves."""
        snap = self._wb_snapshot(data)
        if snap is None:
            return
        payload, version = snap
        host = np.asarray(payload)  # D2H
        self._commit_host(data, version, host)

    def _writeback_batch(self, datas: List[Data]) -> int:
        """Batched synchronous flush (the ``detach()`` path): snapshot
        every dirty tile, ONE device sync + coalesced gets, guarded
        commits — instead of one blocking get per tile in dict order.
        Returns the number of tiles actually committed."""
        from .staging import _SPAN_SEQ

        snaps = []
        for d in datas:
            s = self._wb_snapshot(d)
            if s is not None:
                snaps.append((d, s[0], s[1]))
        if not snaps:
            return 0
        with self._span("dev:writeback", id=next(_SPAN_SEQ),
                        tiles=len(snaps), batch=self._span_batch,
                        bytes=sum(int(getattr(p, "nbytes", 0))
                                  for (_d, p, _v) in snaps)):
            hosts = self._d2h_batch([p for (_d, p, _v) in snaps])
            committed = 0
            for (data, _p, version), host in zip(snaps, hosts):
                if host is not None and self._commit_host(data, version,
                                                          host):
                    committed += 1
        self.stats["wb_batches"] = self.stats.get("wb_batches", 0) + 1
        return committed

    def _lru_touch(self, data: Data, *, dirty: bool) -> None:
        with self._res_lock:
            self._lru_clean.pop(data.data_id, None)
            self._lru_dirty.pop(data.data_id, None)
            (self._lru_dirty if dirty else self._lru_clean)[data.data_id] = data

    # ------------------------------------------------------------------
    # completion / stage_out / epilog
    # ------------------------------------------------------------------
    def _poll_lanes(self, es) -> bool:
        """Retire completed computations, in order per lane (reference
        per-stream event polling)."""
        from ..core import scheduling

        progressed = False
        for lane in self._lanes:
            while lane:
                inflight = None
                try:
                    if not lane[0].ready():
                        break
                    inflight = lane.popleft()
                    self._epilog(inflight)
                except Exception as e:
                    # the async computation itself died (device error
                    # surfacing at poll) or the epilog could not commit
                    # outputs: the task must NOT complete — successors
                    # would consume garbage.  Fail the pool loudly.
                    if inflight is None:
                        inflight = lane.popleft()  # ready() raised
                    debug.error("tpu lane retirement failed: %s", e)
                    self._fail_task_pool(
                        inflight.task,
                        f"device lane retirement raised: {e!r}")
                    progressed = True
                    continue
                scheduling.complete_execution(self.context, es, inflight.task)
                progressed = True
        return progressed

    def _commit_output(self, data: Data, arr, nbytes: int,
                       bumps_heard: bool) -> None:
        """One output of one task, committed (the caller holds the
        residency lock): rebind the device copy, account its residency,
        bump the version, keep the tile resident and dirty.  Shared by
        the per-task epilog and the wave's."""
        idx = self.data_index
        if data.scratch is not None and scratch.unborn(data):
            self.stats["scratch_tiles_born"] += 1
        c = data.get_copy(idx)
        if c is None:
            c = data.attach_copy(idx, arr)
        else:
            c.payload = arr
        # the committed value is HOME-layout (stage_out already
        # unpacked): a packed stage_in marker must not survive it
        c.staged_by = None
        self._hbm_realloc_locked(data, 0, nbytes)
        data.version_bump(idx, bumps_heard)
        self._lru_touch(data, dirty=True)

    def _release_scratch(self, tiles) -> None:
        """A task that was one declared user of each of these scratch
        tiles has committed (the caller holds the residency lock): with
        the last user the tile's copy here is dropped (a program already
        enqueued keeps its buffer)."""
        for data in tiles:
            if scratch.release(data):
                self._lru_clean.pop(data.data_id, None)
                self._lru_dirty.pop(data.data_id, None)
                self._drop_copy(data, evicted=False)
                self.stats["scratch_tiles_freed"] += 1

    def _epilog(self, inflight: _InFlight) -> int:
        """Commit ONE task's outputs: rebind device copies, bump
        versions, keep tiles resident & dirty (reference kernel_epilog
        device_gpu.c:2343 — data stays OWNED on device; host pulls on
        demand).  A flow's custom stage_out hook transforms the body
        output first (scatter a packed subtile back — reference
        stage_custom.jdf).  The path of whatever is not a wave: a task
        that went out alone, a donating program, hooks, the lanes.
        Returns the number of outputs handed to the committer."""
        if pins.active(pins.DEVICE_EPILOG_BEGIN):
            # happens-before join point: the manager thread is about to
            # commit this task's outputs (version bumps) — hb-check must
            # order them after the task's exec, which may have run on a
            # different (worker) thread (analysis/hb.py)
            pins.fire(pins.DEVICE_EPILOG_BEGIN, None, inflight.task)
        bumps_heard = pins.active(pins.DATA_VERSION_BUMP)
        with self._res_lock:
            for (pos, data), arr, so in zip(inflight.out_specs,
                                            inflight.outputs,
                                            inflight.out_hooks):
                if so is not None:
                    # commit to THIS device: a hook building from host data
                    # would otherwise land on the process default device
                    arr = jax.device_put(so(arr, data, self), self.jdev)
                    self.stats["custom_stage_out"] = self.stats.get("custom_stage_out", 0) + 1
                self._commit_output(data, arr, arr.nbytes, bumps_heard)
            # outputs grew residency: re-settle under the budget (zone mode
            # already evicted during allocation)
            if self._zone is None:
                self._reserve(0)
            self._release_scratch(inflight.task._tpu_scratch)
        self.stats["task_commits"] += 1
        if inflight.donated:
            # NOT a donating program's outputs: the successor of an
            # in-place chain consumes this very buffer, so an eager get
            # either stalls the chain behind a device->host copy of
            # every intermediate version (on the chip: 4 GiB per panel
            # step of the N=32768 segmented dpotrf, 15 s each) or loses
            # the race and reads a deleted array.  Such tiles stay
            # dirty-resident; detach/flush/eviction carry the final
            # version home through the synchronous guarded path.
            return 0
        home = inflight.task._tpu_home
        return self._send_home(
            [data for (pos, data) in inflight.out_specs
             if data.scratch is None and (home is None or pos in home)],
            bool(home))

    def _send_home(self, going: List[Data], last: bool) -> int:
        """Tentpole (b) of the staging pipeline: hand just-committed
        outputs to the async committer OUTSIDE _res_lock (its capacity
        wait must not stall residency), in one call.  The committer
        dedups per data_id and drains on its byte watermark, so a tile
        rewritten by a later task commits its FINAL version once; the
        version guard drops anything superseded in flight.  A sticky
        committer error re-raises here and propagates to the caller's
        _fail_task_pool discipline: pool failure, not a hang.
        The callers leave out a scratch tile (it has no home to go to)
        and, where the task's builder knows the DAG (``_tpu_home``), a
        version that a later task overwrites; ``last`` says such a last
        version is among ``going``: it has no later one to wait for, so
        the committer starts on it now, below its watermark (which
        exists to let a tile that is rewritten commit once).  Returns
        the number handed over."""
        com = self._wb_committer()
        if com is None:
            return 0
        if going:
            com.enqueue_all(going, self._span_pool, self._span_batch,
                            kick=last)
        elif last:
            com.kick()
        return len(going)

    def _commit_chunk(self, staged, outs, nout: int, es, complete: bool,
                      sp) -> None:
        """The epilog of one chunk of a wave: ONE hold of the residency
        lock commits every output of every task (``_commit_output``, as
        the per-task epilog does) and releases the scratch tiles whose
        last user was here; outside the lock the outputs that go home
        reach the committer in ONE call; then, and only then, the tasks
        complete, in order (a task's outputs are committed before its
        ``complete_execution``).  The tools' sites fire as they did — an
        epilog a task, a bump an output, a ticket an enqueue — asked once
        a chunk whether anybody listens.

        Once the commit has begun nothing here may raise: an error fails
        the pool loudly and marks the chunk's tasks completed, so that
        the per-task fallback neither retries (double-apply) nor hangs."""
        from ..core import scheduling

        epilogs_heard = pins.active(pins.DEVICE_EPILOG_BEGIN)
        bumps_heard = pins.active(pins.DATA_VERSION_BUMP)
        # the k-th output of every task has one shape: its bytes, once
        sizes = [o.nbytes for o in outs[:nout]]
        going: List[Data] = []
        kick = False
        done: List[Task] = []
        try:
            with self._res_lock:
                for k, (task, _args, ospecs) in enumerate(staged):
                    if getattr(task.taskpool, "failed", False):
                        continue  # a sibling's failure already took the pool
                    task._tpu_effects = True
                    if epilogs_heard:
                        pins.fire(pins.DEVICE_EPILOG_BEGIN, None, task)
                    home = task._tpu_home
                    at = k * nout
                    for j, (pos, data) in enumerate(ospecs):
                        self._commit_output(data, outs[at + j], sizes[j],
                                            bumps_heard)
                        if data.scratch is None \
                                and (home is None or pos in home):
                            going.append(data)
                    if home:
                        kick = True
                    if task._tpu_scratch:
                        self._release_scratch(task._tpu_scratch)
                    done.append(task)
                if self._zone is None:
                    self._reserve(0)
            self.stats["wave_commits"] += 1
            sp.note(n=len(done), outs=len(done) * nout,
                    home=self._send_home(going, kick))
        except Exception as e:
            debug.error("wave epilog of %d x %r failed: %s",
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
                debug.error("wave completion of %r failed: %s", task, e)
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
                if advice == ADVICE_PREFETCH:
                    if data.newest_copy() is None:
                        return  # nothing materialized yet: hint, not a command
                    self._stage_in(data)
                else:
                    mine = data.get_copy(self.data_index)
                    if mine is not None and mine.payload is not None:
                        self._lru_touch(
                            data, dirty=mine.coherency is Coherency.OWNED)
        else:
            super().data_advise(data, advice)

    def drop_residency(self, data: Data) -> None:
        """Release ``data``'s residency slot WITHOUT a host write-back:
        ownership of the device array passes to the caller (who already
        holds the payload).  The counterpart of the reference's
        data_advise release path for benchmark/driver code that reads a
        result and hands the buffer on — without this, every completed
        run's output stays dirty-resident until LRU pressure forces a
        full D2H write-back."""
        with self._lock, self._res_lock:
            self._lru_clean.pop(data.data_id, None)
            self._lru_dirty.pop(data.data_id, None)
            self._drop_copy(data, evicted=False)  # handed over, not evicted

    # ------------------------------------------------------------------
    def resident_data(self, task: Task) -> int:
        total = 0
        for spec in task.body_args or ():
            if spec[0] != "data" or spec[1] is None:
                continue
            c = spec[1].get_copy(self.data_index)
            newest = spec[1].newest_copy()
            if c is not None and c.payload is not None and (newest is None or c.version >= newest.version):
                total += c.nbytes
        return total

    def detach(self) -> None:
        with self._span("dev:detach"):
            self._detach()

    def _detach(self) -> None:
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
        with self._res_lock:
            # flush remaining dirty tiles home as ONE batched device->host
            # get (satellite 2) — the version guard makes tiles the
            # committer already landed a no-op, so each dirty tile
            # commits exactly once
            # (a scratch tile has no home: it is dropped, never copied)
            self._writeback_batch([d for d in self._lru_dirty.values()
                                   if d.scratch is None])
            self._lru_dirty.clear()
            self._lru_clean.clear()
            # release residency ACCOUNTING with the LRUs: the payloads stay
            # attached to their Data objects (a later stage-in reuses them,
            # unaccounted — same rule as externally pre-placed copies), but a
            # slot no LRU tracks can never be evicted, so leaving it charged
            # would leak phantom hbm_used across device reuse (the shared
            # `device=` amortization pattern) until eviction stops working
            if self._zone is not None:
                for (off, _nb) in self._offsets.values():
                    self._zone.release(off)
                self._offsets.clear()
                self.hbm_used = self._zone.used
            else:
                self._accounted.clear()
                self.hbm_used = 0


def device_body(chore, fn):
    """Attach the raw functional body to an accelerator chore."""
    chore.body_fn = fn
    return chore
