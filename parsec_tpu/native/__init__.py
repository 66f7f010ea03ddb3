"""Native C++ runtime core (ctypes bindings).

The reference's runtime core is native C; this package provides the
TPU framework's native core — a C++ shared library built on demand from
``native/src/`` and bound via ctypes (no pybind11 in this image):

* :class:`ZoneAllocator` — first-fit offset allocator with coalescing,
  the HBM-budget manager behind the TPU device module (reference role:
  ``parsec/utils/zone_malloc.c``; redesigned around offsets since PJRT
  owns the actual device memory).
* :class:`NativeGraph` — dependency-counting dataflow engine with a
  priority pool, keep-next-task fast path, streaming (DTD-style)
  insertion, native worker threads, and a fast priority-respecting
  topological ``order()`` used for whole-DAG XLA lowering (reference
  role: ``parsec/scheduling.c`` + ``mca/sched``).

``available()`` reports whether the toolchain produced the library.
Consumers that merely prefer it (graph ordering, the binary tracer) have
a pure-Python path; whoever ASKED for the native engine —
``NativeExecutor``, pump mode, the ``sched_native_queue`` mirror, the HBM
zone of a device bound to a real chip — raises when it is missing.
"""

from __future__ import annotations

import ctypes
import itertools
import os
import subprocess
import threading
from typing import Any, Callable, List, Optional

from ..profiling import pins
from . import abi
from .abi import ASYNC_BODY_FN, BODY_FN

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(os.path.dirname(_HERE))
_SRC_DIR = os.path.join(_REPO, "native", "src")
_BUILD_DIR = os.path.join(_REPO, "native", "build")
#: PARSEC_TPU_NATIVE_TSAN=1 selects the ThreadSanitizer build flavor:
#: same sources, ``-fsanitize=thread``, its own .so so the flavors never
#: clobber each other.  Run the process under the sanitizer runtime
#: (``LD_PRELOAD=libtsan.so`` or a tsan-instrumented interpreter) with
#: ``TSAN_OPTIONS=suppressions=native/tsan.supp`` (see docs/USERGUIDE
#: §10 "Checking your runtime").
_TSAN = bool(os.environ.get("PARSEC_TPU_NATIVE_TSAN"))
_TSAN_SUPP = os.path.join(_REPO, "native", "tsan.supp")

_lib = None
_lib_lock = threading.Lock()
_build_error: Optional[str] = None

_CXX = "g++"
_FLAGS = ["-O2", "-g", "-std=c++17", "-fPIC", "-shared", "-pthread"]
_TSAN_FLAGS = ["-fsanitize=thread"]

#: every C entry point the bindings require — a DERIVED view of the
#: declarative ABI contract (:mod:`parsec_tpu.native.abi`; one spec
#: generates the bindings, this list, and the engine-verify ABI lint).
#: Checked explicitly at load so a library that drifted from the spec
#: produces ONE readable error via :func:`build_error` instead of a
#: ctypes ``AttributeError`` deep inside a consumer.
#: ``missing_symbols()`` is the CI smoke hook.
REQUIRED_SYMBOLS = abi.required_symbols()


def build_command(tsan: bool = _TSAN) -> List[str]:
    """Compiler + flags of one flavor (everything but ``-o`` and the
    sources)."""
    return [_CXX, *_FLAGS, *(_TSAN_FLAGS if tsan else [])]


def lib_path(tsan: bool = _TSAN) -> str:
    """Path of the shared library for THIS source tree and flavor.  The
    file name carries :func:`abi.source_digest`, so the identity of what
    loads is the content it was built from: a library left behind by
    other sources or flags (``native/build/`` is git-ignored but travels
    with a copied directory) has another name and is never opened."""
    stem = "libparsec_core_tsan" if tsan else "libparsec_core"
    digest = abi.source_digest(build_command(tsan))
    return os.path.join(_BUILD_DIR, f"{stem}-{digest}.so")


def compiler_version() -> str:
    """First line of ``g++ --version`` (the toolchain the library is
    built with), or a description of why it cannot be run."""
    try:
        proc = subprocess.run([_CXX, "--version"], capture_output=True,
                              text=True, timeout=30)
    except (OSError, subprocess.TimeoutExpired) as e:
        return f"{_CXX} unavailable: {e}"
    return (proc.stdout.splitlines() or [f"{_CXX}: no output"])[0]


def build_library(*, tsan: bool = _TSAN, force: bool = False,
                  timeout: int = 300) -> str:
    """Compile ``native/src`` into :func:`lib_path` unless that exact
    content-addressed file already exists (``force`` recompiles
    regardless).  Per-process temp file + atomic publish: concurrent
    builds (multi-process TCP ranks on one host) cannot interleave.
    Returns the path; raises RuntimeError with the compiler output on
    failure.  Does not load the library."""
    srcs = [os.path.join(_SRC_DIR, s) for s in abi.SOURCES]
    missing = [s for s in srcs if not os.path.exists(s)]
    if missing:
        raise RuntimeError(f"sources missing under {_SRC_DIR}: {missing}")
    out_path = lib_path(tsan)
    if os.path.exists(out_path) and not force:
        return out_path
    os.makedirs(_BUILD_DIR, exist_ok=True)
    tmp = f"{out_path}.{os.getpid()}.tmp"
    cmd = [*build_command(tsan), "-o", tmp, *srcs]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=timeout)
    except (OSError, subprocess.TimeoutExpired) as e:
        raise RuntimeError(f"{_CXX} invocation failed: {e}")
    if proc.returncode != 0:
        raise RuntimeError(f"{_CXX} failed:\n{proc.stderr[-2000:]}")
    os.replace(tmp, out_path)
    return out_path


def _build() -> Optional[str]:
    """Compile the shared library if missing; returns its path or None
    (recording the failure for diagnostics)."""
    global _build_error
    if os.environ.get("PARSEC_TPU_NATIVE_DISABLE"):
        # CI fallback-path leg / debugging: pretend no toolchain exists so
        # every consumer exercises its pure-Python path
        _build_error = "disabled via PARSEC_TPU_NATIVE_DISABLE"
        return None
    try:
        return build_library()
    except RuntimeError as e:
        _build_error = str(e)
        return None


def _load():
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        path = _build()
        if path is None:
            return None
        lib = ctypes.CDLL(path)
        missing = [s for s in REQUIRED_SYMBOLS if not hasattr(lib, s)]
        if missing:
            global _build_error
            _build_error = (
                f"native library {path} lacks symbol(s) "
                f"{', '.join(missing)} that parsec_tpu.native.abi "
                "declares: the spec and native/src have drifted")
            return None
        # restype/argtypes for every entry point are GENERATED from the
        # declarative ABI contract — the spec that also feeds
        # REQUIRED_SYMBOLS and the engine-verify ABI lint, so bindings
        # cannot drift from what the lint certifies
        abi.bind(lib)
        _lib = lib
        return lib


def missing_symbols() -> List[str]:
    """Symbols from :data:`REQUIRED_SYMBOLS` absent from the built
    library (empty when healthy).  The build smoke test asserts this is
    empty so spec/source drift fails CI with a readable message."""
    lib = _load()
    if lib is None:
        return list(REQUIRED_SYMBOLS)
    return [s for s in REQUIRED_SYMBOLS if not hasattr(lib, s)]


def available() -> bool:
    return _load() is not None


def tsan_suppressions_path() -> str:
    """The shipped suppressions file for the TSan flavor (pass as
    ``TSAN_OPTIONS=suppressions=<path>``)."""
    return _TSAN_SUPP


def build_tsan_library(timeout: int = 300) -> str:
    """Compile the ThreadSanitizer flavor (the CI smoke leg: "the TSan
    build of the async engine still compiles").  Returns the .so path;
    raises RuntimeError with the compiler output when the toolchain
    lacks ``-fsanitize=thread`` or the sources fail under its
    instrumentation.  Does NOT load the library into this process — a
    TSan .so needs the sanitizer runtime preloaded."""
    return build_library(tsan=True, timeout=timeout)


def build_error() -> Optional[str]:
    _load()
    return _build_error


class ZoneAllocator:
    """Offset allocator over a byte budget (native first-fit + coalesce)."""

    def __init__(self, capacity: int):
        lib = _load()
        if lib is None:
            raise RuntimeError(f"native core unavailable: {_build_error}")
        self._lib = lib
        self._z = lib.pz_zone_new(capacity)
        if not self._z:
            raise MemoryError("zone allocation failed")

    def _handle(self):
        """The live native zone.  A closed allocator RAISES: passing the
        cleared handle on would dereference NULL inside the library —
        reachable in practice when the cycle collector finalizes this
        object before a device whose ``detach()`` still consults it (a
        SIGSEGV in ``pthread_mutex_lock`` on the chip)."""
        z = self._z
        if not z:
            raise RuntimeError("zone allocator is closed")
        return z

    def alloc(self, nbytes: int, align: int = 256) -> Optional[int]:
        """Returns a byte offset, or None when fragmented/full."""
        off = self._lib.pz_zone_alloc(self._handle(), nbytes, align)
        return None if off < 0 else off

    def release(self, offset: int) -> None:
        if self._lib.pz_zone_release(self._handle(), offset) != 0:
            raise ValueError(f"unknown offset {offset}")

    @property
    def used(self) -> int:
        return self._lib.pz_zone_used(self._handle())

    @property
    def capacity(self) -> int:
        return self._lib.pz_zone_capacity(self._handle())

    @property
    def largest_free(self) -> int:
        return self._lib.pz_zone_largest_free(self._handle())

    @property
    def num_live(self) -> int:
        return self._lib.pz_zone_num_live(self._handle())

    def close(self) -> None:
        if getattr(self, "_z", None):
            self._lib.pz_zone_destroy(self._z)
            self._z = None

    def __del__(self):  # pragma: no cover
        try:
            self.close()
        except Exception:
            pass


class NativeGraph:
    """Dataflow graph executed (or ordered) by the native engine.

    Two usage modes:
      * build-then-``order()`` — linearise a static DAG for whole-graph
        XLA lowering (no commit/seal needed);
      * ``add_task``/``add_dep``/``commit`` + ``seal`` + ``run(body)`` —
        execute with native worker threads; ``body(task_id, user_tag)``
        is a Python callable entered through a ctypes trampoline.
    """

    #: stable per-graph tokens for the hb site below — ``id(self)``
    #: would be reused after GC and collide sequential graphs' task ids
    #: in the checker's completion state (spurious RT005)
    _HB_TOKENS = itertools.count(1)

    def __init__(self):
        lib = _load()
        if lib is None:
            raise RuntimeError(f"native core unavailable: {_build_error}")
        self._lib = lib
        self._g = lib.pz_graph_new()
        self._n = 0
        self._keepalive: List = []
        self.hb_token = next(NativeGraph._HB_TOKENS)

    def add_task(self, priority: int = 0, user_tag: int = 0) -> int:
        self._n += 1
        return self._lib.pz_graph_add_task(self._g, priority, user_tag)

    def add_dep(self, pred: int, succ: int) -> bool:
        """True if the edge was recorded, False if pred already ran."""
        rc = self._lib.pz_graph_add_dep(self._g, pred, succ)
        if rc < 0:
            raise ValueError(f"bad task id in edge {pred}->{succ}")
        return rc == 1

    def commit(self, task_id: int) -> None:
        self._lib.pz_graph_task_commit(self._g, task_id)

    def add_bulk(self, prio, tenant: int, pred, succ) -> int:
        """Declare ``len(prio)`` tasks (user tag = position, one tenant)
        and the edges ``pred[i] -> succ[i]``, which count from the first
        of them, in ONE call; returns the first task's id.  ``prio`` is a
        ``ctypes.c_int32`` array, ``pred``/``succ`` ``ctypes.c_int64``
        arrays (an attach plan keeps them ready).  Nothing is committed:
        :meth:`commit_range` arms the tasks."""
        n = len(prio)
        base = self._lib.pz_graph_add_bulk(self._g, n, prio, int(tenant),
                                           len(pred), pred, succ)
        if base < 0:
            raise ValueError("bad task id in a bulk edge")
        self._n += n
        return base

    def commit_range(self, first: int, n: int) -> None:
        self._lib.pz_graph_commit_range(self._g, first, n)

    def seal(self) -> None:
        self._lib.pz_graph_seal(self._g)

    POLICIES = {"lfq": 0, "gd": 1}

    def set_policy(self, policy: str) -> None:
        """Scheduling policy: ``lfq`` (per-worker bounded heaps +
        hierarchical steal — reference sched/lfq hbbuffers, the default)
        or ``gd`` (single global priority heap — reference sched/gd)."""
        self._lib.pz_graph_set_policy(self._g, self.POLICIES[policy])

    @property
    def steals(self) -> int:
        return self._lib.pz_graph_steals(self._g)

    @property
    def steals_remote(self) -> int:
        """Cross-VP subset of ``steals`` (0 without a vpmap)."""
        return self._lib.pz_graph_steals_remote(self._g)

    def reset(self) -> None:
        """Rewind a QUIESCED graph for re-execution over the same
        structure: every task returns to uncommitted; the caller
        re-commits exactly as after construction.  Amortizes graph
        construction across repeated same-shape runs (the reference's
        compile-time generated structures play this role)."""
        if self._lib.pz_graph_reset(self._g) != 0:
            raise RuntimeError("cannot reset: tasks still outstanding")

    def set_vpmap(self, vp_of_worker) -> None:
        """Assign each worker id (of the NEXT ``run``) to a VP/locality
        domain: the steal path walks same-VP victims first, then crosses
        domains (reference lfq hbbuffer hierarchy + vpmap,
        ``sched_local_queues_utils.h:22-36``)."""
        n = len(vp_of_worker)
        arr = (ctypes.c_int32 * n)(*[int(v) for v in vp_of_worker])
        self._lib.pz_graph_set_vpmap(self._g, arr, n)

    def run_noop(self, nthreads: int = 2) -> int:
        """Dispatch-bound run with a NATIVE no-op body (no GIL): isolates
        pure scheduling throughput for benchmarks."""
        n = self._lib.pz_graph_run_noop(self._g, nthreads)
        if n < 0:
            raise RuntimeError("graph did not quiesce")
        return n

    def run(self, body: Callable[[int, int], None], nthreads: int = 2) -> int:
        """Execute until quiescence; returns executed count. Exceptions
        in ``body`` are captured and re-raised after the run drains."""
        errors: List[BaseException] = []

        @BODY_FN
        def trampoline(task_id, user_tag, _ctx):
            try:
                body(task_id, user_tag)
            except BaseException as e:  # noqa: BLE001 - relayed to caller
                errors.append(e)

        self._keepalive.append(trampoline)
        n = self._lib.pz_graph_run(self._g, trampoline, None, nthreads)
        if errors:
            raise errors[0]
        if n < 0:
            raise RuntimeError("graph did not quiesce (cycle or uncommitted task)")
        return n

    def run_async(self, body: Callable[[int, int], Any],
                  nthreads: int = 2) -> int:
        """Execute with an ASYNC-capable body (the reference's
        PARSEC_HOOK_RETURN_ASYNC protocol): ``body(task_id, user_tag)``
        returns falsy when the task completed synchronously, truthy when
        a device manager took ownership — its completion must then be
        signalled via :meth:`task_done`, which runs successor release
        natively.  Blocks until every task (async included) completed.
        A raising body aborts the run (:meth:`fail`) so completions that
        will never arrive cannot hang the workers."""
        errors: List[BaseException] = []

        @ASYNC_BODY_FN
        def trampoline(task_id, user_tag, _ctx):
            try:
                return 1 if body(task_id, user_tag) else 0
            except BaseException as e:  # noqa: BLE001 - relayed to caller
                errors.append(e)
                self._lib.pz_graph_fail(self._g)
                # report ASYNC, not done: an enqueue body may raise AFTER
                # its task already completed through task_done (an inline
                # manager drain completes tasks before returning) — a 0
                # here would complete() it a second time and double-release
                # successors.  The fail() above aborts the run either way.
                return 1

        self._keepalive.append(trampoline)
        n = self._lib.pz_graph_run_async(self._g, trampoline, None, nthreads)
        if errors:
            raise errors[0]
        if n < 0:
            raise RuntimeError(
                "graph did not quiesce (cycle, uncommitted task, or a "
                "failed run with async completions outstanding)")
        return n

    def task_done(self, task_id: int) -> bool:
        """Signal an ASYNC task's completion: dependency release,
        ready-queue pushes and quiescence accounting all run natively
        (``pz_task_done``).  Callable from any thread.  Returns False if
        the task had already completed, or if the graph was already
        closed (a straggler callback racing shutdown — harmless either
        way, never a NULL handle into C); raises on an unknown id."""
        g = self._g  # snapshot: close() may null it under our feet
        if not g:
            return False
        rc = self._lib.pz_task_done(g, task_id)
        if rc == -1:
            raise ValueError(f"task_done: unknown task id {task_id}")
        if pins.active(pins.NATIVE_TASK_DONE):
            # happens-before site: one ASYNC completion entered the
            # native engine.  accepted=False records a signal the
            # double-complete guard refused — the hb checker flags two
            # ACCEPTED completions for one task as RT005
            pins.fire(pins.NATIVE_TASK_DONE, None,
                      {"graph": self.hb_token, "task": int(task_id),
                       "accepted": rc == 0})
        return rc == 0

    # ---- zero-interpreter lifecycle (pump mode) ----------------------
    #
    # The batched control-plane API behind NativeExecutor's pump: ONE
    # ctypes call pops a batch of ready ids, ONE call retires the batch
    # (dep decrements + ready pushes + quiescence counting all native),
    # and an optional event drain republishes the lifecycle into PINS.

    #: lifecycle event kinds from :meth:`events_drain` (graph.cpp EvtKind)
    EVT_DEP_DEC, EVT_PUBLISH, EVT_RETIRE = 0, 1, 2

    SCHED_POLICIES = {"prio": 0, "wdrr": 1}

    def sched_config(self, policy: str = "prio", quantum: int = 0,
                     seed: int = -1) -> None:
        """Route ready pushes/pops through the native pump scheduler.
        ``prio`` pops (priority desc, insertion seq asc) — the spq order;
        ``wdrr`` runs weighted deficit round robin over tenant bins (see
        :meth:`set_task_tenant`/:meth:`set_tenant_weight`); ``seed >= 0``
        applies the schedule explorer's deterministic pop-order
        perturbation.  Must be called BEFORE tasks commit."""
        self._lib.pz_graph_sched_config(
            self._g, self.SCHED_POLICIES[policy], int(quantum), int(seed))

    def set_task_tenant(self, task_id: int, tenant: int) -> None:
        self._lib.pz_graph_task_tenant(self._g, task_id, int(tenant))

    def set_tenant_weight(self, tenant: int, weight: int) -> None:
        self._lib.pz_graph_tenant_weight(self._g, int(tenant), int(weight))

    def pop_batch(self, buf) -> int:
        """Pop up to ``len(buf)`` ready ids into ``buf`` (a preallocated
        ``ctypes.c_int64`` array); returns the count (0 = none ready)."""
        return self._lib.pz_graph_pop_batch(self._g, buf, len(buf))

    def done_batch(self, buf, n: int) -> int:
        """Retire ``buf[:n]`` in one native call — successor release,
        ready pushes and retire counting never enter the interpreter.
        Returns the number accepted (double completions are refused per
        task and counted in :attr:`double_completes`)."""
        g = self._g
        if not g:
            return 0
        return self._lib.pz_graph_done_batch(g, buf, n)

    def quiesced(self) -> bool:
        return bool(self._lib.pz_graph_quiesced(self._g))

    def sched_pending(self) -> int:
        return self._lib.pz_graph_sched_pending(self._g)

    def events_enable(self, on: bool) -> None:
        self._lib.pz_graph_events_enable(self._g, 1 if on else 0)

    def events_drain(self, kinds, a, b) -> int:
        """Drain buffered lifecycle events into three preallocated
        parallel ctypes arrays (c_int32 kinds, c_int64 a/b); returns the
        count.  Kinds: :data:`EVT_DEP_DEC` (a=succ id, b=ready),
        :data:`EVT_PUBLISH` (a=task id, b=priority), :data:`EVT_RETIRE`
        (a=task id, b=accepted)."""
        return self._lib.pz_graph_events_drain(self._g, kinds, a, b,
                                               len(kinds))

    def fail(self) -> None:
        """Abort a live run: workers drain their current body and exit;
        ``run``/``run_async`` then reports non-quiescence.  Use when an
        ASYNC completion can no longer arrive (failed device pool).
        No-op on a closed graph."""
        g = self._g
        if g:
            self._lib.pz_graph_fail(g)

    def order(self) -> List[int]:
        """Priority-greedy topological order of a build-mode graph."""
        buf = (ctypes.c_int64 * max(self._n, 1))()
        n = self._lib.pz_graph_order(self._g, buf, self._n)
        if n < 0:
            raise RuntimeError("cycle detected (or graph already executed)")
        return list(buf[:n])

    @property
    def executed(self) -> int:
        return self._lib.pz_graph_executed(self._g)

    @property
    def double_completes(self) -> int:
        """Signals the double-complete guard refused (0 on a healthy
        run — the hb-check harness pins this; a nonzero value means a
        completion path signalled one task twice and the atomic claim
        saved the run)."""
        g = self._g or getattr(self, "_closed_handle", None)
        return self._lib.pz_graph_double_completes(g) if g else 0

    def close(self) -> None:
        """Detach: further run/task_done/fail calls no-op or raise.  The
        native graph is destroyed only when this object is garbage-
        collected (same discipline as :meth:`NativeTracer.close`): a
        straggler completion thread racing close() necessarily still
        holds a reference via its bound ``task_done`` callback, so its
        handle snapshot can never touch freed memory."""
        g = getattr(self, "_g", None)
        if g:
            self._g = None
            self._closed_handle = g

    def __del__(self):  # pragma: no cover
        try:
            g = getattr(self, "_g", None) or getattr(
                self, "_closed_handle", None)
            if g:
                self._g = None
                self._closed_handle = None
                self._lib.pz_graph_destroy(g)
        except Exception:
            pass


class NativeReadyQueue:
    """Standalone native ready queue — the queue STATE of a Python
    scheduler, with pop ORDER decided natively (one shared implementation
    with the pump disciplines in graph.cpp, so worker-based and
    pump-based runs order identically).

    Ownership handoff: the caller keeps its task objects in a dict keyed
    by the integer ``handle`` it pushes; :meth:`pop` returns the handle
    whose task the caller then owns again.  ``policy``: ``prio`` orders
    (priority desc, distance asc, insertion seq asc) — the spq key;
    ``wdrr`` runs deficit round robin over tenant bins."""

    def __init__(self, policy: str = "prio", quantum: int = 0,
                 seed: int = -1):
        lib = _load()
        if lib is None:
            raise RuntimeError(f"native core unavailable: {_build_error}")
        self._lib = lib
        self._q = lib.pz_rq_new(NativeGraph.SCHED_POLICIES[policy],
                                int(quantum), int(seed))
        if not self._q:
            raise MemoryError("pz_rq_new failed")

    def set_tenant_weight(self, tenant: int, weight: int) -> None:
        self._lib.pz_rq_tenant_weight(self._q, int(tenant), int(weight))

    def push(self, priority: int, handle: int, distance: int = 0,
             tenant: int = 0) -> None:
        self._lib.pz_rq_push(self._q, int(priority), int(distance),
                             int(tenant), int(handle))

    def pop(self) -> int:
        """Next handle under the discipline, or -1 when empty."""
        return self._lib.pz_rq_pop(self._q)

    def count(self) -> int:
        return self._lib.pz_rq_count(self._q)

    def clear(self) -> None:
        self._lib.pz_rq_clear(self._q)

    def close(self) -> None:
        q = getattr(self, "_q", None)
        if q:
            self._q = None
            self._lib.pz_rq_destroy(q)

    def __del__(self):  # pragma: no cover
        try:
            self.close()
        except Exception:
            pass


class NativeTracer:
    """Binary event tracer with native per-stream buffers and
    steady-clock nanosecond timestamps (reference role:
    ``parsec/profiling.c`` per-thread dbp buffers).

    A stream is claimed per thread on first log; dumping produces a
    ``PBTRACE1`` binary file readable by
    :func:`parsec_tpu.profiling.binary.read_pbt`.  Keyword names live
    Python-side (:class:`parsec_tpu.profiling.binary.BinaryTrace` pairs
    the dump with a sidecar).
    """

    PHASE_BEGIN, PHASE_END, PHASE_INSTANT, PHASE_COUNTER = 0, 1, 2, 3

    def __init__(self):
        lib = _load()
        if lib is None:
            raise RuntimeError(f"native core unavailable: {_build_error}")
        self._lib = lib
        self._t = lib.pt_tracer_new()
        if not self._t:
            raise MemoryError("pt_tracer_new failed")
        self._tls = threading.local()
        self._streams_lock = threading.Lock()
        self._stream_names: List[str] = []

    def _stream(self, t=None):
        s = getattr(self._tls, "s", None)
        if s is None:
            s = self._lib.pt_stream_new(t if t is not None else self._t)
            if not s:
                raise MemoryError("pt_stream_new failed")
            self._tls.s = s
            # place the name at the NATIVE stream id: two threads racing
            # their first log must not cross-label each other's events
            sid = self._lib.pt_stream_id(s)
            with self._streams_lock:
                while len(self._stream_names) <= sid:
                    self._stream_names.append("")
                self._stream_names[sid] = threading.current_thread().name
        return s

    def log(self, keyword: int, phase: int, event_id: int = 0, info: int = 0) -> None:
        # close() only detaches the handle (native buffers are destroyed
        # when this object is collected, see close()): snapshotting the
        # handle here makes a concurrent close() safe — a straggler logger
        # (e.g. a PINS callback still subscribed during shutdown) either
        # sees None and no-ops, or logs into still-live native memory
        t = self._t
        if t is None:
            return
        self._lib.pt_log(t, self._stream(t), keyword, phase, event_id, info)

    def stream_names(self) -> List[str]:
        with self._streams_lock:
            return list(self._stream_names)

    @property
    def total_events(self) -> int:
        if self._t is None:
            return 0
        return self._lib.pt_total_events(self._t)

    def dump(self, path: str) -> int:
        if self._t is None:
            raise OSError("tracer is closed")
        n = self._lib.pt_dump(self._t, path.encode())
        if n < 0:
            raise OSError(f"cannot write trace to {path}")
        return n

    def close(self) -> None:
        """Detach: further log/dump calls no-op/raise.  The native buffers
        are destroyed only when this object is garbage-collected — a
        concurrently-racing logger thread (which necessarily still holds a
        reference via its bound callback) can therefore never touch freed
        memory."""
        t = getattr(self, "_t", None)
        if t:
            self._t = None
            self._closed_handle = t

    def __del__(self):  # pragma: no cover
        try:
            t = getattr(self, "_t", None) or getattr(self, "_closed_handle", None)
            if t:
                self._t = None
                self._closed_handle = None
                self._lib.pt_tracer_destroy(t)
        except Exception:
            pass
