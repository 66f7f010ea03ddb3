"""Native device dispatch: TPU chores driven from the C++ hot loop.

The tentpole contract (ISSUE 3): with ``native_device=True`` the native
worker's trampoline only ENQUEUES device work (chore returns ASYNC) and
the device manager's completion callback signals ``pz_task_done`` —
dependency counting, ready-queue ops and successor release never
re-enter the interpreter.  Pinned here by PINS assertions (the release/
schedule sites stay silent while per-task EXEC spans carry wave
metadata), plus correctness, mixed-DAG coherency, failure containment,
and critical-path attribution over a real native-dispatched trace.

Runs on the JAX CPU backend (same machinery, virtual device) — tier-1.
"""

import numpy as np
import pytest

from parsec_tpu import native
from parsec_tpu.core.lifecycle import AccessMode
from parsec_tpu.profiling import pins

pytestmark = pytest.mark.skipif(
    not native.available(),
    reason=f"native core unavailable: {native.build_error()}")


def _spd(n, dtype=np.float64, seed=0):
    rng = np.random.default_rng(seed)
    m = rng.standard_normal((n, n)).astype(dtype)
    return m @ m.T + n * np.eye(n, dtype=dtype)


def _dpotrf_taskpool(n, nb, seed=0):
    from parsec_tpu.datadist import TiledMatrix
    from parsec_tpu.ops.cholesky import cholesky_ptg

    S = _spd(n, seed=seed)
    A = TiledMatrix(n, n, nb, nb, name="A", dtype=np.float64).from_array(S)
    tp = cholesky_ptg(use_tpu=True, use_cpu=False).taskpool(NT=A.mt, A=A)
    return S, A, tp


def test_native_device_cholesky_matches_numpy():
    """Device-only dpotrf through the native engine: every task's body
    dispatches via the TpuDevice manager; numerics must be f64-exact."""
    from parsec_tpu.dsl.native_exec import run_native

    S, A, tp = _dpotrf_taskpool(128, 16)
    ran = run_native(tp, nthreads=4, native_device=True)
    assert ran == 120
    L = np.tril(A.to_array())
    np.testing.assert_allclose(L @ L.T, S, rtol=1e-10, atol=1e-10)


def test_native_device_taskpool_run_native_plumb():
    """The option plumbs through the taskpool API surface too
    (PTGTaskpool.run_native / .capture)."""
    S, A, tp = _dpotrf_taskpool(96, 32, seed=3)
    g = tp.capture(ranks=[0])
    assert len(g.nodes) == 10  # NT=3: 3 potrf + 3 trsm + 3 syrk + 1 gemm
    ran = tp.run_native(nthreads=2, native_device=True)
    assert ran == len(g.nodes)
    L = np.tril(A.to_array())
    np.testing.assert_allclose(L @ L.T, S, rtol=1e-10, atol=1e-10)


def test_native_device_no_python_release_deps():
    """THE acceptance pin, tightened from two interpreter entries per
    task to ZERO: during a pump-mode run no per-task Python fires at
    all between attach and drain — no enqueue trampoline, no completion
    callback, no dependency release, no scheduling.  The Python pump
    makes O(batches) ctypes calls (``pz_graph_pop_batch`` /
    ``pz_graph_done_batch``) and the executor's counters prove the
    per-task entry points were never taken.  EXEC spans still fire once
    per task from the device manager, carrying wave metadata; the
    RELEASE_DEPS_BEGIN and SCHEDULE sites (the dynamic runtime's Python
    release path) stay completely silent."""
    from parsec_tpu.dsl.native_exec import NativeExecutor

    S, A, tp = _dpotrf_taskpool(256, 32, seed=1)
    counts = {}
    waves = []

    def counter(site):
        def cb(es, payload):
            counts[site] = counts.get(site, 0) + 1
        return cb

    silent_sites = (pins.RELEASE_DEPS_BEGIN, pins.SCHEDULE_BEGIN,
                    pins.SCHEDULE_END, pins.PREPARE_INPUT_BEGIN)
    for site in silent_sites + (pins.EXEC_BEGIN, pins.EXEC_END,
                                pins.COMPLETE_EXEC_BEGIN):
        pins.subscribe(site, counter(site))

    def on_exec(es, task):
        waves.append(task.prof.get("wave"))
    pins.subscribe(pins.EXEC_BEGIN, on_exec)

    try:
        ex = NativeExecutor(tp, native_device=True)
        assert ex._pump, "all-device dpotrf must select pump mode"
        ran = ex.run(nthreads=4)
        dev = ex.device
        stats = dict(ex.stats)
        ex.close()
    finally:
        pins.clear()

    assert ran == 120
    for site in silent_sites:
        assert counts.get(site, 0) == 0, f"{site} fired on the native path"
    # ZERO interpreter entries per task: neither legacy path was taken,
    # and the pump really ran (batched, so far fewer pops than tasks)
    assert stats["trampoline_entries"] == 0
    assert stats["completion_callbacks"] == 0
    assert 1 <= stats["pop_batches"] < 120
    assert stats["pumped_tasks"] == 120
    # per-task EXEC spans from the device manager, completion spans from
    # the batched native retirement
    assert counts[pins.EXEC_BEGIN] == 120
    assert counts[pins.EXEC_END] == 120
    assert counts[pins.COMPLETE_EXEC_BEGIN] == 120
    # the progress currency still moves (batched task_done_batch)
    assert tp.nb_retired == 120
    # wave metadata: batched dispatch really happened, and singles are
    # distinguishable (wave == 0)
    assert dev.stats.get("wave_tasks", 0) > 0
    batched = [w for w in waves if w]
    assert batched and all(w >= 1 for w in batched)
    assert sum(1 for w in waves if w) == dev.stats["wave_tasks"]


def test_native_device_mixed_dag_stays_coherent():
    """A device class feeding a CPU-only class: the CPU fallback stages
    through the Data discipline, and the device's detach must NOT roll a
    newer host version back (the write-back version guard)."""
    from parsec_tpu.data import LocalCollection
    from parsec_tpu.dsl.dtd import stage_to_cpu
    from parsec_tpu.dsl.native_exec import run_native
    from parsec_tpu.dsl.ptg import PTG

    coll = LocalCollection("B", shape=(4,), dtype=np.float32)
    ptg = PTG("mixed_native")
    d = ptg.task_class("d", i="0 .. 3")
    d.affinity("B(i)")
    d.flow("X", AccessMode.INOUT, "<- B(i)", "-> X c(i)")
    d.body(tpu=lambda X, i: X + 2.0)
    c = ptg.task_class("c", i="0 .. 3")
    c.affinity("B(i)")
    c.flow("X", AccessMode.INOUT, "<- X d(i)", "-> B(i)")

    def cpu_body(X, i):
        X *= 3.0

    c.body(cpu=cpu_body)
    ran = run_native(ptg.taskpool(B=coll), nthreads=2, native_device=True)
    assert ran == 8
    for i in range(4):
        np.testing.assert_allclose(stage_to_cpu(coll.data_of(i)), 6.0)


def test_native_device_failure_contained():
    """A raising device body fails the run loudly (pool fail → native
    abort) instead of hanging workers on a completion that never comes."""
    from parsec_tpu.data import LocalCollection
    from parsec_tpu.dsl.native_exec import run_native
    from parsec_tpu.dsl.ptg import PTG

    coll = LocalCollection("A", shape=(4,), dtype=np.float32)
    ptg = PTG("boom_native")
    tc = ptg.task_class("t", i="0 .. 3")
    tc.affinity("A(i)")
    tc.flow("X", AccessMode.INOUT, "<- A(i)", "-> A(i)")

    def dev_body(X, i):
        raise RuntimeError("device body exploded")

    tc.body(tpu=dev_body)
    with pytest.raises(RuntimeError, match="native device run failed"):
        run_native(ptg.taskpool(A=coll), nthreads=2, native_device=True)


def test_native_device_rebind_rejected():
    """rebind() on a device-mode executor fails loudly (Data bindings are
    build-time); the error names the supported amortization path."""
    from parsec_tpu.dsl.native_exec import NativeExecutor

    _S, _A, tp = _dpotrf_taskpool(96, 32, seed=5)
    ex = NativeExecutor(tp, native_device=True)
    try:
        with pytest.raises(NotImplementedError, match="device="):
            ex.rebind(tp)
    finally:
        ex.close()


def test_native_device_critpath_attributes_waves(tmp_path):
    """Observability satellite: a native-dispatched run under the
    per-rank tracer yields per-task exec spans (device manager EXEC
    pins) AND dependency edges (bulk pre-run emission), so
    profiling.critpath recovers a multi-task chain with real compute
    attribution — no host-gap hole where the device waves ran."""
    import json

    from parsec_tpu.dsl.native_exec import NativeExecutor
    from parsec_tpu.profiling import critpath
    from parsec_tpu.profiling.overlap import measure_overlap

    _S, _A, tp = _dpotrf_taskpool(128, 32, seed=2)
    stats = {}
    with measure_overlap(stats, trace_dir=str(tmp_path)):
        ex = NativeExecutor(tp, native_device=True)
        ex.run(nthreads=2)
        ex.close()
    with open(stats["merged_trace"]) as f:
        doc = json.load(f)
    rep = critpath.analyze(doc.get("traceEvents", []))
    # NT=4 dpotrf: the potrf chain alone is 4 deep; the analyzer must
    # recover a real dependency chain, not a single orphan span
    assert rep["n_tasks"] >= 4
    assert rep["buckets"]["compute_us"] > 0
    # device spans exist: no all-host-gap attribution.  The floor is
    # ABSOLUTE, not a fraction of wall: with the executable cache a
    # warm-process run no longer pays jit compiles inside its first
    # exec spans, so honest pure-compute spans are microseconds while
    # the fixed host costs around them are not.
    assert rep["buckets"]["compute_us"] > 100.0  # us: real device spans


def test_native_device_use_globals_value_order():
    """Regression (round-6 review): VALUE body_args must follow the
    positional contract params, defs, body_globals — a use_globals()
    device class bound its scalars out of order and silently computed
    with swapped values."""
    from parsec_tpu.data import LocalCollection
    from parsec_tpu.dsl.dtd import stage_to_cpu
    from parsec_tpu.dsl.native_exec import run_native
    from parsec_tpu.dsl.ptg import PTG

    coll = LocalCollection("A", shape=(2,), dtype=np.float32)
    ptg = PTG("globals_order")
    tc = ptg.task_class("t", k="0 .. 3")
    tc.affinity("A(k)")
    tc.flow("X", AccessMode.INOUT, "<- A(k)", "-> A(k)")
    tc.use_globals("G")

    def body(X, k, G):
        return X + 10.0 * k + G  # wrong binding would swap k and G

    tc.body(tpu=body)
    ran = run_native(ptg.taskpool(A=coll, G=100.0), nthreads=2,
                     native_device=True)
    assert ran == 4
    for k in range(4):
        np.testing.assert_allclose(stage_to_cpu(coll.data_of(k)),
                                   10.0 * k + 100.0)


# ---------------------------------------------------------------------------
# value arguments through the pump (device/value_args.py)
# ---------------------------------------------------------------------------

def _device_over(store_dir, exported):
    """A device whose executable cache takes the exported path for every
    program (``min_share_s`` 0 over a disk store, as
    ``tests/runtime/test_compile_bcast.py`` sets it up) or for none."""
    import types

    from parsec_tpu import compile_cache as cc
    from parsec_tpu.device.tpu import TpuDevice

    cache = cc.ExecutableCache(store=cc.DiskStore(str(store_dir)),
                               min_disk_s=0.0 if exported else 1e9)
    dev = TpuDevice(types.SimpleNamespace(rank=0, nranks=1, devices=[],
                                          compile_cache=cache), index=1)
    dev.attach()
    return dev


def _reading_taskpool():
    """A class whose body reads a parameter (``int``), a floating and a
    boolean global: all three ride the program's two host vectors."""
    from parsec_tpu.data import LocalCollection
    from parsec_tpu.dsl.ptg import PTG

    coll = LocalCollection("A", shape=(4,), dtype=np.float32)
    ptg = PTG("values_read")
    tc = ptg.task_class("t", k="0 .. 7")
    tc.affinity("A(k)")
    tc.flow("X", AccessMode.INOUT, "<- A(k)", "-> A(k)")
    tc.use_globals("G", "UP")

    def body(X, k, G, UP):
        import jax.numpy as jnp

        return jnp.where(UP, X + 10.0 * k + G, X)

    tc.body(tpu=body)
    return coll, ptg.taskpool(A=coll, G=0.5, UP=True)


@pytest.mark.parametrize("exported", [False, True],
                         ids=["plain_lowering", "exported_path"])
@pytest.mark.parametrize("graph", ["dpotrf_ignores_its_values",
                                   "body_reads_its_values"])
def test_native_device_values_dropped_or_packed(tmp_path, exported, graph):
    """Through the pump, on both ways a program is compiled: the dpotrf
    tile bodies read none of their parameters (NT=8: 8·1 + 28·2 + 28·2 +
    56·3 = 288 values, every one dropped, no scalar in any call); a body
    that reads its values gets them packed, with the numbers right."""
    from parsec_tpu.dsl.dtd import stage_to_cpu
    from parsec_tpu.dsl.native_exec import NativeExecutor

    dev = _device_over(tmp_path, exported)
    try:
        if graph == "dpotrf_ignores_its_values":
            S, A, tp = _dpotrf_taskpool(256, 32, seed=4)
        else:
            coll, tp = _reading_taskpool()
        ex = NativeExecutor(tp, native_device=True, device=dev)
        ran = ex.run(nthreads=2)
        ex.close()
        sigs = [sig for (cf, _plan, _exe) in dev._jit_cache.values()
                for sig in cf._memo]
        assert not any(e[0] == "s" for sig in sigs for e in sig), sigs
        if graph == "dpotrf_ignores_its_values":
            assert ran == 120
            L = np.tril(A.to_array())
            np.testing.assert_allclose(L @ L.T, S, rtol=1e-10, atol=1e-10)
            counted = (288, 0)
        else:
            assert ran == 8
            for k in range(8):
                np.testing.assert_array_equal(
                    stage_to_cpu(coll.data_of(k)),
                    np.float32(10.0 * k + 0.5))
            counted = (0, 24)
        assert (dev.stats["value_args_dropped"],
                dev.stats["value_args_packed"]) == counted
        assert dev.stats["value_args_positional"] == 0
        assert bool(dev._ccache.stats["bytes_written"]) == exported
    finally:
        dev.detach()


@pytest.mark.parametrize("handed_in", [True, False])
def test_a_finalizer_detaches_only_a_device_the_executor_made(handed_in):
    """An executor nobody closed is finalized wherever the collector
    runs — once on the write-back committer's own thread, where the
    detach's flush waited 300 s for itself under everybody else's flush.
    A device that was handed in (``device=``) is its sharers' to detach:
    the finalizer leaves its tiles and its committer alone.  A device the
    executor made itself is still flushed home and detached."""
    import gc

    from parsec_tpu.dsl.native_exec import NativeExecutor

    dev = NativeExecutor._make_device() if handed_in else None
    S, A, tp = _dpotrf_taskpool(64, 16, seed=5)
    ex = NativeExecutor(tp, native_device=True, device=dev)
    dev = ex.device
    assert ex.run() == 20
    resident = len(dev._res.clean) + len(dev._res.dirty)
    assert resident == 10
    del ex, tp
    gc.collect()
    left = len(dev._res.clean) + len(dev._res.dirty)
    if handed_in:
        assert left == resident and dev._committer is not None
        dev.detach()
    else:
        assert left == 0 and dev._committer is None
    L = np.tril(A.to_array())
    np.testing.assert_allclose(L @ L.T, S, rtol=1e-10, atol=1e-10)
