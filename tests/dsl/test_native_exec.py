"""Native execution engine: the C++ core (dep counters, priority pool,
worker threads) runs the DAG; Python is entered only for BODYs."""

import os
import time

import numpy as np
import pytest

from parsec_tpu import native


pytestmark = pytest.mark.skipif(
    not native.available(), reason=f"native core unavailable: {native.build_error()}")


def _spd(n, dtype=np.float64, seed=0):
    rng = np.random.default_rng(seed)
    m = rng.standard_normal((n, n)).astype(dtype)
    return m @ m.T + n * np.eye(n, dtype=dtype)


def test_native_cholesky_matches_numpy():
    from parsec_tpu.datadist import TiledMatrix
    from parsec_tpu.dsl.native_exec import run_native
    from parsec_tpu.ops.cholesky import cholesky_ptg

    n, nb = 128, 16  # 8x8 tiles -> 120 tasks
    S = _spd(n)
    A = TiledMatrix(n, n, nb, nb, name="A", dtype=np.float64).from_array(S)
    tp = cholesky_ptg(use_tpu=False, use_cpu=True).taskpool(NT=A.mt, A=A)
    ran = run_native(tp, nthreads=4)
    assert ran == 120  # 8 potrf + 28 trsm + 28 syrk + 56 gemm
    L = np.tril(A.to_array())
    np.testing.assert_allclose(L @ L.T, S, rtol=1e-8, atol=1e-8)


def test_native_stencil_matches_reference():
    from parsec_tpu.dsl.native_exec import run_native
    from parsec_tpu.ops.stencil import (reference_stencil, stencil_grid,
                                        stencil_taskpool)

    rng = np.random.default_rng(1)
    grid = rng.standard_normal((24, 36))
    mt, nt, iters = 3, 3, 4
    A = stencil_grid(grid, mt, nt)
    tp = stencil_taskpool(A, iters)
    ran = run_native(tp, nthreads=4)
    assert ran == iters * mt * nt
    np.testing.assert_allclose(
        A.to_array(), reference_stencil(grid, iters), rtol=1e-12)


def test_native_matches_dynamic_runtime_results():
    """Same taskpool through both engines -> identical tiles."""
    from parsec_tpu import Context
    from parsec_tpu.datadist import TiledMatrix
    from parsec_tpu.dsl.native_exec import run_native
    from parsec_tpu.ops.cholesky import cholesky_ptg

    n, nb = 96, 32
    S = _spd(n, seed=2)

    A1 = TiledMatrix(n, n, nb, nb, name="A", dtype=np.float64).from_array(S)
    run_native(cholesky_ptg(use_tpu=False).taskpool(NT=A1.mt, A=A1))

    A2 = TiledMatrix(n, n, nb, nb, name="A", dtype=np.float64).from_array(S)
    with Context(nb_cores=2) as ctx:
        tp = cholesky_ptg(use_tpu=False).taskpool(NT=A2.mt, A=A2)
        ctx.add_taskpool(tp)
        assert tp.wait(timeout=60)
    np.testing.assert_allclose(A1.to_array(), A2.to_array(), rtol=1e-13)


def test_native_body_error_propagates():
    from parsec_tpu.core.lifecycle import AccessMode
    from parsec_tpu.dsl.native_exec import run_native
    from parsec_tpu.dsl.ptg import PTG
    from parsec_tpu.data.collection import LocalCollection

    coll = LocalCollection("A", shape=(2,), dtype=np.float64)

    ptg = PTG("boom")
    tc = ptg.task_class("t", i="0 .. 3")
    tc.affinity("A(i)")
    tc.flow("X", AccessMode.INOUT, "<- A(i)", "-> A(i)")

    def body(X, i, **_):
        if i == 2:
            raise RuntimeError("body exploded")
        X += 1

    tc.body(cpu=body)
    with pytest.raises(RuntimeError, match="body exploded"):
        run_native(ptg.taskpool(A=coll))


def test_native_dispatch_overhead_beats_dynamic():
    """Dispatch-bound microbench: tiny bodies, hundreds of tasks. The
    native engine must not be slower than the dynamic Python path (it
    usually wins by a wide margin; assert a conservative bound)."""
    from parsec_tpu import Context
    from parsec_tpu.datadist import TiledMatrix
    from parsec_tpu.dsl.native_exec import NativeExecutor
    from parsec_tpu.ops.cholesky import cholesky_ptg

    n, nb = 512, 32  # 16x16 tiles -> 816 tasks, ~us-scale bodies
    S = _spd(n, np.float32, seed=3)

    A1 = TiledMatrix(n, n, nb, nb, name="A", dtype=np.float32).from_array(S)
    ex = NativeExecutor(cholesky_ptg(use_tpu=False).taskpool(NT=A1.mt, A=A1))
    t0 = time.perf_counter()
    ex.run(nthreads=4)
    t_native = time.perf_counter() - t0
    ex.close()

    A2 = TiledMatrix(n, n, nb, nb, name="A", dtype=np.float32).from_array(S)
    with Context(nb_cores=4) as ctx:
        tp = cholesky_ptg(use_tpu=False).taskpool(NT=A2.mt, A=A2)
        t0 = time.perf_counter()
        ctx.add_taskpool(tp)
        assert tp.wait(timeout=120)
        t_dyn = time.perf_counter() - t0

    np.testing.assert_allclose(A1.to_array(), A2.to_array(), rtol=2e-2, atol=1e-3)
    # wall-clock assertions on shared CI boxes flake; enforce only when
    # opted in (local perf runs), otherwise this test is correctness-only
    if os.environ.get("PARSEC_TPU_PERF_ASSERT"):
        assert t_native <= t_dyn * 1.5, (t_native, t_dyn)


def test_native_path_fires_pins_events():
    """Observers (task profiler, alperf, SDE) see the same exec/complete
    lifecycle from the native engine as from the dynamic path."""
    from parsec_tpu.datadist import TiledMatrix
    from parsec_tpu.dsl.native_exec import run_native
    from parsec_tpu.ops.cholesky import cholesky_ptg
    from parsec_tpu.profiling import pins

    events = []
    cb_b = lambda es, task: events.append(("exec", task.task_class.name, repr(task)))
    cb_e = lambda es, task: events.append(("done", task.task_class.name, repr(task)))
    pins.subscribe(pins.EXEC_BEGIN, cb_b)
    pins.subscribe(pins.COMPLETE_EXEC_END, cb_e)
    try:
        n, nb = 64, 16  # NT=4: all four task classes appear (gemm needs NT>=3)
        S = _spd(n, seed=5)
        A = TiledMatrix(n, n, nb, nb, name="A", dtype=np.float64).from_array(S)
        ran = run_native(cholesky_ptg(use_tpu=False).taskpool(NT=A.mt, A=A))
    finally:
        pins.unsubscribe(pins.EXEC_BEGIN, cb_b)
        pins.unsubscribe(pins.COMPLETE_EXEC_END, cb_e)
    assert sum(1 for e in events if e[0] == "exec") == ran
    assert sum(1 for e in events if e[0] == "done") == ran
    classes = {e[1] for e in events}
    assert classes == {"potrf", "trsm", "syrk", "gemm"}


def test_native_dtd_fires_pins_events():
    from parsec_tpu.dsl.dtd_native import INOUT, NativeDTD
    from parsec_tpu.profiling import pins

    events = []
    cb = lambda es, task: events.append(task.task_class.name)
    pins.subscribe(pins.EXEC_BEGIN, cb)
    try:
        x = np.zeros(1)

        def bump(a):
            a += 1

        with NativeDTD(nthreads=2) as tp:
            for _ in range(5):
                tp.insert_task(bump, (x, INOUT))
    finally:
        pins.unsubscribe(pins.EXEC_BEGIN, cb)
    assert events.count("bump") == 5
