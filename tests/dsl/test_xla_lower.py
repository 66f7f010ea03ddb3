"""Whole-DAG XLA lowering (GraphExecutor) tests."""

import numpy as np
import pytest

from parsec_tpu.datadist import TiledMatrix
from parsec_tpu.dsl.ptg import PTG, IN, INOUT
from parsec_tpu.dsl.xla_lower import GraphExecutor
from parsec_tpu.ops import cholesky_ptg


def _spd(n, dtype=np.float64, seed=0):
    rng = np.random.default_rng(seed)
    m = rng.standard_normal((n, n)).astype(dtype)
    return m @ m.T + n * np.eye(n, dtype=dtype)


def test_lowered_cholesky_matches_numpy():
    n, nb = 64, 16
    A = TiledMatrix(n, n, nb, nb, name="A", dtype=np.float64)
    S = _spd(n)
    A.from_array(S)
    tp = cholesky_ptg(use_tpu=True, use_cpu=False).taskpool(NT=A.mt, A=A)
    ex = GraphExecutor(tp)
    nt = A.mt
    assert len(ex.input_keys) == nt * (nt + 1) // 2  # lower triangle read
    ex(block=True)
    L = np.tril(A.to_array())
    np.testing.assert_allclose(L @ L.T, S, rtol=1e-8, atol=1e-8)


def test_lowered_matches_dynamic_runtime():
    from parsec_tpu import Context

    n, nb = 48, 16
    S = _spd(n)
    # dynamic runtime (CPU chores)
    A1 = TiledMatrix(n, n, nb, nb, name="A", dtype=np.float64).from_array(S)
    ctx = Context(nb_cores=4)
    try:
        tp1 = cholesky_ptg(use_tpu=False, use_cpu=True).taskpool(NT=A1.mt, A=A1)
        ctx.add_taskpool(tp1)
        assert tp1.wait(timeout=60)
    finally:
        ctx.fini()
    # captured graph
    A2 = TiledMatrix(n, n, nb, nb, name="A", dtype=np.float64).from_array(S)
    tp2 = cholesky_ptg(use_tpu=True, use_cpu=False).taskpool(NT=A2.mt, A=A2)
    GraphExecutor(tp2)(block=True)
    np.testing.assert_allclose(np.tril(A2.to_array()), np.tril(A1.to_array()),
                               rtol=1e-8, atol=1e-8)


def test_lowered_chain_with_explicit_feeds():
    import jax.numpy as jnp

    from parsec_tpu.data import LocalCollection

    dc = LocalCollection("D", shape=(4,), init=lambda k: np.zeros(4))
    ptg = PTG("chain")
    s = ptg.task_class("s", k="0 .. 7")
    s.affinity("D(0)")
    s.flow("X", INOUT,
           "<- (k == 0) ? D(0) : X s(k-1)",
           "-> (k < 7) ? X s(k+1) : D(0)")
    s.body(tpu=lambda X, k: X + k)
    tp = ptg.taskpool(D=dc)
    ex = GraphExecutor(tp)
    out = ex.apply({("D", (0,)): jnp.ones(4)})
    np.testing.assert_allclose(out[("D", (0,))], 1.0 + sum(range(8)))


def test_lowered_requires_functional_body():
    from parsec_tpu.data import LocalCollection

    dc = LocalCollection("D", shape=(2,), init=lambda k: np.zeros(2))
    ptg = PTG("cpuonly")
    s = ptg.task_class("s")
    s.flow("X", INOUT, "<- D(0)", "-> D(0)")
    s.body(cpu=lambda X: X.__iadd__(1))
    with pytest.raises(ValueError, match="functional"):
        GraphExecutor(ptg.taskpool(D=dc))


def test_lowered_cholesky_pallas_chores():
    """dpotrf with the fused Pallas update kernels (interpret off-TPU)
    through the whole-DAG capture path."""
    n, nb = 128, 32
    A = TiledMatrix(n, n, nb, nb, name="A", dtype=np.float32)
    S = _spd(n, dtype=np.float32, seed=3)
    A.from_array(S)
    tp = cholesky_ptg(use_tpu=True, use_cpu=False,
                      use_pallas=True).taskpool(NT=A.mt, A=A)
    ex = GraphExecutor(tp)
    ex(block=True)
    L = np.tril(A.to_array())
    np.testing.assert_allclose(L @ L.T, S, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_lowered_cholesky_trtri_chores(use_pallas):
    """trsm as matmul against the per-column inverse (use_trtri): same
    factorization within f32 tolerance."""
    n, nb = 128, 32
    A = TiledMatrix(n, n, nb, nb, name="A", dtype=np.float32)
    S = _spd(n, dtype=np.float32, seed=4)
    A.from_array(S)
    tp = cholesky_ptg(use_tpu=True, use_cpu=False, use_pallas=use_pallas,
                      use_trtri=True).taskpool(NT=A.mt, A=A)
    ex = GraphExecutor(tp)
    ex(block=True)
    L = np.tril(A.to_array())
    np.testing.assert_allclose(L @ L.T, S, rtol=2e-3, atol=2e-3)


def test_dynamic_cholesky_trtri_cpu():
    from parsec_tpu import Context

    n, nb = 96, 32
    A = TiledMatrix(n, n, nb, nb, name="A", dtype=np.float64)
    S = _spd(n, seed=5)
    A.from_array(S)
    tp = cholesky_ptg(use_tpu=False, use_cpu=True, use_trtri=True).taskpool(
        NT=A.mt, A=A, TILE_SHAPE=(nb, nb), TILE_DTYPE=np.float64)
    with Context(nb_cores=2) as ctx:
        ctx.add_taskpool(tp)
        assert tp.wait(timeout=60)
    L = np.tril(A.to_array())
    np.testing.assert_allclose(L @ L.T, S, rtol=1e-8, atol=1e-8)


def test_lowered_cholesky_bf16_updates():
    """Mixed precision (bf16 panel operands, f32 accumulate): correct
    factorization within mixed-precision tolerance."""
    n, nb = 128, 32
    A = TiledMatrix(n, n, nb, nb, name="A", dtype=np.float32)
    S = _spd(n, dtype=np.float32, seed=6)
    A.from_array(S)
    tp = cholesky_ptg(use_tpu=True, use_cpu=False, use_pallas=True,
                      bf16_updates=True).taskpool(NT=A.mt, A=A)
    GraphExecutor(tp)(block=True)
    L = np.tril(A.to_array())
    err = np.abs(L @ L.T - S).max() / np.abs(S).max()
    assert err < 2e-2, err


def test_bf16_updates_requires_pallas():
    with pytest.raises(ValueError, match="requires use_pallas"):
        cholesky_ptg(use_pallas=False, bf16_updates=True)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_batched_levels_cholesky_matches(use_pallas):
    """Level-batched lowering (vmapped same-class groups) is numerically
    identical to per-task emission."""
    n, nb = 160, 32  # NT=5: non-trivial levels, uniform tiles
    A = TiledMatrix(n, n, nb, nb, name="A", dtype=np.float32)
    S = _spd(n, dtype=np.float32, seed=7)
    A.from_array(S)
    tp = cholesky_ptg(use_tpu=True, use_cpu=False,
                      use_pallas=use_pallas).taskpool(NT=A.mt, A=A)
    ex = GraphExecutor(tp, batch_levels=True)
    ex(block=True)
    L = np.tril(A.to_array())
    np.testing.assert_allclose(L @ L.T, S, rtol=2e-3, atol=2e-3)


def test_batched_levels_stencil_matches():
    from parsec_tpu.ops.stencil import (reference_stencil, stencil_grid,
                                        stencil_taskpool)

    rng = np.random.default_rng(8)
    grid = rng.standard_normal((32, 32)).astype(np.float32)
    A = stencil_grid(grid, 4, 4)
    tp = stencil_taskpool(A, 4, use_tpu=True, use_cpu=False)
    ex = GraphExecutor(tp, batch_levels=True)
    ex(block=True)
    np.testing.assert_allclose(A.to_array(), reference_stencil(grid, 4),
                               rtol=1e-5, atol=1e-5)


def test_batched_levels_ragged_tiles_fall_back():
    """Non-divisible matrix: ragged edge tiles split groups by shape (or
    fall back per-task) and the result stays exact."""
    n, nb = 112, 32  # 4 tiles: 32,32,32,16 -> ragged
    A = TiledMatrix(n, n, nb, nb, name="A", dtype=np.float64)
    S = _spd(n, seed=9)
    A.from_array(S)
    tp = cholesky_ptg(use_tpu=True, use_cpu=False).taskpool(NT=A.mt, A=A)
    ex = GraphExecutor(tp, batch_levels=True)
    ex(block=True)
    L = np.tril(A.to_array())
    np.testing.assert_allclose(L @ L.T, S, rtol=1e-8, atol=1e-8)
