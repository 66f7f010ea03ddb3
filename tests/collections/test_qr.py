"""Tiled Householder QR (the second flagship PTG, ops/qr.py).

Invariant-based verification: A = Q R with orthogonal Q implies
A^T A = R^T R — checks the factorization without tracking Q. Diagonal-
sign canonicalisation then compares R against numpy's directly.
"""

import numpy as np
import pytest

from parsec_tpu import Context
from parsec_tpu.datadist import TiledMatrix
from parsec_tpu.dsl.xla_lower import GraphExecutor
from parsec_tpu.ops.qr import qr_ptg, run_qr


def _mk(n, dtype=np.float64, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, n)).astype(dtype)


def _check_r(A, R, rtol):
    # R upper triangular
    np.testing.assert_allclose(np.tril(R, -1), 0, atol=1e-10 * max(1, np.abs(R).max()))
    # A^T A == R^T R  (Q orthogonal)
    np.testing.assert_allclose(R.T @ R, A.T @ A, rtol=rtol,
                               atol=rtol * np.abs(A.T @ A).max())
    # sign-canonical comparison against numpy
    R_np = np.linalg.qr(A, mode="r")
    s_ours = np.sign(np.diag(R))
    s_np = np.sign(np.diag(R_np))
    np.testing.assert_allclose(s_ours[:, None] * R, s_np[:, None] * R_np,
                               rtol=rtol, atol=rtol * np.abs(R_np).max())


@pytest.mark.parametrize("n,nb", [(64, 32), (96, 32), (128, 32)])
def test_qr_dynamic_cpu(n, nb):
    A0 = _mk(n, seed=n)
    A = TiledMatrix(n, n, nb, nb, name="A", dtype=np.float64).from_array(A0)
    with Context(nb_cores=4) as ctx:
        run_qr(ctx, A, use_tpu=False, use_cpu=True)
    _check_r(A0, A.to_array(), rtol=1e-9)


def test_qr_graph_lowered():
    n, nb = 128, 32
    A0 = _mk(n, np.float32, seed=7)
    A = TiledMatrix(n, n, nb, nb, name="A", dtype=np.float32).from_array(A0)
    tp = qr_ptg(use_tpu=True, use_cpu=False).taskpool(
        NT=A.mt, A=A, TILE_SHAPE=(nb, nb), TILE_DTYPE=np.float32,
        QSHAPE2=(np.float32, (2 * nb, 2 * nb)))
    GraphExecutor(tp)(block=True)
    _check_r(A0, A.to_array(), rtol=5e-3)


def test_qr_graph_batched_levels():
    n, nb = 160, 32
    A0 = _mk(n, np.float32, seed=8)
    A = TiledMatrix(n, n, nb, nb, name="A", dtype=np.float32).from_array(A0)
    tp = qr_ptg(use_tpu=True, use_cpu=False).taskpool(
        NT=A.mt, A=A, TILE_SHAPE=(nb, nb), TILE_DTYPE=np.float32,
        QSHAPE2=(np.float32, (2 * nb, 2 * nb)))
    GraphExecutor(tp, batch_levels=True)(block=True)
    _check_r(A0, A.to_array(), rtol=5e-3)


def test_qr_native_engine():
    from parsec_tpu import native

    if not native.available():
        pytest.skip(f"native core unavailable: {native.build_error()}")
    from parsec_tpu.dsl.native_exec import run_native

    n, nb = 96, 32
    A0 = _mk(n, seed=9)
    A = TiledMatrix(n, n, nb, nb, name="A", dtype=np.float64).from_array(A0)
    tp = qr_ptg(use_tpu=False, use_cpu=True).taskpool(
        NT=A.mt, A=A, TILE_SHAPE=(nb, nb), TILE_DTYPE=np.float64,
        QSHAPE2=(np.float64, (2 * nb, 2 * nb)))
    run_native(tp, nthreads=4)
    _check_r(A0, A.to_array(), rtol=1e-9)


def test_qr_single_tile():
    A0 = _mk(32, seed=10)
    A = TiledMatrix(32, 32, 32, 32, name="A", dtype=np.float64).from_array(A0)
    with Context(nb_cores=2) as ctx:
        run_qr(ctx, A, use_tpu=False, use_cpu=True)
    _check_r(A0, A.to_array(), rtol=1e-10)


def test_qr_via_dtd_replay():
    """Regression: the DTD replay path must honor per-flow NEW shapes
    ([type=QSHAPE2]) — it used to allocate Q as TILE_SHAPE and produce a
    silently wrong factorization."""
    from parsec_tpu.dsl.ptg_to_dtd import replay_via_dtd

    n, nb = 96, 32
    A0 = _mk(n, seed=11)
    A = TiledMatrix(n, n, nb, nb, name="A", dtype=np.float64).from_array(A0)
    tp = qr_ptg(use_tpu=False, use_cpu=True).taskpool(
        NT=A.mt, A=A, TILE_SHAPE=(nb, nb), TILE_DTYPE=np.float64,
        QSHAPE2=(np.float64, (2 * nb, 2 * nb)))
    with Context(nb_cores=4) as ctx:
        replay_via_dtd(tp, ctx)
    _check_r(A0, A.to_array(), rtol=1e-9)


def test_qr_rejects_ragged_or_wide():
    from parsec_tpu.ops.qr_tree import QRTree

    with Context(nb_cores=1) as ctx:
        bad = TiledMatrix(112, 112, 32, 32, name="A", dtype=np.float64)
        with pytest.raises(ValueError, match="M >= N and uniform square"):
            run_qr(ctx, bad, use_tpu=False)
        wide = TiledMatrix(64, 96, 32, 32, name="A", dtype=np.float64)
        with pytest.raises(ValueError, match="M >= N and uniform square"):
            run_qr(ctx, wide, use_tpu=False)
        # (tall is accepted: tests/collections/test_qr_tree.py); a tree
        # of another grid is not
        tall = TiledMatrix(96, 64, 32, 32, name="A", dtype=np.float64)
        with pytest.raises(ValueError, match="not a tree of a 3 x 2"):
            run_qr(ctx, tall, tree=QRTree(4, 2, 2), use_tpu=False)


def test_new_tile_spec_guarded_otherwise_branch():
    """[type=...] props apply when NEW sits in a guard's else-branch."""
    from parsec_tpu.dsl.ptg import PTG
    from parsec_tpu.core.lifecycle import AccessMode

    ptg = PTG("probe")
    tc = ptg.task_class("t", i="0 .. 1")
    tc.flow("X", AccessMode.INOUT,
            "<- (i > 0) ? X t(i-1) : NEW [type=XSHAPE]",
            "-> (i < 1) ? X t(i+1)")
    tc.body(cpu=lambda X, **_: None)
    tp = ptg.taskpool(XSHAPE=(np.float32, (3, 5)), TILE_SHAPE=(1,))
    shape, dtype = tp.new_tile_spec("t", "X")
    assert shape == (3, 5) and np.dtype(dtype) == np.float32


def test_qr_graph_pallas_chores():
    n, nb = 128, 32
    A0 = _mk(n, np.float32, seed=12)
    A = TiledMatrix(n, n, nb, nb, name="A", dtype=np.float32).from_array(A0)
    tp = qr_ptg(use_tpu=True, use_cpu=False, use_pallas=True).taskpool(
        NT=A.mt, A=A, TILE_SHAPE=(nb, nb), TILE_DTYPE=np.float32,
        QSHAPE2=(np.float32, (2 * nb, 2 * nb)))
    GraphExecutor(tp)(block=True)
    _check_r(A0, A.to_array(), rtol=5e-3)


# -- the kills' batched form (a wave as ONE kernel: ``_lockstep_qr``) -------

def _stack_of(cls, n, nb, seed, plant=None):
    rng = np.random.default_rng(seed)
    tiles = [rng.uniform(-1, 1, (n, nb, nb)).astype(np.float32)
             for _ in range(1 if cls == "geqrt" else 2)]
    if plant == "zero_column":      # a tail of zeros: tau = 0, H = I
        for t in tiles:
            t[:, :, 3] = 0.0
    if plant == "triangle":         # nothing below the diagonal anywhere
        tiles = [np.triu(t) for t in tiles]
    if plant == "zeros":
        tiles = [np.zeros_like(t) for t in tiles]
    return tiles


@pytest.mark.parametrize("plant", [None, "zero_column", "triangle", "zeros"])
@pytest.mark.parametrize("cls,n,nb,wo", [
    ("geqrt", 3, 40, 16),   # blocks of 16, 16, 8; three matrices a grid step
    ("tsqrt", 4, 40, 16),
    ("ttqrt", 6, 24, 128),  # one block narrower than its width
    ("tsqrt", 1, 32, 8),
    ("geqrt", 8, 16, 8),
])
def test_a_wave_of_kills_is_lapacks_householder_qr(cls, n, nb, wo, plant):
    """Matrix for matrix LAPACK's factorization of the same stack (its
    signs too, so R and Q themselves agree, not only up to a sign), for
    any number of matrices, block widths that do not divide the tile, and
    columns that need no reflector; and the body is the form over a stack
    of one."""
    import jax.numpy as jnp

    from parsec_tpu.ops import qr

    tiles = _stack_of(cls, n, nb, seed=n * nb, plant=plant)
    bodies = dict(zip(("geqrt", "tsqrt", "ttqrt"),
                      qr._kills(qr._lockstep_qr(wo))))
    outs = bodies[cls]._batched(*tiles)
    for t in range(n):
        if cls == "geqrt":
            q, r = np.linalg.qr(tiles[0][t], mode="complete")
            want = (r, q)
        else:
            low = np.triu(tiles[1][t]) if cls == "ttqrt" else tiles[1][t]
            q, r = np.linalg.qr(np.vstack([np.triu(tiles[0][t]), low]),
                                mode="complete")
            want = (r[:nb], np.zeros_like(low), q)
        assert len(outs) == len(want)
        for got, ref in zip(outs, want):
            got = np.asarray(got[t])
            assert got.shape == ref.shape and got.dtype == ref.dtype
            assert np.max(np.abs(got - ref)) <= 2e-5
        r, q = np.asarray(outs[0][t]), np.asarray(outs[-1][t])
        assert not np.tril(r, -1).any()
        assert np.max(np.abs(q.T @ q - np.eye(len(q)))) <= 1e-5
        if cls != "geqrt":
            assert not np.asarray(outs[1][t]).any()
    alone = bodies[cls](*[jnp.asarray(x[0]) for x in tiles], None)
    for got, ref in zip(alone, outs):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref[0]))
