"""The north-star algorithm through the JDF front-end: tiled dpotrf from
examples/jdf/cholesky.jdf, dynamic-scheduled (CPU bodies), whole-DAG
captured (tpu bodies), and 4-rank distributed — all against numpy."""

import os

import numpy as np
import pytest

from parsec_tpu import Context
from parsec_tpu.datadist import TwoDimBlockCyclic
from parsec_tpu.dsl import compile_jdf_file

JDF = os.path.join(os.path.dirname(__file__), "..", "..",
                   "examples", "jdf", "cholesky.jdf")


def _spd(n, seed=0):
    rng = np.random.default_rng(seed)
    m = rng.standard_normal((n, n))
    return m @ m.T + n * np.eye(n)


def _check(A, SPD):
    L = np.tril(A.to_array())
    np.testing.assert_allclose(L @ L.T, SPD, rtol=1e-8, atol=1e-8)


def test_jdf_cholesky_dynamic():
    N, NB = 128, 32
    SPD = _spd(N)
    A = TwoDimBlockCyclic(N, N, NB, NB, name="A").from_array(SPD)
    jdf = compile_jdf_file(JDF)
    ctx = Context(nb_cores=4)
    try:
        tp = jdf.new(A=A, NT=A.mt)
        ctx.add_taskpool(tp)
        assert tp.wait(timeout=120)
    finally:
        ctx.fini()
    _check(A, SPD)


def test_jdf_cholesky_whole_dag_capture():
    """The same JDF lowered to ONE jitted XLA computation via its tpu
    incarnations (the whole-DAG fast path, from a .jdf source)."""
    from parsec_tpu.dsl.xla_lower import GraphExecutor

    N, NB = 128, 32
    SPD = _spd(N, seed=1)
    A = TwoDimBlockCyclic(N, N, NB, NB, name="A").from_array(SPD)
    jdf = compile_jdf_file(JDF)
    tp = jdf.new(A=A, NT=A.mt)
    GraphExecutor(tp)(write_back=True, block=True)
    _check(A, SPD)


def test_jdf_cholesky_multirank():
    """2x2 block-cyclic over 4 ranks on the in-process fabric."""
    from tests.runtime.test_multirank import run_ranks

    N, NB, NR = 96, 24, 4
    SPD = _spd(N, seed=2)
    mats = {}

    def build(rank, ctx):
        A = TwoDimBlockCyclic(N, N, NB, NB, p=2, q=2, myrank=rank,
                              name="A").from_array(SPD)
        mats[rank] = A
        jdf = compile_jdf_file(JDF)
        return jdf.new(A=A, NT=A.mt)

    run_ranks(NR, build, timeout=120)

    # assemble L from each rank's local tiles
    L = np.zeros((N, N))
    for rank, A in mats.items():
        for (i, j) in A.local_tiles():
            c = A.data_of(i, j).newest_copy()
            h, w = A.tile_shape(i, j)
            L[i * NB:i * NB + h, j * NB:j * NB + w] = np.asarray(c.payload)[:h, :w]
    L = np.tril(L)
    np.testing.assert_allclose(L @ L.T, SPD, rtol=1e-8, atol=1e-8)
