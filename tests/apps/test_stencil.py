"""Stencil 2D5pt app test (reference tests/apps/stencil + BASELINE.json
'Stencil 2D5pt' tracked config)."""

import numpy as np
import pytest

from parsec_tpu import Context
from parsec_tpu.ops.stencil import StencilBuffers, reference_stencil, stencil_ptg


@pytest.fixture
def ctx():
    c = Context(nb_cores=4)
    yield c
    c.fini()


@pytest.mark.parametrize("iters", [1, 2, 5])
def test_stencil_matches_dense_reference(ctx, iters):
    rng = np.random.default_rng(0)
    grid = rng.standard_normal((32, 48))
    mt, nt = 4, 3
    A = StencilBuffers(grid, mt, nt)
    tp = stencil_ptg().taskpool(T=iters, MT=mt, NT=nt, A=A)
    ctx.add_taskpool(tp)
    assert tp.wait(timeout=60)
    np.testing.assert_allclose(
        A.to_array(iters % 2), reference_stencil(grid, iters), rtol=1e-12)


def test_stencil_device_bodies(ctx, monkeypatch):
    rng = np.random.default_rng(1)
    grid = rng.standard_normal((16, 16))
    A = StencilBuffers(grid, 2, 2)
    tp = stencil_ptg(use_tpu=True).taskpool(T=3, MT=2, NT=2, A=A)
    ctx.add_taskpool(tp)
    assert tp.wait(timeout=120)
    # results may live on the device; to_array goes through newest copies
    np.testing.assert_allclose(
        A.to_array(3 % 2), reference_stencil(grid, 3), rtol=1e-10)


def test_stencil_single_tile(ctx):
    grid = np.ones((8, 8))
    A = StencilBuffers(grid, 1, 1)
    tp = stencil_ptg().taskpool(T=2, MT=1, NT=1, A=A)
    ctx.add_taskpool(tp)
    assert tp.wait(timeout=30)
    np.testing.assert_allclose(A.to_array(0), reference_stencil(grid, 2), rtol=1e-12)


def test_stencil_pallas_bodies(ctx):
    """Pallas chore (interpret off-TPU): same numerics as the jnp body.
    use_cpu=False drops the CPU chore so every task MUST run the pallas
    body (the ETA-based device selection cannot fall back)."""
    rng = np.random.default_rng(2)
    grid = rng.standard_normal((16, 24)).astype(np.float32)
    A = StencilBuffers(grid, 2, 2)
    tp = stencil_ptg(use_pallas=True, use_cpu=False).taskpool(T=3, MT=2, NT=2, A=A)
    ctx.add_taskpool(tp)
    assert tp.wait(timeout=120)
    np.testing.assert_allclose(
        A.to_array(3 % 2), reference_stencil(grid, 3), rtol=1e-5, atol=1e-5)
