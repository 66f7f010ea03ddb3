"""Stencil 2D5pt app test (reference tests/apps/stencil + BASELINE.json
'Stencil 2D5pt' tracked config)."""

import numpy as np
import pytest

from parsec_tpu import Context
from parsec_tpu.ops.stencil import (reference_stencil, stencil_grid,
                                    stencil_taskpool)


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
    A = stencil_grid(grid, mt, nt)
    # one sweep cannot be in place: its result gets a matrix of its own
    B = stencil_grid(np.zeros_like(grid), mt, nt, name="B") \
        if iters == 1 else A
    tp = stencil_taskpool(A, iters, B=B)
    ctx.add_taskpool(tp)
    assert tp.wait(timeout=60)
    np.testing.assert_allclose(
        B.to_array(), reference_stencil(grid, iters), rtol=1e-12)


def test_stencil_device_bodies(ctx, monkeypatch):
    rng = np.random.default_rng(1)
    grid = rng.standard_normal((16, 16))
    A = stencil_grid(grid, 2, 2)
    tp = stencil_taskpool(A, 3, use_tpu=True)
    ctx.add_taskpool(tp)
    assert tp.wait(timeout=120)
    # results may live on the device; to_array goes through newest copies
    np.testing.assert_allclose(
        A.to_array(), reference_stencil(grid, 3), rtol=1e-10)


def test_stencil_single_tile(ctx):
    grid = np.ones((8, 8))
    A = stencil_grid(grid, 1, 1)
    tp = stencil_taskpool(A, 2)
    ctx.add_taskpool(tp)
    assert tp.wait(timeout=30)
    np.testing.assert_allclose(A.to_array(), reference_stencil(grid, 2), rtol=1e-12)


def test_stencil_pallas_bodies(ctx):
    """Pallas chore (interpret off-TPU): same numerics as the jnp body.
    use_cpu=False drops the CPU chore so every task MUST run the pallas
    body (the ETA-based device selection cannot fall back)."""
    rng = np.random.default_rng(2)
    grid = rng.standard_normal((16, 24)).astype(np.float32)
    A = stencil_grid(grid, 2, 2)
    tp = stencil_taskpool(A, 3, use_pallas=True, use_cpu=False)
    ctx.add_taskpool(tp)
    assert tp.wait(timeout=120)
    np.testing.assert_allclose(
        A.to_array(), reference_stencil(grid, 3), rtol=1e-5, atol=1e-5)
