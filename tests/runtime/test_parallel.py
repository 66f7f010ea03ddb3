"""Multi-chip SPMD tests on the virtual 8-device CPU mesh (reference:
"multi-node" testing is multi-process on one node, SURVEY.md §4)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from parsec_tpu.parallel import (
    best_grid,
    collectives,
    make_mesh,
    ring_gemm,
    spmd_cholesky,
    summa_gemm,
)

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs the 8-device CPU mesh"
)


def test_best_grid():
    assert best_grid(8) == (2, 4)
    assert best_grid(16) == (4, 4)
    assert best_grid(7) == (1, 7)


def test_make_mesh_shape():
    m = make_mesh()
    assert m.devices.size == 8
    assert m.axis_names == ("p", "q")


@pytest.mark.parametrize("topo", ["star", "chain", "binomial"])
def test_bcast_topologies(topo):
    """All three reference broadcast topologies deliver the root's data."""
    mesh = make_mesh((1, 8), axes=("r", "x"))
    root = 3

    def kern(x):
        return collectives.bcast(x, "x", root=root, topology=topo)

    x = jnp.arange(8.0).reshape(8, 1)  # shard i holds value i
    f = shard_map(kern, mesh=mesh, in_specs=P("x", None), out_specs=P("x", None))
    out = np.asarray(jax.jit(f)(x))
    np.testing.assert_allclose(out, np.full((8, 1), float(root)))


def test_collective_wrappers():
    mesh = make_mesh((1, 8), axes=("r", "x"))

    def kern(x):
        s = collectives.allreduce_sum(jnp.sum(x), "x")
        g = collectives.allgather(x, "x")
        return s * jnp.ones_like(x), g

    x = jnp.arange(8.0).reshape(8, 1)
    f = shard_map(kern, mesh=mesh, in_specs=P("x", None),
                  out_specs=(P("x", None), P(None, None)),
                  check_vma=False)
    s, g = jax.jit(f)(x)
    assert float(np.asarray(s)[0, 0]) == 28.0
    np.testing.assert_allclose(np.asarray(g).ravel(), np.arange(8.0))


def test_shift_ring():
    mesh = make_mesh((1, 8), axes=("r", "x"))

    def kern(x):
        return collectives.shift(x, "x", 1)

    x = jnp.arange(8.0).reshape(8, 1)
    f = shard_map(kern, mesh=mesh, in_specs=P("x", None), out_specs=P("x", None))
    out = np.asarray(jax.jit(f)(x)).ravel()
    np.testing.assert_allclose(out, np.roll(np.arange(8.0), 1))


def test_summa_gemm_matches():
    mesh = make_mesh((2, 4))
    rng = np.random.default_rng(0)
    A = rng.standard_normal((64, 64))
    B = rng.standard_normal((64, 64))
    C = summa_gemm(jnp.asarray(A), jnp.asarray(B), mesh)
    np.testing.assert_allclose(np.asarray(C), A @ B, rtol=1e-10)


def test_ring_gemm_matches():
    mesh = make_mesh((8, 1), axes=("x", "y"))
    rng = np.random.default_rng(1)
    A = rng.standard_normal((64, 32))
    B = rng.standard_normal((32, 48))
    C = ring_gemm(jnp.asarray(A), jnp.asarray(B), mesh, axis="x")
    np.testing.assert_allclose(np.asarray(C), A @ B, rtol=1e-10)


def test_spmd_cholesky_single():
    rng = np.random.default_rng(2)
    n, nb = 64, 16
    M = rng.standard_normal((n, n))
    SPD = M @ M.T + n * np.eye(n)
    L = spmd_cholesky(jnp.asarray(SPD), nb)
    np.testing.assert_allclose(np.tril(np.asarray(L)), np.linalg.cholesky(SPD),
                               rtol=1e-8, atol=1e-8)


def test_spmd_cholesky_sharded():
    mesh = make_mesh((2, 4))
    rng = np.random.default_rng(3)
    n, nb = 64, 16
    M = rng.standard_normal((n, n))
    SPD = M @ M.T + n * np.eye(n)
    L = spmd_cholesky(jnp.asarray(SPD), nb, mesh=mesh)
    np.testing.assert_allclose(np.tril(np.asarray(L)), np.linalg.cholesky(SPD),
                               rtol=1e-8, atol=1e-8)


def test_spmd_stencil_matches_reference():
    """Halo-exchange stencil on a 2D device mesh == the dense oracle
    (the BASELINE.json 'stencil 2D5pt comm/compute overlap' config)."""
    import jax.numpy as jnp

    from parsec_tpu.parallel import make_mesh, spmd_stencil_5pt
    from parsec_tpu.ops.stencil import reference_stencil

    devs = jax.devices()
    p, q = (4, 2) if len(devs) >= 8 else (len(devs), 1)
    mesh = make_mesh((p, q), axes=("r", "c"), devices=devs[:p * q])
    rng = np.random.default_rng(0)
    grid = rng.standard_normal((8 * p, 8 * q)).astype(np.float32)
    out = np.asarray(spmd_stencil_5pt(jnp.asarray(grid), 5, mesh, axes=("r", "c")))
    np.testing.assert_allclose(out, reference_stencil(grid, 5), rtol=1e-5, atol=1e-6)


def test_spmd_stencil_single_iteration_edges():
    import jax.numpy as jnp

    from parsec_tpu.parallel import make_mesh, spmd_stencil_5pt
    from parsec_tpu.ops.stencil import reference_stencil

    devs = jax.devices()
    mesh = make_mesh((len(devs), 1), axes=("r", "c"), devices=devs)
    grid = np.ones((8 * len(devs), 16), np.float64)
    out = np.asarray(spmd_stencil_5pt(jnp.asarray(grid), 1, mesh, axes=("r", "c")))
    np.testing.assert_allclose(out, reference_stencil(grid, 1), rtol=1e-12)
