"""SPMD execution of distributed dense kernels over a device mesh.

This is the TPU-first counterpart of the reference's multi-rank execution
(owner-computes block-cyclic tasks + explicit messages): instead of one
process per rank exchanging tiles over MPI (``remote_dep_mpi.c``), the whole
computation is ONE jitted program partitioned by GSPMD/shard_map over a
``jax.sharding.Mesh`` — XLA inserts the ICI collectives the dataflow
implies (the "pick a mesh, annotate shardings, let XLA insert collectives"
recipe). Inside shard_map, communication is explicit ppermute/all_gather,
mirroring the reference's neighbour sends and broadcast trees.

Provided kernels:
* ``spmd_cholesky``      — blocked right-looking dpotrf on a (p, q)-sharded
                           matrix; GSPMD-partitioned panel solves + updates.
* ``summa_gemm``         — C = A @ B with all_gather of row/col panels
                           (SUMMA), explicit via shard_map.
* ``ring_gemm``          — C = A @ B over a 1D ring with ppermute-rotated B
                           blocks: the sequence-parallel/ring-attention
                           communication pattern on ICI.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from .mesh import block_sharding


# ---------------------------------------------------------------------------
# blocked Cholesky (GSPMD-partitioned)
# ---------------------------------------------------------------------------

def _chol_step(A: jax.Array, k: jax.Array, nb: int, n: int) -> jax.Array:
    """One right-looking step on the full (sharded) matrix using
    fixed-shape slices + row masking so shapes stay static under jit."""
    i0 = k * nb
    Akk = lax.dynamic_slice(A, (i0, i0), (nb, nb))
    L = jnp.linalg.cholesky(Akk)
    col = lax.dynamic_slice(A, (0, i0), (n, nb))
    # panel solve against L^T for every row; only rows below the diagonal
    # block are meaningful, the rest are masked to zero
    Pfull = jax.scipy.linalg.solve_triangular(L, col.T, lower=True).T
    rows = jnp.arange(n)[:, None]
    below = rows >= i0 + nb
    Pmask = jnp.where(below, Pfull, 0.0)
    # trailing update touches exactly the (below, below) submatrix
    A = A - jnp.dot(Pmask, Pmask.T, precision="highest")
    # write back the factor panel: L on the diagonal block, P below, zeros
    # above (the strictly-upper region is junk for a lower factorization)
    panel = Pmask + lax.dynamic_update_slice(jnp.zeros((n, nb), A.dtype), L, (i0, 0))
    A = lax.dynamic_update_slice(A, panel, (0, i0))
    return A


def spmd_cholesky(A: jax.Array, nb: int, mesh: Optional[Mesh] = None) -> jax.Array:
    """Factorize SPD ``A`` (n×n, n % nb == 0) in f32/f64; returns the full
    matrix whose lower triangle is L. With ``mesh``, A is block-sharded over
    (p, q) and GSPMD partitions every step."""
    n = A.shape[0]
    assert n % nb == 0, "n must be a multiple of nb"
    nt = n // nb

    def run(A):
        def body(k, A):
            return _chol_step(A, k, nb, n)

        return lax.fori_loop(0, nt, body, A)

    if mesh is None:
        return jax.jit(run)(A)
    sh = block_sharding(mesh)
    A = jax.device_put(A, sh)
    return jax.jit(run, in_shardings=sh, out_shardings=sh)(A)


# ---------------------------------------------------------------------------
# SUMMA GEMM (explicit shard_map collectives)
# ---------------------------------------------------------------------------

def summa_gemm(A: jax.Array, B: jax.Array, mesh: Mesh) -> jax.Array:
    """C = A @ B with A, B, C block-sharded over (p, q): each device
    all_gathers its row panel of A along q and its column panel of B along
    p, then multiplies locally — textbook SUMMA on ICI."""
    pax, qax = mesh.axis_names

    def kernel(a_blk, b_blk):
        a_row = lax.all_gather(a_blk, qax, axis=1, tiled=True)   # my row of A
        b_col = lax.all_gather(b_blk, pax, axis=0, tiled=True)   # my col of B
        return jnp.dot(a_row, b_col, precision="highest")

    spec = P(pax, qax)
    f = shard_map(kernel, mesh=mesh, in_specs=(spec, spec), out_specs=spec)
    return jax.jit(f)(A, B)


# ---------------------------------------------------------------------------
# ring GEMM (1D ring, ppermute rotation — the ring-attention pattern)
# ---------------------------------------------------------------------------

def ring_gemm(A: jax.Array, B: jax.Array, mesh: Mesh, axis: Optional[str] = None) -> jax.Array:
    """C = A @ B over a 1D ring: A row-sharded, B row-sharded (on its
    contraction dim). Each of the R steps multiplies the resident B block
    against the matching column slice of the local A rows, then rotates the
    B block one ICI hop (lax.ppermute) — communication fully overlapped by
    XLA with the local matmuls."""
    axis = axis or mesh.axis_names[0]
    R = mesh.shape[axis]
    n_k = A.shape[1]
    assert n_k % R == 0
    kb = n_k // R

    def kernel(a_blk, b_blk):
        idx = lax.axis_index(axis)

        def step(s, carry):
            c, b = carry
            # the resident b block corresponds to contraction slice
            # ((idx + s) mod R) of A's columns
            src = (idx.astype(s.dtype) + s) % R
            a_slice = lax.dynamic_slice(
                a_blk, (jnp.zeros((), s.dtype), src * kb), (a_blk.shape[0], kb))
            c = c + jnp.dot(a_slice, b, precision="highest")
            b = lax.ppermute(b, axis, [(i, (i - 1) % R) for i in range(R)])
            return (c, b)

        from .ring_attention import _varying

        c0 = _varying(jnp.zeros((a_blk.shape[0], b_blk.shape[1]), A.dtype), axis)
        c, _ = lax.fori_loop(0, R, step, (c0, b_blk))
        return c

    in_specs = (P(axis, None), P(axis, None))
    out_spec = P(axis, None)
    f = shard_map(kernel, mesh=mesh, in_specs=in_specs, out_specs=out_spec)
    return jax.jit(f)(A, B)
