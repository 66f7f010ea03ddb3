"""Multi-chip 2D 5-point stencil: halo exchange over the device mesh.

The BASELINE.json "Stencil 2D5pt, comm/compute overlap" configuration
(reference app: ``/root/reference/tests/apps/stencil/``). The reference
gets overlap from its comm thread progressing halo messages while workers
compute interiors; the TPU-native equivalent expresses each iteration's
halo exchange as ``lax.ppermute`` neighbour hops inside one jitted
``shard_map`` program — XLA schedules the ICI transfers concurrently with
the interior compute (the same overlap, obtained from the compiler).

The grid is block-sharded over a ``(p, q)`` mesh; each device owns an
``(H/p, W/q)`` block and exchanges one halo row/column per side per
iteration. Zero (Dirichlet) boundaries match
:func:`parsec_tpu.ops.stencil.reference_stencil`.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

__all__ = ["spmd_stencil_5pt"]


def spmd_stencil_5pt(grid: jax.Array, iters: int, mesh: Mesh,
                     axes: Optional[tuple] = None) -> jax.Array:
    """Run ``iters`` Jacobi 5-point steps on a grid block-sharded over
    ``mesh``; returns the final grid with the same sharding."""
    ax_r, ax_c = axes if axes is not None else mesh.axis_names[:2]
    p, q = mesh.shape[ax_r], mesh.shape[ax_c]
    H, W = grid.shape
    assert H % p == 0 and W % q == 0, (grid.shape, (p, q))

    def kernel(g):
        # g: the local (H/p, W/q) block
        ri = lax.axis_index(ax_r)
        ci = lax.axis_index(ax_c)
        h, w = g.shape

        def step(_, cur):
            # neighbour halos: one ppermute per direction. Edge devices
            # receive their own sent row/col, masked to zero below.
            up_perm = [(i, (i + 1) % p) for i in range(p)]      # send down
            down_perm = [(i, (i - 1) % p) for i in range(p)]    # send up
            left_perm = [(i, (i + 1) % q) for i in range(q)]
            right_perm = [(i, (i - 1) % q) for i in range(q)]
            from_up = lax.ppermute(cur[-1:, :], ax_r, up_perm)      # row above mine
            from_down = lax.ppermute(cur[:1, :], ax_r, down_perm)   # row below mine
            from_left = lax.ppermute(cur[:, -1:], ax_c, left_perm)  # col left of mine
            from_right = lax.ppermute(cur[:, :1], ax_c, right_perm) # col right of mine
            zr = jnp.zeros((1, w), cur.dtype)
            zc = jnp.zeros((h, 1), cur.dtype)
            from_up = jnp.where(ri == 0, zr, from_up)
            from_down = jnp.where(ri == p - 1, zr, from_down)
            from_left = jnp.where(ci == 0, zc, from_left)
            from_right = jnp.where(ci == q - 1, zc, from_right)

            up = jnp.concatenate([from_up, cur[:-1, :]], axis=0)
            down = jnp.concatenate([cur[1:, :], from_down], axis=0)
            left = jnp.concatenate([from_left, cur[:, :-1]], axis=1)
            right = jnp.concatenate([cur[:, 1:], from_right], axis=1)
            return 0.25 * (up + down + left + right)

        return lax.fori_loop(0, iters, step, g)

    spec = P(ax_r, ax_c)
    f = shard_map(kernel, mesh=mesh, in_specs=(spec,), out_specs=spec)
    return jax.jit(f)(grid)
