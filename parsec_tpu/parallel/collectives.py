"""Collective-communication building blocks over mesh axes.

The reference's dependency broadcasts travel down host-chosen topology
trees — star, chain-pipeline, binomial — re-rooted at the sender
(``/root/reference/parsec/remote_dep.c:262-345``, the reference's MCA
``runtime_comm_coll_bcast``). On TPU the transport is ICI and the
primitives are XLA collectives; these helpers express the same three
topologies as rounds of ``lax.ppermute`` inside ``shard_map``, plus thin
wrappers over the standard collectives.

All functions are meant to be called *inside* a ``shard_map``-ed function
with the named axis in scope.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def my_index(axis: str) -> jax.Array:
    return lax.axis_index(axis)


def shift(x, axis: str, offset: int = 1):
    """Ring rotation by ``offset`` along a mesh axis (ICI neighbour hop)."""
    n = lax.axis_size(axis)
    perm = [(i, (i + offset) % n) for i in range(n)]
    return lax.ppermute(x, axis, perm)


def bcast_star(x, axis: str, root: int = 0):
    """Star broadcast: root reaches everyone in one logical round (the
    reference's default flat topology). ppermute demands a permutation, so
    the one-to-all round is a masked psum."""
    contrib = jnp.where(lax.axis_index(axis) == root, x, jnp.zeros_like(x))
    return lax.psum(contrib, axis)


def bcast_chain(x, axis: str, root: int = 0):
    """Chain-pipeline broadcast: n-1 neighbour hops; each round forwards to
    the next rank (reference chain topology, best for large payloads on a
    ring interconnect)."""
    n = lax.axis_size(axis)
    cur = x
    for r in range(n - 1):
        src = (root + r) % n
        dst = (root + r + 1) % n
        recv = lax.ppermute(cur, axis, [(src, dst)])
        cur = jnp.where(lax.axis_index(axis) == dst, recv, cur)
    return cur


def bcast_binomial(x, axis: str, root: int = 0):
    """Binomial-tree broadcast: ceil(log2 n) rounds, round r has the first
    2^r holders forward to holders 2^r..2^(r+1)-1 (reference binomial
    topology, latency-optimal for small activation messages)."""
    n = lax.axis_size(axis)
    rounds = max(1, math.ceil(math.log2(n))) if n > 1 else 0
    cur = x
    for r in range(rounds):
        span = 1 << r
        perm = []
        for i in range(span):
            j = i + span
            if j < n:
                perm.append(((root + i) % n, (root + j) % n))
        if not perm:
            break
        recv = lax.ppermute(cur, axis, perm)
        idx = (lax.axis_index(axis) - root) % n
        is_dst = (idx >= span) & (idx < 2 * span)
        cur = jnp.where(is_dst, recv, cur)
    return cur


def bcast(x, axis: str, root: int = 0, topology: Optional[str] = None):
    """Broadcast by ``topology`` (star | chain | binomial, the
    default; reference ``runtime_comm_coll_bcast``: 0=star 1=chain
    2=binomial)."""
    fn = {"star": bcast_star, "chain": bcast_chain,
          "binomial": bcast_binomial}[topology or "binomial"]
    return fn(x, axis, root)


# thin standard wrappers (named for discoverability next to the trees)

def allreduce_sum(x, axis: str):
    return lax.psum(x, axis)


def reduce_scatter_sum(x, axis: str, tiled_axis: int = 0):
    return lax.psum_scatter(x, axis, scatter_dimension=tiled_axis, tiled=True)


def allgather(x, axis: str, tiled_axis: int = 0):
    return lax.all_gather(x, axis, axis=tiled_axis, tiled=True)
