"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference runtime predates long-context ML and has no analog
(SURVEY.md §5.7); its closest capabilities are the pipelined neighbour
exchanges of the broadcast topologies (``remote_dep.c:320-345``) and the
redistribution engine. Here the same *communication patterns* are
expressed TPU-natively as single jitted shard_map programs whose
collectives ride ICI:

* :func:`ring_attention` — blockwise-causal attention over a 1D device
  ring. Every device owns one sequence block of Q/K/V; K/V blocks rotate
  one ICI hop per step (``lax.ppermute``, the neighbour-exchange pattern)
  while a streaming (online-softmax) accumulator keeps the numerics of
  full attention without ever materialising the S×S matrix. Compute at
  each step overlaps the rotation — the same comm/compute overlap the
  reference gets from its comm thread, obtained here from XLA's
  scheduler.

* :func:`ulysses_attention` — all-to-all sequence parallelism: resharding
  [seq-sharded, all heads] → [all seq, head-sharded] (``lax.all_to_all``),
  dense per-head attention, and the inverse reshard. One hop of the
  redistribution engine's "reshard as collective" idea.

Both operate on ``[batch, seq, heads, head_dim]`` arrays sequence-sharded
over one mesh axis and return the same layout.

These are single-program SPMD loops compiled by XLA; the RUNTIME-native
formulation — the same numerics as PTG task graphs whose K/V rotation
rides the eager/rendezvous wire protocol, dispatched through the native
ASYNC path and servable as batched-inference taskpools — lives in
:mod:`parsec_tpu.ops.attention` (USERGUIDE §13).  The two are
bit-compared at matching precision in
``tests/runtime/test_attention_ring.py``; :func:`attention_reference`
here remains the numerics oracle for both.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

_NEG_BIG = -1e30  # finite "-inf" for running-max init (keeps exp() NaN-free)


def attention_reference(q, k, v, causal: bool = False, scale: Optional[float] = None):
    """Dense softmax attention on one device (the numerics oracle)."""
    scale = scale or 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        mask = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        logits = jnp.where(mask, logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", w.astype(v.dtype), v)
    return out


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis: Optional[str] = None,
    causal: bool = False,
    scale: Optional[float] = None,
    use_pallas: bool = False,
) -> jax.Array:
    """Attention over sequence blocks distributed around a device ring.

    ``q, k, v``: ``[B, S, H, D]``, sequence dim sharded over ``axis``.
    R ring steps; at step s the device holding query block ``i`` computes
    against key/value block ``(i + s) mod R`` then forwards K/V one hop.
    Online softmax (running max ``m``, normaliser ``l``, accumulator)
    makes the result exactly dense attention.

    ``use_pallas`` runs the per-step block update as the fused
    :func:`parsec_tpu.ops.pallas_kernels.flash_attention_block` kernel
    (VMEM-resident logits, MXU matmuls) instead of the jnp einsum chain;
    intended for head_dim >= 128 on real TPU hardware (interpret mode
    covers other backends).
    """
    axis = axis or mesh.axis_names[0]
    R = mesh.shape[axis]
    assert q.shape[1] % R == 0, f"ring size {R} must divide seq length {q.shape[1]}"
    scale_v = scale or 1.0 / math.sqrt(q.shape[-1])

    def kernel(q_blk, k_blk, v_blk):
        idx = lax.axis_index(axis)
        Bb, Sb, H, D = q_blk.shape
        qpos = idx * Sb + jnp.arange(Sb)  # global positions of my queries

        if use_pallas:
            from ..ops.pallas_kernels import flash_attention_block

            qh = jnp.transpose(q_blk, (0, 2, 1, 3))  # [B,H,Sb,D]

            def blk_update(acc, m, l, kb, vb, ki):
                kh = jnp.transpose(kb, (0, 2, 1, 3))
                vh = jnp.transpose(vb, (0, 2, 1, 3))
                upd = jax.vmap(jax.vmap(
                    lambda q2, k2, v2, a2, m2, l2: flash_attention_block(
                        q2, k2, v2, a2, m2, l2, idx * Sb, ki * Sb,
                        causal=causal, scale=float(scale_v))))
                a, mm, ll = upd(qh, kh, vh, acc,
                                m[..., None], l[..., None])
                return a, mm[..., 0], ll[..., 0]
        else:
            blk_update = None

        def step(s, carry):
            acc, m, l, kb, vb = carry
            ki = (idx + s) % R  # block id of the resident K/V
            if use_pallas:
                acc_new, m_new, l_new = blk_update(acc, m, l, kb, vb, ki)
            else:
                logits = (jnp.einsum("bqhd,bkhd->bhqk", q_blk, kb)
                          .astype(jnp.float32) * scale_v)
                if causal:
                    kpos = ki * Sb + jnp.arange(Sb)
                    mask = qpos[:, None] >= kpos[None, :]
                    logits = jnp.where(mask[None, None], logits, -jnp.inf)
                m_new = jnp.maximum(m, logits.max(axis=-1))
                p = jnp.exp(logits - m_new[..., None])  # -inf - finite -> 0
                corr = jnp.exp(m - m_new)
                l_new = l * corr + p.sum(axis=-1)
                acc_new = (acc * corr[..., None]
                           + jnp.einsum("bhqk,bkhd->bhqd", p,
                                        vb.astype(jnp.float32)))
            perm = [(i, (i - 1) % R) for i in range(R)]
            kb = lax.ppermute(kb, axis, perm)
            vb = lax.ppermute(vb, axis, perm)
            return (acc_new, m_new, l_new, kb, vb)

        acc0 = _varying(jnp.zeros((Bb, H, Sb, D), jnp.float32), axis)
        m0 = _varying(jnp.full((Bb, H, Sb), _NEG_BIG, jnp.float32), axis)
        l0 = _varying(jnp.zeros((Bb, H, Sb), jnp.float32), axis)
        acc, m, l, _, _ = lax.fori_loop(0, R, step, (acc0, m0, l0, k_blk, v_blk))
        out = acc / l[..., None]
        return jnp.transpose(out, (0, 2, 1, 3)).astype(q_blk.dtype)  # -> [B,Sb,H,D]

    spec = P(None, axis, None, None)
    # pallas_call's out_shape carries no varying-manual-axes info, so the
    # vma consistency check cannot see through it — disable it for this
    # path (numerics are covered by the oracle tests)
    f = shard_map(kernel, mesh=mesh, in_specs=(spec, spec, spec),
                  out_specs=spec, check_vma=not use_pallas)
    return jax.jit(f)(q, k, v)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis: Optional[str] = None,
    causal: bool = False,
    scale: Optional[float] = None,
) -> jax.Array:
    """All-to-all sequence parallelism (DeepSpeed-Ulysses pattern):
    reshard seq-sharded → head-sharded, run dense attention on the full
    sequence for the local head group, reshard back. Two all_to_all
    collectives total; the axis size must divide the head count."""
    axis = axis or mesh.axis_names[0]
    R = mesh.shape[axis]
    assert q.shape[2] % R == 0, f"mesh axis size {R} must divide head count {q.shape[2]}"

    def kernel(q_blk, k_blk, v_blk):
        # [B, Sb, H, D] -> [B, S, H/R, D]: gather seq, scatter heads
        a2a = functools.partial(
            lax.all_to_all, axis_name=axis, split_axis=2, concat_axis=1, tiled=True)
        qh, kh, vh = a2a(q_blk), a2a(k_blk), a2a(v_blk)
        out = attention_reference(qh, kh, vh, causal=causal, scale=scale)
        # [B, S, H/R, D] -> [B, Sb, H, D]: scatter seq, gather heads
        return lax.all_to_all(
            out, axis_name=axis, split_axis=1, concat_axis=2, tiled=True)

    spec = P(None, axis, None, None)
    f = shard_map(kernel, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return jax.jit(f)(q, k, v)


def _varying(x, axis):
    """Mark a constant as device-varying inside shard_map."""
    return lax.pcast(x, axis, to="varying")
