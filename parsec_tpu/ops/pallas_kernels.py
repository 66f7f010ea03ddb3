"""Pallas TPU kernels for the hot tile ops.

The reference offloads its hot BODYs to hand-written device kernels
(CUDA ``.cu`` bodies, ``tests/runtime/cuda/nvlink.jdf:136-155``); the
TPU-native equivalent is Pallas: kernels scheduled explicitly onto
VMEM/MXU with grid-blocked accumulation, fused with their elementwise
pre/post ops so each task BODY is one HBM round-trip.

Kernels here:

* :func:`matmul_update` — ``C = A + alpha * B1 @ op(B2)`` as one
  grid-blocked MXU kernel (the syrk/gemm tile-update bodies of the
  dpotrf taskpool; fuses the subtraction into the accumulation loop).
* :func:`stencil_5pt` — one 2D 5-point stencil step for a tile with
  explicit halo edges (the stencil PTG BODY).
* :func:`stencil_5pt_fused` — T stencil iterations on a resident grid
  without leaving VMEM between iterations (the single-chip fused path;
  the PTG overlap study uses per-step tasks, this is the roofline).
* :func:`flash_attention_block` — one online-softmax block update
  ``(acc, m, l) x (q, k, v) -> (acc, m, l)`` (the ring-attention step
  BODY; never materialises the S x S matrix).

Every wrapper takes ``interpret=None`` meaning "by the platform the
enclosing program is lowered for": Mosaic compilation on a TPU — always —
and the Pallas interpreter elsewhere (so the CPU test suite exercises
identical kernel code).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "matmul",
    "matmul_update",
    "stencil_5pt",
    "stencil_5pt_fused",
    "flash_attention_block",
]


def _pallas(interpret: Optional[bool], args, kernel, **call_kwargs):
    """``pl.pallas_call(kernel, **call_kwargs)(*args)``.  With
    ``interpret=None`` the choice is made where it cannot be wrong: at
    LOWERING, per platform (``lax.platform_dependent``) — a program
    lowered for a TPU carries the Mosaic kernel whatever the process's
    default backend is called, and no other platform is asked to
    compile one."""
    def build(interp: bool):
        return pl.pallas_call(kernel, interpret=interp, **call_kwargs)

    if interpret is not None:
        return build(interpret)(*args)
    return lax.platform_dependent(*args, tpu=build(False),
                                  default=build(True))


def _block(dim: int, want: int, align: int) -> int:
    """Largest block <= want that divides dim, multiple of align when
    possible (falls back to dim itself for small/ragged sizes)."""
    if dim <= want:
        return dim
    b = (want // align) * align
    while b >= align:
        if dim % b == 0:
            return b
        b -= align
    return dim


# -- matmul update ----------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("alpha", "transpose_b", "interpret",
                                             "bm", "bn", "bk", "split_f32"))
def matmul_update(C, A, B, *, alpha: float = -1.0, transpose_b: bool = True,
                  interpret: Optional[bool] = None,
                  bm: int = 512, bn: int = 512, bk: int = 512,
                  split_f32: bool = False):
    """``C + alpha * (A @ B.T)`` (or ``A @ B``) as one fused Pallas kernel.

    The dpotrf update bodies are exactly this shape: syrk is
    ``A - B @ B.T``, gemm is ``A - B1 @ B2.T``. Fusing the addition into
    the MXU accumulation loop writes C once instead of streaming the
    product through HBM twice.

    ``split_f32`` (round-4 VERDICT #5, the fused single-pass f32
    trailing update for getrf): each f32 operand block splits IN VMEM
    into a (hi, lo) bfloat16 pair and the product accumulates the three
    significant cross terms — hi*hi + hi*lo + lo*hi — at MXU bf16 rate
    with f32 accumulation.  Numerically this IS XLA's
    ``Precision.HIGH`` 3-pass decomposition, but as ONE kernel: the f32
    operands cross HBM once (vs once per pass) and no pass intermediate
    is ever materialised, so the op stays MXU-bound instead of
    bandwidth-bound.
    """
    (m, ka) = A.shape
    if transpose_b:
        (n, kb) = B.shape
    else:
        (kb, n) = B.shape
    assert ka == kb and C.shape == (m, n), (C.shape, A.shape, B.shape)
    # MXU-friendly blocks that tile the problem exactly
    bm_ = _block(m, bm, 128)
    bn_ = _block(n, bn, 128)
    bk_ = _block(ka, bk, 128)
    grid = (m // bm_, n // bn_, ka // bk_)

    if transpose_b:
        # kernel consumes B^T blocks: index map reads B[j-block, k-block]
        b_spec = pl.BlockSpec((bn_, bk_), lambda i, j, k: (j, k))
        b_op = lambda b: b.T
    else:
        b_spec = pl.BlockSpec((bk_, bn_), lambda i, j, k: (k, j))
        b_op = lambda b: b

    def kernel(c_in_ref, a_ref, b_ref, o_ref):
        k = pl.program_id(2)

        @pl.when(k == 0)
        def _init():
            o_ref[:] = c_in_ref[:]

        a = a_ref[:]
        b = b_op(b_ref[:])
        if split_f32:
            f32 = jnp.float32
            a_hi = a.astype(jnp.bfloat16)
            a_lo = (a - a_hi.astype(f32)).astype(jnp.bfloat16)
            b_hi = b.astype(jnp.bfloat16)
            b_lo = (b - b_hi.astype(f32)).astype(jnp.bfloat16)
            prod = jnp.dot(a_hi, b_hi, preferred_element_type=f32)
            prod += jnp.dot(a_hi, b_lo, preferred_element_type=f32)
            prod += jnp.dot(a_lo, b_hi, preferred_element_type=f32)
            o_ref[:] += alpha * prod
        else:
            o_ref[:] += alpha * jnp.dot(
                a, b, preferred_element_type=o_ref.dtype)

    return _pallas(
        interpret, (C, A, B), kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), C.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm_, bn_), lambda i, j, k: (i, j)),   # C
            pl.BlockSpec((bm_, bk_), lambda i, j, k: (i, k)),   # A
            b_spec,                                             # B
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, k: (i, j)),
        cost_estimate=pl.CostEstimate(
            flops=(3 if split_f32 else 1) * 2 * m * n * ka + m * n,
            # per-operand dtypes: mixed-precision callers pass bf16 A/B
            # with an f32 C — half the operand traffic of all-f32
            bytes_accessed=(m * ka * A.dtype.itemsize
                            + n * ka * B.dtype.itemsize
                            + 2 * m * n * C.dtype.itemsize),
            transcendentals=0),
    )


@functools.partial(jax.jit, static_argnames=("transpose_b", "interpret",
                                             "bm", "bn", "bk"))
def matmul(A, B, *, transpose_b: bool = True,
           interpret: Optional[bool] = None,
           bm: int = 512, bn: int = 512, bk: int = 512):
    """``A @ B.T`` (or ``A @ B``) as a grid-blocked MXU kernel (no
    accumulate-into input — the k==0 step initialises the output)."""
    (m, ka) = A.shape
    if transpose_b:
        (n, kb) = B.shape
        b_spec_shape = lambda bn_, bk_: pl.BlockSpec((bn_, bk_), lambda i, j, k: (j, k))
        b_op = lambda b: b.T
    else:
        (kb, n) = B.shape
        b_spec_shape = lambda bn_, bk_: pl.BlockSpec((bk_, bn_), lambda i, j, k: (k, j))
        b_op = lambda b: b
    assert ka == kb, (A.shape, B.shape)
    bm_ = _block(m, bm, 128)
    bn_ = _block(n, bn, 128)
    bk_ = _block(ka, bk, 128)
    grid = (m // bm_, n // bn_, ka // bk_)

    def kernel(a_ref, b_ref, o_ref):
        k = pl.program_id(2)

        @pl.when(k == 0)
        def _init():
            o_ref[:] = jnp.zeros_like(o_ref)

        o_ref[:] += jnp.dot(a_ref[:], b_op(b_ref[:]),
                            preferred_element_type=o_ref.dtype)

    return _pallas(
        interpret, (A, B), kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), A.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda i, j, k: (i, k)),
            b_spec_shape(bn_, bk_),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, k: (i, j)),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * n * ka,
            bytes_accessed=(m * ka + n * ka + m * n) * A.dtype.itemsize,
            transcendentals=0),
    )


# -- 2D 5-point stencil -----------------------------------------------------

def _stencil_kernel(old_ref, up_ref, down_ref, left_ref, right_ref, o_ref):
    old = old_ref[:]
    h, w = old.shape
    # shifted neighbours with halo edges spliced in; jnp.roll-free slicing
    up = jnp.concatenate([up_ref[:], old[:-1, :]], axis=0)        # value above
    down = jnp.concatenate([old[1:, :], down_ref[:]], axis=0)     # value below
    left = jnp.concatenate([left_ref[:], old[:, :-1]], axis=1)    # value left
    right = jnp.concatenate([old[:, 1:], right_ref[:]], axis=1)   # value right
    o_ref[:] = 0.25 * (up + down + left + right)


@functools.partial(jax.jit, static_argnames=("interpret",))
def stencil_5pt(old, up, down, left, right, *, interpret: Optional[bool] = None):
    """One 5-point Jacobi step for an ``(h, w)`` tile.

    ``up``/``down`` are ``(1, w)`` halo rows, ``left``/``right`` are
    ``(h, 1)`` halo columns (zeros at physical boundaries). Equivalent to
    the zero-padded formula in :mod:`parsec_tpu.ops.stencil` but runs as
    a single VMEM-resident kernel (one read + one write of the tile).
    """
    h, w = old.shape
    specs = [pl.BlockSpec(memory_space=pltpu.VMEM)] * 5
    return _pallas(
        interpret, (old, up, down, left, right), _stencil_kernel,
        out_shape=jax.ShapeDtypeStruct((h, w), old.dtype),
        in_specs=specs,
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
    )


@functools.partial(jax.jit, static_argnames=("iters", "interpret"))
def stencil_5pt_fused(grid, iters: int, *, interpret: Optional[bool] = None):
    """``iters`` Jacobi 5-point steps with the grid resident in VMEM.

    Scope (measured on v5e): grids must fit VMEM with headroom — up to
    ~512x512 f32 compiles; beyond that the in-loop temporaries blow the
    scoped-VMEM budget. At those sizes XLA's own ``fori_loop`` already
    keeps the grid VMEM-resident, so this kernel measures parity (0.98x),
    not a win — it exists as the explicit-residency reference point for
    the stencil study; the real large-grid path is the per-tile PTG BODY
    (:func:`stencil_5pt`) or the SPMD halo-exchange program
    (:func:`parsec_tpu.parallel.spmd_stencil_5pt`).
    """
    h, w = grid.shape

    def kernel(g_ref, o_ref, scratch):
        scratch[:] = g_ref[:]

        def step(_, __):
            g = scratch[:]
            zr = jnp.zeros((1, w), g.dtype)
            zc = jnp.zeros((h, 1), g.dtype)
            up = jnp.concatenate([zr, g[:-1, :]], axis=0)
            down = jnp.concatenate([g[1:, :], zr], axis=0)
            left = jnp.concatenate([zc, g[:, :-1]], axis=1)
            right = jnp.concatenate([g[:, 1:], zc], axis=1)
            scratch[:] = 0.25 * (up + down + left + right)
            return ()

        jax.lax.fori_loop(0, iters, step, ())
        o_ref[:] = scratch[:]

    return _pallas(
        interpret, (grid,), kernel,
        out_shape=jax.ShapeDtypeStruct((h, w), grid.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((h, w), grid.dtype)],
    )


# -- flash attention block update ------------------------------------------

@functools.partial(jax.jit, static_argnames=("causal", "scale", "interpret", "bq"))
def flash_attention_block(q, k, v, acc, m, l, q_off, k_off, *,
                          causal: bool = False, scale: float = 1.0,
                          interpret: Optional[bool] = None, bq: int = 512):
    """One online-softmax block update — the ring-attention step BODY.

    Shapes (one head): ``q``: (Sq, D), ``k``/``v``: (Sk, D),
    carry ``acc``: (Sq, D) f32, ``m``/``l``: (Sq, 1) f32.
    ``q_off``/``k_off`` are the global sequence offsets of the two blocks
    (scalars) used for the causal mask. Returns updated ``(acc, m, l)``.

    Grid-blocked over Sq; K/V stay resident per block row. The S x S
    logits tile exists only in VMEM.
    """
    Sq, D = q.shape
    Sk, _ = k.shape
    bq_ = _block(Sq, bq, 128)
    grid = (Sq // bq_,)
    offs = jnp.asarray([[q_off], [k_off]], jnp.int32)   # (2,1) SMEM scalars

    def kernel(off_ref, q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
               o_acc, o_m, o_l):
        i = pl.program_id(0)
        qb = q_ref[:].astype(jnp.float32)
        kb = k_ref[:].astype(jnp.float32)
        logits = jnp.dot(qb, kb.T, preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = off_ref[0, 0] + i * bq_ + jax.lax.broadcasted_iota(
                jnp.int32, (bq_, Sk), 0)
            kpos = off_ref[1, 0] + jax.lax.broadcasted_iota(
                jnp.int32, (bq_, Sk), 1)
            # mask with -inf, not a finite big-negative: a fully-masked
            # block must leave the carry untouched even when m is still at
            # its -1e30 init (exp(-inf - finite) == 0 exactly)
            logits = jnp.where(qpos >= kpos, logits, -jnp.inf)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, logits.max(axis=-1, keepdims=True))
        p = jnp.exp(logits - m_new)
        corr = jnp.exp(m_prev - m_new)
        o_l[:] = l_ref[:] * corr + p.sum(axis=-1, keepdims=True)
        o_m[:] = m_new
        o_acc[:] = acc_ref[:] * corr + jnp.dot(
            p, v_ref[:].astype(jnp.float32), preferred_element_type=jnp.float32)

    row = lambda i: (i, 0)
    return _pallas(
        interpret, (offs, q, k, v, acc, m, l), kernel,
        grid=grid,
        out_shape=(
            jax.ShapeDtypeStruct((Sq, D), jnp.float32),
            jax.ShapeDtypeStruct((Sq, 1), jnp.float32),
            jax.ShapeDtypeStruct((Sq, 1), jnp.float32),
        ),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),           # offsets
            pl.BlockSpec((bq_, D), row),                     # q
            pl.BlockSpec((Sk, D), lambda i: (0, 0)),         # k
            pl.BlockSpec((Sk, D), lambda i: (0, 0)),         # v
            pl.BlockSpec((bq_, D), row),                     # acc
            pl.BlockSpec((bq_, 1), row),                     # m
            pl.BlockSpec((bq_, 1), row),                     # l
        ],
        out_specs=(
            pl.BlockSpec((bq_, D), row),
            pl.BlockSpec((bq_, 1), row),
            pl.BlockSpec((bq_, 1), row),
        ),
    )
