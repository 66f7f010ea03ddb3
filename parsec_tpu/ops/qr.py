"""Tiled Householder QR factorization as a PTG — the second flagship.

The reference ecosystem's dense-QR lives in DPLASMA (like dpotrf, not in
the PaRSEC repo itself — SURVEY.md §6); this is the classic PLASMA-style
tiled QR task graph, re-derived TPU-first:

  for k:  geqrt(k):       A[k,k]          -> Q_k, R_kk
          unmqr(k, n):    A[k,n]          <- Q_k^T A[k,n]        (n > k)
          tsqrt(k, m):    [R_kk; A[m,k]]  -> Q_km, R_kk'         (m > k)
          tsmqr(k, m, n): [A[k,n]; A[m,n]] <- Q_km^T [ . ; . ]   (m,n > k)

Representation choice (TPU-first): instead of the LAPACK compact-WY
(V, T) storage the reference consumers use, the orthogonal factors are
materialised as small dense Q blocks passed along NEW flows — every
update becomes a plain MXU matmul, which is the fast shape on this
hardware; the cost is extra FLOPs in tsqrt (complete QR of a 2nb x nb
stack) amortised across the row's tsmqr updates.

The factorization leaves R in the upper triangle of A (below-diagonal
tiles zeroed). Orthogonality is implicit; the invariant A^T A = R^T R
verifies the result without tracking Q (tests).
"""

from __future__ import annotations

import numpy as np

from ..core.lifecycle import AccessMode
from ..dsl.ptg import PTG

IN = AccessMode.IN
INOUT = AccessMode.INOUT

try:
    import jax.numpy as jnp
except Exception:  # pragma: no cover
    jnp = None


# -- tile bodies -------------------------------------------------------------

def geqrt_cpu(T, Q, **_):
    q, r = np.linalg.qr(T)
    T[:] = r
    Q[:] = q


def geqrt_tpu(T, Q, **_):
    q, r = jnp.linalg.qr(T)
    return r, q


def unmqr_cpu(Q, C, **_):
    C[:] = Q.T @ C


def unmqr_tpu(Q, C, **_):
    return jnp.dot(Q.T, C, precision="highest")


def tsqrt_cpu(R, B, Q, **_):
    nb = R.shape[0]
    stacked = np.vstack([np.triu(R), B])
    q, r = np.linalg.qr(stacked, mode="complete")
    R[:] = r[:nb]
    B[:] = 0.0
    Q[:] = q


def tsqrt_tpu(R, B, Q, **_):
    nb = R.shape[0]
    stacked = jnp.vstack([jnp.triu(R), B])
    q, r = jnp.linalg.qr(stacked, mode="complete")
    return r[:nb], jnp.zeros_like(B), q


def tsmqr_cpu(Q, C1, C2, **_):
    nb = C1.shape[0]
    s = Q.T @ np.vstack([C1, C2])
    C1[:] = s[:nb]
    C2[:] = s[nb:]


def tsmqr_tpu(Q, C1, C2, **_):
    nb = C1.shape[0]
    s = jnp.dot(Q.T, jnp.vstack([C1, C2]), precision="highest")
    return s[:nb], s[nb:]


def _dot_bf16(a, b):
    """bf16 operands, f32 accumulation: one MXU pass."""
    return jnp.dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)


def unmqr_bf16(Q, C, **_):
    return _dot_bf16(Q.T, C)


def tsmqr_bf16(Q, C1, C2, **_):
    nb = C1.shape[0]
    s = _dot_bf16(Q.T, jnp.vstack([C1, C2]))
    return s[:nb], s[nb:]


def unmqr_pallas(Q, C, **_):
    from .pallas_kernels import matmul

    return matmul(Q.T, C, transpose_b=False)


def tsmqr_pallas(Q, C1, C2, **_):
    from .pallas_kernels import matmul

    nb = C1.shape[0]
    s = matmul(Q.T, jnp.vstack([C1, C2]), transpose_b=False)
    return s[:nb], s[nb:]


# -- the PTG -----------------------------------------------------------------

def qr_ptg(*, use_tpu: bool = True, use_cpu: bool = True,
           use_pallas: bool = False, bf16_updates: bool = False) -> PTG:
    """Build the tiled-QR PTG. Instantiate with ``.taskpool(NT=A.mt, A=A,
    TILE_SHAPE=(nb, nb), TILE_DTYPE=..., QSHAPE2=(dtype, (2*nb, 2*nb)))``
    — the NEW-flow Q blocks are allocated from ``TILE_SHAPE`` except
    tsqrt's, whose ``[type=QSHAPE2]`` dep property resolves the (2nb, 2nb)
    stacked-Q shape through the constants (device chores are functional
    and ignore the scratch; the shapes matter for the in-place CPU path).
    :func:`run_qr` fills these in.

    ``bf16_updates`` runs the unmqr/tsmqr updates with bf16 operands and
    f32 accumulation (one MXU pass for the six of ``highest``): the
    lower-precision path the benchmark's check is held against.

    Square tile grids with uniform tiles (N divisible by nb)."""
    ptg = PTG("geqrf")
    unmqr_dev, tsmqr_dev = (
        (unmqr_bf16, tsmqr_bf16) if bf16_updates
        else (unmqr_pallas, tsmqr_pallas) if use_pallas
        else (unmqr_tpu, tsmqr_tpu))

    def bodies(cpu, tpu):
        kw = {}
        if use_cpu:
            kw["cpu"] = cpu
        if use_tpu or use_pallas:
            kw["tpu"] = tpu
        return kw

    geqrt = ptg.task_class("geqrt", k="0 .. NT-1")
    geqrt.affinity("A(k, k)")
    geqrt.priority("(NT - k) * 1000")
    geqrt.flow("T", INOUT,
               "<- (k == 0) ? A(k, k) : C2 tsmqr(k-1, k, k)",
               "-> (k < NT-1) ? R tsqrt(k, k+1)",
               "-> (k == NT-1) ? A(k, k)")
    geqrt.flow("Q", INOUT,
               "<- NEW",
               "-> Q unmqr(k, k+1 .. NT-1)")
    geqrt.body(**bodies(geqrt_cpu, geqrt_tpu))

    tsqrt = ptg.task_class("tsqrt", k="0 .. NT-2", m="k+1 .. NT-1")
    tsqrt.affinity("A(m, k)")
    tsqrt.priority("(NT - m) * 100 + 500")
    tsqrt.flow("R", INOUT,
               "<- (m == k+1) ? T geqrt(k) : R tsqrt(k, m-1)",
               "-> (m < NT-1) ? R tsqrt(k, m+1) : A(k, k)")
    tsqrt.flow("B", INOUT,
               "<- (k == 0) ? A(m, k) : C2 tsmqr(k-1, m, k)",
               "-> A(m, k)")
    tsqrt.flow("Q", INOUT,
               "<- NEW [type=QSHAPE2]",  # (2nb, 2nb): taskpool constant
               "-> Q tsmqr(k, m, k+1 .. NT-1)")
    tsqrt.body(**bodies(tsqrt_cpu, tsqrt_tpu))

    unmqr = ptg.task_class("unmqr", k="0 .. NT-2", n="k+1 .. NT-1")
    unmqr.affinity("A(k, n)")
    unmqr.priority("(NT - n) * 100 + 400")
    unmqr.flow("Q", IN, "<- Q geqrt(k)")
    unmqr.flow("C", INOUT,
               "<- (k == 0) ? A(k, n) : C2 tsmqr(k-1, k, n)",
               "-> C1 tsmqr(k, k+1, n)")
    unmqr.body(**bodies(unmqr_cpu, unmqr_dev))

    tsmqr = ptg.task_class("tsmqr", k="0 .. NT-2", m="k+1 .. NT-1", n="k+1 .. NT-1")
    tsmqr.affinity("A(m, n)")
    tsmqr.priority("(NT - m) * 10")
    tsmqr.flow("Q", IN, "<- Q tsqrt(k, m)")
    tsmqr.flow("C1", INOUT,
               "<- (m == k+1) ? C unmqr(k, n) : C1 tsmqr(k, m-1, n)",
               "-> (m < NT-1) ? C1 tsmqr(k, m+1, n) : A(k, n)")
    tsmqr.flow("C2", INOUT,
               "<- (k == 0) ? A(m, n) : C2 tsmqr(k-1, m, n)",
               "-> (m == k+1 and n == k+1) ? T geqrt(k+1)",
               "-> (m == k+1 and n > k+1) ? C unmqr(k+1, n)",
               "-> (m > k+1 and n == k+1) ? B tsqrt(k+1, m)",
               "-> (m > k+1 and n > k+1) ? C2 tsmqr(k+1, m, n)",
               "-> A(m, n)")
    tsmqr.body(**bodies(tsmqr_cpu, tsmqr_dev))

    return ptg


def run_qr(context, A, *, use_tpu: bool = True, use_cpu: bool = True) -> None:
    """Factorize TiledMatrix ``A`` in place: A := R (upper), zeros below."""
    if A.m != A.n or A.mb != A.nb or A.m % A.mb != 0:
        raise ValueError(
            f"tiled QR needs a square matrix with uniform square tiles "
            f"(N divisible by nb); got {A.m}x{A.n}, tiles {A.mb}x{A.nb}")
    nb = A.mb
    tp = qr_ptg(use_tpu=use_tpu, use_cpu=use_cpu).taskpool(
        NT=A.mt, A=A, TILE_SHAPE=(nb, nb), TILE_DTYPE=A.default_dtype,
        QSHAPE2=(A.default_dtype, (2 * nb, 2 * nb)))
    context.add_taskpool(tp)
    ok = tp.wait(timeout=None)
    if not ok:
        raise RuntimeError("qr taskpool did not quiesce")
