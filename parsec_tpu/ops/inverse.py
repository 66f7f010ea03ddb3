"""The inverse of a symmetric positive definite matrix, in place, as three
taskpools composed: ``dpotrf``, ``dtrtri``, ``dlauum``.

DPLASMA's ``dplasma_dpoinv_sync`` (``tests/testing_zpoinv.c``) and LAPACK's
``dpotrf`` + ``dpotri``: ``A = L L^T``, ``W = L^-1``, ``A^-1 = W^T W``,
lower storage, each step a tile algorithm of its own over the SAME tiles
(PLASMA's ``pzpotrf``, ``pztrtri``, ``pzlauum``; DPLASMA's ``zpotrf_L.jdf``,
``ztrtri_L.jdf``, ``zlauum_L.jdf``).  :func:`poinv` is the ``_sync`` form:
three pools, member *i+1* starting when member *i* has ended
(:func:`parsec_tpu.core.compound.compose`), not the merged
``zpoinv_L.jdf`` that pipelines across the steps.

``trtri`` (the inverse of the lower, non-unit triangular factor)::

  for k:  trtri_trsm_r(k, m):    A[m,k]  = -A[m,k] @ A[k,k]^-1        (m > k)
          trtri_gemm(k, m, n):   A[m,n] += A[m,k] @ A[k,n]        (m > k > n)
          trtri_trsm_l(k, n):    A[k,n]  = A[k,k]^-1 @ A[k,n]         (n < k)
          trtri_diag(k):         A[k,k]  = tril(A[k,k])^-1

``lauum`` (``W^T W`` of the lower triangular ``W``)::

  for k:  lauum_syrk(k, n):      A[n,n] += A[k,n]^T @ A[k,n]          (n < k)
          lauum_gemm(k, m, n):   A[m,n] += A[k,m]^T @ A[k,n]      (n < m < k)
          lauum_trmm(k, n):      A[k,n]  = tril(A[k,k])^T @ A[k,n]    (n < k)
          lauum_diag(k):         A[k,k]  = tril(A[k,k])^T @ tril(A[k,k])

Each DAG has dpotrf's task count.  The sources work in place and order a
tile's readers before its overwriter by the loop nest; a PTG has no loop
nest, and the runtime keeps ONE version of a tile where it lives, so those
orders are stated: a ``CTL`` flow from every reader of a version to the
task that overwrites it (the linter's PTG011 otherwise).  Everything else
is the tiles' dataflow, as in ``ops/cholesky.py``.
"""

from __future__ import annotations

import numpy as np

from ..core.compound import CompoundTaskpool, compose
from ..core.lifecycle import AccessMode, DEV_CPU, DEV_TPU
from ..dsl.ptg import PTG
from .cholesky import cholesky_ptg

try:
    import jax
    import jax.numpy as jnp
    from jax.scipy.linalg import solve_triangular as _jsolve
except Exception:  # pragma: no cover
    jax = jnp = None

IN = AccessMode.IN
INOUT = AccessMode.INOUT

#: the task classes of the two DAGs, in the order of the loop nests above
TRTRI_CLASSES = ("trtri_trsm_r", "trtri_gemm", "trtri_trsm_l", "trtri_diag")
LAUUM_CLASSES = ("lauum_syrk", "lauum_gemm", "lauum_trmm", "lauum_diag")

#: priorities, as in ``cholesky.PRIORITY``: the step first (both DAGs
#: advance by k), within a step what the next step waits for
PRIORITY = {
    "trtri_trsm_r": "(NT - k) * 1000 + 500",
    "trtri_gemm": "(NT - k) * 1000 + (NT - m)",
    "trtri_trsm_l": "(NT - k) * 1000 - 100",
    "trtri_diag": "(NT - k) * 1000 - 200",
    "lauum_syrk": "(NT - k) * 1000 + 10",
    "lauum_gemm": "(NT - k) * 1000 + (NT - m)",
    "lauum_trmm": "(NT - k) * 1000 - 100",
    "lauum_diag": "(NT - k) * 1000 - 200",
}


# -- tile bodies -------------------------------------------------------------

def _dot(a, b, bf16: bool):
    """``a @ b`` at ``highest``, or with bfloat16 operands and float32
    accumulation (the control path: one MXU pass where ``highest`` makes
    six, operands rounded to 8 bits)."""
    if bf16:
        return jnp.dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32).astype(a.dtype)
    return jnp.dot(a, b, precision="highest")


def trtri_trsm_r_cpu(T, C, **_):
    # X T = -C  <=>  T^T X^T = -C^T
    C[:] = -np.linalg.solve(np.tril(T).T, C.T).T


def trtri_trsm_r_tpu(T, C, **_):
    return -_jsolve(T, C.T, lower=True, trans=1).T


def trtri_gemm_cpu(A, B1, B2, **_):
    A += B1 @ B2


def trtri_gemm_tpu(A, B1, B2, **_):
    return A + _dot(B1, B2, False)


def trtri_gemm_bf16(A, B1, B2, **_):
    return A + _dot(B1, B2, True)


def trtri_trsm_l_cpu(T, C, **_):
    C[:] = np.linalg.solve(np.tril(T), C)


def trtri_trsm_l_tpu(T, C, **_):
    return _jsolve(T, C, lower=True)


def trtri_diag_cpu(T, **_):
    T[:] = np.linalg.solve(np.tril(T), np.eye(T.shape[0], dtype=T.dtype))


def trtri_diag_tpu(T, **_):
    return _jsolve(T, jnp.eye(T.shape[0], dtype=T.dtype), lower=True)


def lauum_syrk_cpu(A, B, **_):
    A += B.T @ B


def lauum_syrk_tpu(A, B, **_):
    return A + _dot(B.T, B, False)


def lauum_syrk_bf16(A, B, **_):
    return A + _dot(B.T, B, True)


def lauum_gemm_cpu(A, B1, B2, **_):
    A += B1.T @ B2


def lauum_gemm_tpu(A, B1, B2, **_):
    return A + _dot(B1.T, B2, False)


def lauum_gemm_bf16(A, B1, B2, **_):
    return A + _dot(B1.T, B2, True)


def lauum_trmm_cpu(T, C, **_):
    C[:] = np.tril(T).T @ C


def lauum_trmm_tpu(T, C, **_):
    return _dot(jnp.tril(T).T, C, False)


def lauum_diag_cpu(T, **_):
    L = np.tril(T)
    T[:] = L.T @ L


def lauum_diag_tpu(T, **_):
    L = jnp.tril(T)
    return _dot(L.T, L, False)


def _one_call_a_wave(body, *flows):
    """Names as ``body._batched`` the form that runs a wave of ``body``
    as ONE batched call over its tile keywords ``flows``, stacked
    task-major (``TpuDevice._launch``).  For the classes that are few
    and cheap on the chip (a step's solves, its diagonal tile, its
    ``syrk`` and ``trmm``): an unrolled wave of 16 triangular solves is
    16 solves to COMPILE, the batched one is one, and the cell's cold
    set-up is three DAGs' wave programs.  The ``gemm`` updates, where
    the work is, stay unrolled: stacking would copy four tiles a task."""
    def wave(**kw):
        return (jax.vmap(body)(*(kw[f] for f in flows)),)
    body._batched = wave


if jax is not None:
    for _body, _flows in ((trtri_trsm_r_tpu, "TC"), (trtri_trsm_l_tpu, "TC"),
                          (trtri_diag_tpu, "T"), (lauum_syrk_tpu, "AB"),
                          (lauum_syrk_bf16, "AB"), (lauum_trmm_tpu, "TC"),
                          (lauum_diag_tpu, "T")):
        _one_call_a_wave(_body, *_flows)


def _bodies(use_cpu: bool, use_tpu: bool):
    def bodies(cpu, tpu):
        kw = {}
        if use_cpu:
            kw[DEV_CPU] = cpu
        if use_tpu:
            kw[DEV_TPU] = tpu
        return kw
    return bodies


# -- the PTGs ----------------------------------------------------------------

def trtri_ptg(*, use_tpu: bool = True, use_cpu: bool = True,
              bf16_updates: bool = False) -> PTG:
    """Build the dtrtri PTG (lower, non-unit; instantiate with
    ``.taskpool(NT=A.mt, A=A)`` where ``A`` holds the triangular factor
    in its lower tiles; the inverse replaces it).  ``bf16_updates``: the
    gemm updates take bfloat16 operands (the benchmark's control path)."""
    ptg = PTG("dtrtri")
    bodies = _bodies(use_cpu, use_tpu)

    # A[m,k] = -A[m,k] A[k,k]^-1: the ORIGINAL tiles, so all are ready at
    # once; its output is what step k's gemms multiply by
    trsm_r = ptg.task_class("trtri_trsm_r", k="0 .. NT-2", m="k+1 .. NT-1")
    trsm_r.affinity("A(m, k)")
    trsm_r.priority(PRIORITY["trtri_trsm_r"])
    trsm_r.flow("T", IN, "<- A(k, k)")
    trsm_r.flow("C", INOUT,
                "<- A(m, k)",
                "-> B1 trtri_gemm(k, m, 0 .. k-1)",
                "-> (m == k+1) ? C trtri_trsm_l(m, k) "
                ": A trtri_gemm(k+1, m, k)",
                "-> (m == k+1) ? B2 trtri_gemm(m, m+1 .. NT-1, k)")
    trsm_r.ctl("read", "-> solved trtri_diag(k)")
    trsm_r.body(**bodies(trtri_trsm_r_cpu, trtri_trsm_r_tpu))

    # A[m,n] += A[m,k] A[k,n]: a chain over k = n+1 .. m-1 between
    # trsm_r(n, m) and trsm_l(m, n).  It reads A[m,k] as trsm_r(k, m) left
    # it and A[k,n] before trsm_l(k, n): both overwriters wait for it
    gemm = ptg.task_class("trtri_gemm", k="1 .. NT-2", m="k+1 .. NT-1",
                          n="0 .. k-1")
    gemm.affinity("A(m, n)")
    gemm.priority(PRIORITY["trtri_gemm"])
    gemm.flow("A", INOUT,
              "<- (k == n+1) ? C trtri_trsm_r(n, m) : A trtri_gemm(k-1, m, n)",
              "-> (k == m-1) ? C trtri_trsm_l(m, n) : A trtri_gemm(k+1, m, n)",
              "-> (k == m-1) ? B2 trtri_gemm(m, m+1 .. NT-1, n)")
    gemm.flow("B1", IN, "<- C trtri_trsm_r(k, m)")
    gemm.flow("B2", IN,
              "<- (k == n+1) ? C trtri_trsm_r(n, k) : A trtri_gemm(k-1, k, n)")
    # the overwriter of A[m,k-1] (this task, when n == k-1) waits for
    # step k-1's readers of it
    gemm.ctl("b1_free",
             "<- (n == k-1) ? b1_read trtri_gemm(k-1, m, 0 .. k-2)")
    gemm.ctl("b1_read",
             "-> (m == k+1) ? b1_free trtri_trsm_l(m, k) "
             ": b1_free trtri_gemm(k+1, m, k)")
    gemm.ctl("b2_read", "-> b2_free trtri_trsm_l(k, n)")
    gemm.body(**bodies(trtri_gemm_cpu,
                       trtri_gemm_bf16 if bf16_updates else trtri_gemm_tpu))

    # A[k,n] = A[k,k]^-1 A[k,n]: the last version of A[k,n]
    trsm_l = ptg.task_class("trtri_trsm_l", k="1 .. NT-1", n="0 .. k-1")
    trsm_l.affinity("A(k, n)")
    trsm_l.priority(PRIORITY["trtri_trsm_l"])
    trsm_l.flow("T", IN, "<- A(k, k)")
    trsm_l.flow("C", INOUT,
                "<- (n == k-1) ? C trtri_trsm_r(n, k) "
                ": A trtri_gemm(k-1, k, n)",
                "-> A(k, n)")
    trsm_l.ctl("b1_free",
               "<- (n == k-1) ? b1_read trtri_gemm(k-1, k, 0 .. k-2)")
    trsm_l.ctl("b2_free", "<- b2_read trtri_gemm(k, k+1 .. NT-1, n)")
    trsm_l.ctl("read", "-> solved trtri_diag(k)")
    trsm_l.body(**bodies(trtri_trsm_l_cpu, trtri_trsm_l_tpu))

    # A[k,k] = tril(A[k,k])^-1, once step k's solves have read it
    diag = ptg.task_class("trtri_diag", k="0 .. NT-1")
    diag.affinity("A(k, k)")
    diag.priority(PRIORITY["trtri_diag"])
    diag.flow("T", INOUT, "<- A(k, k)", "-> A(k, k)")
    diag.ctl("solved",
             "<- read trtri_trsm_r(k, k+1 .. NT-1)",
             "<- read trtri_trsm_l(k, 0 .. k-1)")
    diag.body(**bodies(trtri_diag_cpu, trtri_diag_tpu))
    return ptg


def lauum_ptg(*, use_tpu: bool = True, use_cpu: bool = True,
              bf16_updates: bool = False) -> PTG:
    """Build the dlauum PTG (lower; instantiate with ``.taskpool(NT=A.mt,
    A=A)`` where ``A`` holds the lower triangular ``W``; the lower
    triangle of ``W^T W`` replaces it, diagonal tiles whole).
    ``bf16_updates``: the syrk and gemm updates take bfloat16 operands
    (the benchmark's control path)."""
    ptg = PTG("dlauum")
    bodies = _bodies(use_cpu, use_tpu)

    # A[n,n] += A[k,n]^T A[k,n]: the chain of A[n,n] after lauum_diag(n)
    syrk = ptg.task_class("lauum_syrk", k="1 .. NT-1", n="0 .. k-1")
    syrk.affinity("A(n, n)")
    syrk.priority(PRIORITY["lauum_syrk"])
    syrk.flow("A", INOUT,
              "<- (k == n+1) ? T lauum_diag(n) : A lauum_syrk(k-1, n)",
              "-> (k == NT-1) ? A(n, n) : A lauum_syrk(k+1, n)")
    syrk.flow("B", IN, "<- A(k, n)")
    syrk.ctl("read", "-> free lauum_trmm(k, n)")
    syrk.body(**bodies(lauum_syrk_cpu,
                       lauum_syrk_bf16 if bf16_updates else lauum_syrk_tpu))

    # A[m,n] += A[k,m]^T A[k,n]: the chain of A[m,n] after lauum_trmm(m, n)
    gemm = ptg.task_class("lauum_gemm", k="2 .. NT-1", m="1 .. k-1",
                          n="0 .. m-1")
    gemm.affinity("A(m, n)")
    gemm.priority(PRIORITY["lauum_gemm"])
    gemm.flow("A", INOUT,
              "<- (k == m+1) ? C lauum_trmm(m, n) : A lauum_gemm(k-1, m, n)",
              "-> (k == NT-1) ? A(m, n) : A lauum_gemm(k+1, m, n)")
    gemm.flow("B1", IN, "<- A(k, m)")
    gemm.flow("B2", IN, "<- A(k, n)")
    gemm.ctl("b1_read", "-> free lauum_trmm(k, m)")
    gemm.ctl("b2_read", "-> free lauum_trmm(k, n)")
    gemm.body(**bodies(lauum_gemm_cpu,
                       lauum_gemm_bf16 if bf16_updates else lauum_gemm_tpu))

    # A[k,n] = tril(A[k,k])^T A[k,n], once step k's updates have read
    # the original A[k,n]: the first link of A[k,n]'s chain
    trmm = ptg.task_class("lauum_trmm", k="1 .. NT-1", n="0 .. k-1")
    trmm.affinity("A(k, n)")
    trmm.priority(PRIORITY["lauum_trmm"])
    trmm.flow("T", IN, "<- A(k, k)")
    trmm.flow("C", INOUT,
              "<- A(k, n)",
              "-> (k == NT-1) ? A(k, n) : A lauum_gemm(k+1, k, n)")
    trmm.ctl("free",
             "<- read lauum_syrk(k, n)",
             "<- b2_read lauum_gemm(k, n+1 .. k-1, n)",
             "<- b1_read lauum_gemm(k, n, 0 .. n-1)")
    trmm.ctl("read", "-> free lauum_diag(k)")
    trmm.body(**bodies(lauum_trmm_cpu, lauum_trmm_tpu))

    # A[k,k] = tril(A[k,k])^T tril(A[k,k]), once step k's trmms have
    # read it: the first link of A[k,k]'s chain
    diag = ptg.task_class("lauum_diag", k="0 .. NT-1")
    diag.affinity("A(k, k)")
    diag.priority(PRIORITY["lauum_diag"])
    diag.flow("T", INOUT,
              "<- A(k, k)",
              "-> (k == NT-1) ? A(k, k) : A lauum_syrk(k+1, k)")
    diag.ctl("free", "<- read lauum_trmm(k, 0 .. k-1)")
    diag.body(**bodies(lauum_diag_cpu, lauum_diag_tpu))
    return ptg


def poinv(A, *, use_tpu: bool = True, use_cpu: bool = True,
          use_pallas: bool = False,
          bf16_updates: bool = False) -> CompoundTaskpool:
    """The inverse of the SPD ``TiledMatrix`` ``A`` in place (its lower
    tiles; diagonal tiles come out whole): ``dpotrf``, ``dtrtri`` and
    ``dlauum`` over ``A``'s tiles, composed.  Run it through a context
    (``ctx.add_taskpool(poinv(A))``) or the native executor
    (``NativeExecutor(poinv(A), native_device=True)``: the matrix goes
    onto the device once and comes home once).  ``use_pallas`` is
    member 1's (``cholesky_ptg``); ``bf16_updates`` gives every
    member's syrk / gemm updates bfloat16 operands (the benchmark's
    control path; member 1's asks for ``use_pallas``)."""
    NT = A.mt
    return compose(
        cholesky_ptg(use_tpu=use_tpu, use_cpu=use_cpu, use_pallas=use_pallas,
                     bf16_updates=bf16_updates).taskpool(NT=NT, A=A),
        trtri_ptg(use_tpu=use_tpu, use_cpu=use_cpu,
                  bf16_updates=bf16_updates).taskpool(NT=NT, A=A),
        lauum_ptg(use_tpu=use_tpu, use_cpu=use_cpu,
                  bf16_updates=bf16_updates).taskpool(NT=NT, A=A))
