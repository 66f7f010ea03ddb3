"""Tiled Cholesky factorization (dpotrf) as a PTG — the flagship taskpool.

The reference runtime's headline dense-linear-algebra consumer is DPLASMA's
dpotrf over a 2D block-cyclic matrix (north star in BASELINE.json). The
reference repo itself contains no Cholesky (SURVEY.md §6); this is the
classic right-looking tiled algorithm expressed in the PTG DSL:

  for k:  potrf(k):      A[k,k]   = chol(A[k,k])
          trsm(k, m):    A[m,k]   = A[m,k] @ A[k,k]^{-T}          (m > k)
          syrk(k, m):    A[m,m]  -= A[m,k] @ A[m,k]^T             (m > k)
          gemm(k, m, n): A[m,n]  -= A[m,k] @ A[n,k]^T         (m > n > k)

Dataflow: each tile's value threads through the update chain as a flow, so
lookahead across iterations emerges from dependencies alone — the classic
PTG win over fork-join loops.
"""

from __future__ import annotations

import numpy as np

from ..core.lifecycle import AccessMode
from ..dsl.ptg import PTG
from . import tiles

IN = AccessMode.IN
INOUT = AccessMode.INOUT


def cholesky_ptg(*, use_tpu: bool = True, use_cpu: bool = True,
                 use_pallas: bool = False, use_trtri: bool = False,
                 bf16_updates: bool = False) -> PTG:
    """Build the dpotrf PTG (instantiate with ``.taskpool(NT=..., A=...)``
    where ``A`` is a TiledMatrix holding the SPD matrix; the factorization
    happens in place, lower-triangular).

    ``use_pallas`` swaps the syrk/gemm update TPU chores for the fused
    Pallas MXU kernels (:mod:`parsec_tpu.ops.pallas_kernels`) — the
    TPU-native analogue of the reference's hand-written CUDA BODYs
    (``tests/runtime/cuda/nvlink.jdf:136-155``).

    ``use_trtri`` adds a per-column ``trtri(k)`` task inverting the
    factored diagonal block, turning every trsm into one MXU matmul
    ``C @ inv(T)^T`` (standalone, 4x the XLA triangular solve at
    nb=512) — the classic GPU-dpotrf critical-path trade. Pays off when
    per-task dispatch latency matters (dynamic path) or solves sit on
    the critical path; in the whole-DAG captured program XLA already
    overlaps the solves, so there it measured neutral (round-2 chip run).
    CPU chores then need the ``TILE_SHAPE``/``TILE_DTYPE`` constants
    for the NEW-flow scratch (device chores are functional and ignore
    it).

    ``bf16_updates`` (requires ``use_pallas``) feeds the syrk/gemm panel
    operands to the MXU in bfloat16 with f32 accumulation — the standard
    mixed-precision recipe. Only the operand cast rounds (~4e-3 per
    element; bf16 x bf16 products are exact in f32): measured end-to-end
    last-tile error at N=8192 is ~2e-5, passing the bench's 1e-3 gate;
    small ill-conditioned problems can see worse (tests allow 2e-2).
    Opt-in speed mode, not the default."""
    ptg = PTG("dpotrf")

    def bodies(cpu, tpu):
        kw = {}
        if use_cpu:
            kw["cpu"] = cpu
        if use_tpu or use_pallas:
            # a pallas chore is a device chore: requesting it implies the
            # device incarnation even when use_tpu wasn't set explicitly
            kw["tpu"] = tpu
        return kw

    potrf = ptg.task_class("potrf", k="0 .. NT-1")
    potrf.affinity("A(k, k)")
    potrf.priority("(NT - k) * 1000")
    potrf.flow("T", INOUT,
               "<- (k == 0) ? A(k, k) : A syrk(k-1, k)",
               # trtri mode: the factored block feeds the inverter, which
               # fans the inverse out to the column's trsms
               "-> T trtri(k)" if use_trtri else "-> T trsm(k, k+1 .. NT-1)",
               "-> A(k, k)")
    potrf.body(**bodies(tiles.potrf_cpu, tiles.potrf_tpu))

    if use_trtri:
        trtri = ptg.task_class("trtri", k="0 .. NT-2")
        trtri.affinity("A(k, k)")
        trtri.priority("(NT - k) * 1000 - 1")  # right behind its potrf
        trtri.flow("T", IN, "<- T potrf(k)")
        trtri.flow("I", INOUT,
                   "<- NEW",
                   "-> I trsm(k, k+1 .. NT-1)")
        trtri.body(**bodies(tiles.trtri_cpu, tiles.trtri_tpu))

    trsm = ptg.task_class("trsm", k="0 .. NT-2", m="k+1 .. NT-1")
    trsm.affinity("A(m, k)")
    trsm.priority("(NT - m) * 100")
    if use_trtri:
        trsm.flow("I", IN,
                  "<- I trtri(k)")
    else:
        trsm.flow("T", IN,
                  "<- T potrf(k)")
    trsm.flow("C", INOUT,
              "<- (k == 0) ? A(m, k) : A gemm(k-1, m, k)",
              "-> B syrk(k, m)",
              "-> B1 gemm(k, m, k+1 .. m-1)",
              "-> B2 gemm(k, m+1 .. NT-1, m)",
              "-> A(m, k)")
    if use_trtri:
        trsm.body(**bodies(tiles.trsm_inv_cpu,
                           tiles.trsm_inv_pallas if use_pallas
                           else tiles.trsm_inv_tpu))
    else:
        trsm.body(**bodies(tiles.trsm_cpu, tiles.trsm_tpu))

    syrk = ptg.task_class("syrk", k="0 .. NT-2", m="k+1 .. NT-1")
    syrk.affinity("A(m, m)")
    syrk.priority("(NT - m) * 100 + 10")
    syrk.flow("A", INOUT,
              "<- (k == 0) ? A(m, m) : A syrk(k-1, m)",
              "-> (k == m-1) ? T potrf(m) : A syrk(k+1, m)")
    syrk.flow("B", IN,
              "<- C trsm(k, m)")
    syrk_dev = tiles.syrk_tpu
    gemm_dev = tiles.gemm_update_tpu
    if use_pallas:
        syrk_dev = tiles.syrk_pallas_bf16 if bf16_updates else tiles.syrk_pallas
        gemm_dev = (tiles.gemm_update_pallas_bf16 if bf16_updates
                    else tiles.gemm_update_pallas)
    elif bf16_updates:
        raise ValueError("bf16_updates requires use_pallas")
    syrk.body(**bodies(tiles.syrk_cpu, syrk_dev))

    gemm = ptg.task_class("gemm", k="0 .. NT-3", m="k+2 .. NT-1", n="k+1 .. m-1")
    gemm.affinity("A(m, n)")
    gemm.priority("(NT - m) * 10")
    gemm.flow("A", INOUT,
              "<- (k == 0) ? A(m, n) : A gemm(k-1, m, n)",
              "-> (k == n-1) ? C trsm(n, m) : A gemm(k+1, m, n)")
    gemm.flow("B1", IN, "<- C trsm(k, m)")
    gemm.flow("B2", IN, "<- C trsm(k, n)")
    gemm.body(**bodies(tiles.gemm_update_cpu, gemm_dev))

    return ptg


def run_cholesky(context, A, *, use_tpu: bool = True, use_cpu: bool = True) -> None:
    """Factorize TiledMatrix ``A`` (SPD) in place: A := L (lower)."""
    tp = cholesky_ptg(use_tpu=use_tpu, use_cpu=use_cpu).taskpool(NT=A.mt, A=A)
    context.add_taskpool(tp)
    ok = tp.wait(timeout=None)
    if not ok:
        raise RuntimeError("cholesky taskpool did not quiesce")
