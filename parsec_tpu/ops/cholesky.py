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
    operands to the MXU in bfloat16 with f32 accumulation: one MXU pass
    where ``highest`` makes six, and the operand cast rounds to 8 bits.
    It is the lower-precision control path of the benchmark's tile
    configurations (their ``control.options``), which their limits have
    to fail; nothing else uses it."""
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

    syrk_dev = tiles.syrk_tpu
    gemm_dev = tiles.gemm_update_tpu
    if use_pallas:
        syrk_dev = tiles.syrk_pallas_bf16 if bf16_updates else tiles.syrk_pallas
        gemm_dev = (tiles.gemm_update_pallas_bf16 if bf16_updates
                    else tiles.gemm_update_pallas)
    elif bf16_updates:
        raise ValueError("bf16_updates requires use_pallas")
    if use_trtri:
        trsm_body = bodies(tiles.trsm_inv_cpu,
                           tiles.trsm_inv_pallas if use_pallas
                           else tiles.trsm_inv_tpu)
    else:
        trsm_body = bodies(tiles.trsm_cpu, tiles.trsm_tpu)
    add_dpotrf_classes(ptg, {
        "potrf": bodies(tiles.potrf_cpu, tiles.potrf_tpu),
        "trtri": bodies(tiles.trtri_cpu, tiles.trtri_tpu),
        "trsm": trsm_body,
        "syrk": bodies(tiles.syrk_cpu, syrk_dev),
        "gemm": bodies(tiles.gemm_update_cpu, gemm_dev),
    }, use_trtri=use_trtri)
    return ptg


def add_dpotrf_classes(ptg: PTG, bodies, *, first="A({m}, {n})",
                       potrf_out=(), trsm_out=(),
                       trsm_b2_out=("-> B2 gemm(k, m+1 .. NT-1, m)",),
                       gemm_b2=("<- C trsm(k, n)",),
                       use_trtri: bool = False) -> None:
    """The four dpotrf task classes (five with ``use_trtri``) on ``ptg``,
    with their priorities and dependencies: :func:`cholesky_ptg`'s, and
    whoever composes the factorization into a larger pool (``ops/mle.py``:
    the matrix comes from a generator class and the factor feeds a
    solve).  ``bodies``: class name -> the keywords of its ``body()``.

    What a composer may say, all in dependency text: ``first``, the
    source of tile ``{m}, {n}`` before its first update (default: the
    collection's tile); ``potrf_out`` / ``trsm_out``, further
    readers of a factored diagonal / panel tile; ``trsm_b2_out`` and
    ``gemm_b2``, the two ends of the edge that hands a panel tile to the
    ``gemm`` tasks of its column as their ``B2`` (a composer that gives
    some of them a converted twin instead narrows the one and guards the
    other)."""
    def tile(m, n):
        return first.format(m=m, n=n)

    potrf = ptg.task_class("potrf", k="0 .. NT-1")
    potrf.affinity("A(k, k)")
    potrf.priority("(NT - k) * 1000")
    potrf.flow("T", INOUT,
               f"<- (k == 0) ? {tile('k', 'k')} : A syrk(k-1, k)",
               # trtri mode: the factored block feeds the inverter, which
               # fans the inverse out to the column's trsms
               "-> T trtri(k)" if use_trtri else "-> T trsm(k, k+1 .. NT-1)",
               *potrf_out,
               "-> A(k, k)")
    potrf.body(**bodies["potrf"])

    if use_trtri:
        trtri = ptg.task_class("trtri", k="0 .. NT-2")
        trtri.affinity("A(k, k)")
        trtri.priority("(NT - k) * 1000 - 1")  # right behind its potrf
        trtri.flow("T", IN, "<- T potrf(k)")
        trtri.flow("I", INOUT,
                   "<- NEW",
                   "-> I trsm(k, k+1 .. NT-1)")
        trtri.body(**bodies["trtri"])

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
              f"<- (k == 0) ? {tile('m', 'k')} : A gemm(k-1, m, k)",
              "-> B syrk(k, m)",
              "-> B1 gemm(k, m, k+1 .. m-1)",
              *trsm_b2_out,
              *trsm_out,
              "-> A(m, k)")
    trsm.body(**bodies["trsm"])

    syrk = ptg.task_class("syrk", k="0 .. NT-2", m="k+1 .. NT-1")
    syrk.affinity("A(m, m)")
    syrk.priority("(NT - m) * 100 + 10")
    syrk.flow("A", INOUT,
              f"<- (k == 0) ? {tile('m', 'm')} : A syrk(k-1, m)",
              "-> (k == m-1) ? T potrf(m) : A syrk(k+1, m)")
    syrk.flow("B", IN,
              "<- C trsm(k, m)")
    syrk.body(**bodies["syrk"])

    gemm = ptg.task_class("gemm", k="0 .. NT-3", m="k+2 .. NT-1", n="k+1 .. m-1")
    gemm.affinity("A(m, n)")
    gemm.priority("(NT - m) * 10")
    gemm.flow("A", INOUT,
              f"<- (k == 0) ? {tile('m', 'n')} : A gemm(k-1, m, n)",
              "-> (k == n-1) ? C trsm(n, m) : A gemm(k+1, m, n)")
    gemm.flow("B1", IN, "<- C trsm(k, m)")
    gemm.flow("B2", IN, *gemm_b2)
    gemm.body(**bodies["gemm"])


def run_cholesky(context, A, *, use_tpu: bool = True, use_cpu: bool = True) -> None:
    """Factorize TiledMatrix ``A`` (SPD) in place: A := L (lower)."""
    tp = cholesky_ptg(use_tpu=use_tpu, use_cpu=use_cpu).taskpool(NT=A.mt, A=A)
    context.add_taskpool(tp)
    ok = tp.wait(timeout=None)
    if not ok:
        raise RuntimeError("cholesky taskpool did not quiesce")
