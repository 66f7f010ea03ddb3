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

The same mathematics in the other DSL is :func:`cholesky_dtd`: DPLASMA's
``testing_dpotrf_dtd.c``, the loop nest above written as sequential task
insertion, the graph discovered by the runtime task by task.  Both forms
take their bodies from :func:`dpotrf_bodies` and their priorities from
:data:`PRIORITY`.
"""

from __future__ import annotations

import numpy as np

from ..core.lifecycle import AccessMode, DEV_CPU, DEV_TPU
from ..dsl.ptg import PTG
from . import tiles

IN = AccessMode.IN
INOUT = AccessMode.INOUT
AFFINITY = AccessMode.AFFINITY

#: a task's priority by its class, as the PTG's expression text over
#: ``NT`` and the task's parameters (``k``: the step; ``m``: the tile row):
#: the panel first, then the updates of the rows it unlocks soonest.
#: ``cholesky_dtd`` evaluates the same text at insertion.
PRIORITY = {
    "potrf": "(NT - k) * 1000",
    "trtri": "(NT - k) * 1000 - 1",  # right behind its potrf
    "trsm": "(NT - m) * 100",
    "syrk": "(NT - m) * 100 + 10",
    "gemm": "(NT - m) * 10",
}


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
    add_dpotrf_classes(ptg, dpotrf_bodies(
        use_tpu=use_tpu, use_cpu=use_cpu, use_pallas=use_pallas,
        use_trtri=use_trtri, bf16_updates=bf16_updates),
        use_trtri=use_trtri)
    return ptg


def dpotrf_bodies(*, use_tpu: bool = True, use_cpu: bool = True,
                  use_pallas: bool = False, use_trtri: bool = False,
                  bf16_updates: bool = False):
    """Class name -> ``{"cpu": body, "tpu": body}`` (the incarnations
    asked for) of the dpotrf task classes, as :func:`cholesky_ptg`'s
    options say: what a PTG class's ``body()`` takes as keywords and
    what :func:`cholesky_dtd` hands ``insert_task`` by device type."""
    def bodies(cpu, tpu):
        kw = {}
        if use_cpu:
            kw[DEV_CPU] = cpu
        if use_tpu or use_pallas:
            # a pallas chore is a device chore: requesting it implies the
            # device incarnation even when use_tpu wasn't set explicitly
            kw[DEV_TPU] = tpu
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
    return {
        "potrf": bodies(tiles.potrf_cpu, tiles.potrf_tpu),
        "trtri": bodies(tiles.trtri_cpu, tiles.trtri_tpu),
        "trsm": trsm_body,
        "syrk": bodies(tiles.syrk_cpu, syrk_dev),
        "gemm": bodies(tiles.gemm_update_cpu, gemm_dev),
    }


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
    potrf.priority(PRIORITY["potrf"])
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
        trtri.priority(PRIORITY["trtri"])
        trtri.flow("T", IN, "<- T potrf(k)")
        trtri.flow("I", INOUT,
                   "<- NEW",
                   "-> I trsm(k, k+1 .. NT-1)")
        trtri.body(**bodies["trtri"])

    trsm = ptg.task_class("trsm", k="0 .. NT-2", m="k+1 .. NT-1")
    trsm.affinity("A(m, k)")
    trsm.priority(PRIORITY["trsm"])
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
    syrk.priority(PRIORITY["syrk"])
    syrk.flow("A", INOUT,
              f"<- (k == 0) ? {tile('m', 'm')} : A syrk(k-1, m)",
              "-> (k == m-1) ? T potrf(m) : A syrk(k+1, m)")
    syrk.flow("B", IN,
              "<- C trsm(k, m)")
    syrk.body(**bodies["syrk"])

    gemm = ptg.task_class("gemm", k="0 .. NT-3", m="k+2 .. NT-1", n="k+1 .. m-1")
    gemm.affinity("A(m, n)")
    gemm.priority(PRIORITY["gemm"])
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


def cholesky_dtd(tp, A, *, use_tpu: bool = True, use_cpu: bool = False,
                 use_pallas: bool = False, use_trtri: bool = False,
                 bf16_updates: bool = False, tile=None) -> int:
    """Insert the tile Cholesky of ``A`` (lower, in place) into the DTD
    pool ``tp``, task by task: the loop nest of DPLASMA's
    ``testing_dpotrf_dtd.c``.  The runtime is told nothing of the graph;
    it finds every dependency from the access mode of each tile argument
    as the task arrives.  The user's calling sequence is the
    reference's::

        tp = DTDTaskpool(ctx)
        cholesky_dtd(tp, A)       # returns while tasks still run
        tp.wait()
        tp.flush_all(A)           # the factor comes home here, not before
        tp.close()

    Bodies and priorities are :func:`cholesky_ptg`'s (same options), so
    the two DSLs run one mathematics; the right-looking order has no
    write-after-read hazard, so nothing is renamed.  ``tile(m, n)`` names
    tile ``(m, n)`` for ``tp.insert_task`` (default: ``A.data_of``; a
    pool that tracks host arrays, ``NativeDTD``, is given the arrays).
    Returns the number of tasks inserted."""
    from ..device import scratch

    if use_trtri and tile is not None:
        raise ValueError("use_trtri makes scratch tiles of its own: it "
                         "cannot name them through a caller's tile()")
    NT = A.mt
    if tile is None:
        tile = A.data_of
    bodies = dpotrf_bodies(use_tpu=use_tpu, use_cpu=use_cpu,
                           use_pallas=use_pallas, use_trtri=use_trtri,
                           bf16_updates=bf16_updates)

    def body(cls):
        b = bodies[cls]
        # a pool that knows no device types takes the one CPU body
        return b if len(b) > 1 or DEV_CPU not in b else b[DEV_CPU]

    potrf, trtri, trsm, syrk, gemm = (
        body(c) for c in ("potrf", "trtri", "trsm", "syrk", "gemm"))
    prio = {c: compile(text, f"<priority of {c}>", "eval")
            for c, text in PRIORITY.items()}
    inserted = 0
    for k in range(NT):
        env = {"NT": NT, "k": k}
        tp.insert_task(potrf, (tile(k, k), INOUT | AFFINITY),
                       priority=eval(prio["potrf"], env), name="potrf")
        inserted += 1
        panel = tile(k, k)
        if use_trtri and k < NT - 1:
            # the inverse of the factored block: a scratch tile that
            # lives where it is made and dies with the column's last trsm
            panel = scratch.new(("trtri", k), A.tile_shape(k, k),
                                A.dtype_of(k, k))
            scratch.add_users(panel, NT - k)
            tp.insert_task(trtri, (tile(k, k), IN),
                           (panel, INOUT | AFFINITY),
                           priority=eval(prio["trtri"], env), name="trtri")
            inserted += 1
        for m in range(k + 1, NT):
            env["m"] = m
            tp.insert_task(trsm, (panel, IN),
                           (tile(m, k), INOUT | AFFINITY),
                           priority=eval(prio["trsm"], env), name="trsm")
        for m in range(k + 1, NT):
            env["m"] = m
            tp.insert_task(syrk, (tile(m, m), INOUT | AFFINITY),
                           (tile(m, k), IN),
                           priority=eval(prio["syrk"], env), name="syrk")
            p = eval(prio["gemm"], env)
            for n in range(k + 1, m):
                tp.insert_task(gemm, (tile(m, n), INOUT | AFFINITY),
                               (tile(m, k), IN), (tile(n, k), IN),
                               priority=p, name="gemm")
            inserted += m - k
        inserted += NT - k - 1
    return inserted
