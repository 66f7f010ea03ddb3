"""Tiled Householder QR factorization as a PTG — the second flagship.

The reference ecosystem's dense-QR lives in DPLASMA (like dpotrf, not in
the PaRSEC repo itself — SURVEY.md §6); this is its tile QR over a
reduction TREE (``dplasma_dgeqrf_param`` over a ``dplasma_qrtree_t``:
:mod:`.qr_tree`), re-derived TPU-first.  In panel k every row m >= k is
either a domain head (it gets a ``geqrt``) or is killed as a square by
its head (TS); the heads but row k are then killed as triangles by other
heads (TT), row k last:

  geqrt(k, m):    A[m,k]              -> Q, R_m              (m a head)
  unmqr(k, m, n): A[m,n]              <- Q^T A[m,n]          (n > k)
  tsqrt(k, m):    [R_p; A[m,k]]       -> Q, R_p'   (p = currpiv(k, m))
  tsmqr(k, m, n): [A[p,n]; A[m,n]]    <- Q^T [ . ; . ]       (n > k)
  ttqrt(k, m):    [R_p; R_m]          -> Q, R_p'             (m a head)
  ttmqr(k, m, n): [A[p,n]; A[m,n]]    <- Q^T [ . ; . ]       (n > k)

A pivot's kills follow one another (``nextpiv`` / ``prevpiv``), TS before
TT; row (m, n)'s last update of panel k feeds its first task of panel
k+1.  With the flat tree (one domain: row k kills k+1 .. MT-1 in order)
this is the classic PLASMA tile QR, to the task.  The matrix may be tall:
MT >= NT tile rows and columns.

Representation choice (TPU-first): instead of the LAPACK compact-WY
(V, T) storage the reference consumers use, the orthogonal factors are
materialised as small dense Q blocks passed along NEW flows — every
update becomes a plain MXU matmul, which is the fast shape on this
hardware; the cost is extra FLOPs in tsqrt / ttqrt (complete QR of a
2nb x nb stack) amortised across the row's updates.

A wave of kills is ONE kernel (PR 44).  A kill's work is a dependent
loop (512 Householder steps of a few hundred KB each), bound by the
latency of a step and not by the chip's arithmetic; a wave program that
unrolls 64 such bodies runs 64 such loops one after another, and so does
``jnp.linalg.qr`` under ``vmap`` (compiled for a v5e it is hand-written
kernels that factor one block of one matrix: my chip runs, PR 44).  So
the kills have a Householder QR of their own, over a STACK of matrices
with every matrix's step j taken together (:func:`_lockstep_qr`), and
``geqrt_tpu``, ``tsqrt_tpu`` and ``ttqrt_tpu`` (:func:`_kills`) name, as
``_batched``, the form the device module calls ONCE for a whole wave
(``TpuDevice._launch``).  A body is that form over a stack of one: a
kill alone runs the same kernel (0.24 ms where ``jnp.linalg.qr`` took
0.66).  The updates name no form: a ``tsmqr`` is one MXU-bound product
that fills the chip by itself, and stacking its tiles would only add
copies.  The TS / TT kills also say that their second output, the killed
tile, is exact zeros whatever goes in (``_zeros``): that version is the
tile's last, and the device module lands zeros at home without copying
them from the chip (4,060 of the 4,096 tiles a hierarchical QR of
512 x 8 tiles sends home).

The factorization leaves R in the upper triangle of A's first NT tile
rows (every other tile zeroed).  Orthogonality is implicit; the invariant
A^T A = R^T R verifies the result without tracking Q (tests).
"""

from __future__ import annotations

import math

import numpy as np

from ..core.lifecycle import AccessMode
from ..dsl.ptg import PTG
from .qr_tree import QRTree, flat_tree

IN = AccessMode.IN
INOUT = AccessMode.INOUT

try:
    import jax
    import jax.numpy as jnp
    from jax import lax
except Exception:  # pragma: no cover
    jax = jnp = lax = None


# -- tile bodies -------------------------------------------------------------

def geqrt_cpu(T, Q, **_):
    q, r = np.linalg.qr(T)
    T[:] = r
    Q[:] = q


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


def tsmqr_cpu(Q, C1, C2, **_):
    nb = C1.shape[0]
    s = Q.T @ np.vstack([C1, C2])
    C1[:] = s[:nb]
    C2[:] = s[nb:]


def tsmqr_tpu(Q, C1, C2, **_):
    nb = C1.shape[0]
    s = jnp.dot(Q.T, jnp.vstack([C1, C2]), precision="highest")
    return s[:nb], s[nb:]


# The TT kill is the TS kill on two triangles; ``ttmqr`` runs the ``tsmqr``
# bodies as they are.

def ttqrt_cpu(R, B, Q, **_):
    tsqrt_cpu(R, np.triu(B), Q)
    B[:] = 0.0


# -- the kills' device bodies: a wave of them as ONE kernel -------------------

def _lockstep_qr(wo):
    """Blocked Householder QR of every matrix of a stack, in lockstep.

    The column steps of a block of ``wo`` columns run inside ONE Pallas
    kernel, the block resident in VMEM, four matrices a grid step: their
    steps are independent chains that the chip's scheduler interleaves,
    and a step costs its vector work (the same steps as a
    ``lax.fori_loop`` of XLA operations are a dozen launches a step
    whatever the stack: 2.23 ms a ``tsqrt`` alone where this takes 0.24,
    19.6 against 13.1 at 64; my chip run, PR 44).  Only whole blocks
    touch the trailing columns and Q, as
    products at ``highest``.  Matrices are held TRANSPOSED (a column is a
    row: the active rows lie along the lanes).

    (The helpers are nested, the width closed over: a device program's
    content key walks a callable's nested code and closure cells, not the
    module's globals, so the whole form is in the key of every program
    built around it.)"""

    def hdot(spec, a, b):
        return jnp.einsum(spec, a, b, precision="highest")

    def block_kernel(p_ref, o_ref, tt_ref):
        """``o_ref``: the block as LAPACK leaves it (R on and before the
        pivots, the reflectors' tails behind them); ``tt_ref``: its
        compact-WY T, transposed."""
        from .pallas_kernels import pl  # (imported where a kill is built:
        # Pallas costs every importer of this module most of a second)

        _g, b, r = p_ref.shape
        lane = lax.broadcasted_iota(jnp.int32, (1, 1, r), 2)
        sub = lax.broadcasted_iota(jnp.int32, (1, b, 1), 1)
        tlane = lax.broadcasted_iota(jnp.int32, (1, 1, b), 2)
        o_ref[...] = p_ref[...]
        tt_ref[...] = jnp.zeros(tt_ref.shape, tt_ref.dtype)

        def step(jj, _):
            row = o_ref[:, pl.ds(jj, 1), :]
            alpha = jnp.sum(jnp.where(lane == jj, row, 0.0), -1,
                            keepdims=True)
            x = jnp.where(lane > jj, row, 0.0)
            xx = jnp.sum(x * x, -1, keepdims=True)
            norm = jnp.sqrt(alpha * alpha + xx)
            # LAPACK's larfg: beta = -sign(alpha) * norm; a tail of zeros
            # leaves the column as it is (tau = 0)
            flat = xx == 0.0
            beta = jnp.where(flat, alpha,
                             jnp.where(alpha >= 0.0, -norm, norm))
            tau = jnp.where(flat, 0.0,
                            (beta - alpha) / jnp.where(flat, 1.0, beta))
            v = jnp.where(lane == jj, 1.0,
                          x / jnp.where(flat, 1.0, alpha - beta))
            # one pass over the block: a finished column's product with v
            # is an entry of V^T V (T's recurrence), a later column's is
            # the update's
            blk = o_ref[...]
            z = jnp.sum(blk * v, -1, keepdims=True)
            blk = blk - jnp.where(sub > jj, tau * z, 0.0) * v
            done = jnp.where(lane > jj, v, jnp.where(lane == jj, beta, row))
            o_ref[...] = jnp.where(sub == jj, done, blk)
            tt = tt_ref[...]
            tcol = -tau * jnp.sum(tt * jnp.where(sub < jj, z, 0.0), 1,
                                  keepdims=True)
            tt_ref[...] = jnp.where(
                sub == jj, tcol + jnp.where(tlane == jj, tau, 0.0), tt)
            return 0

        lax.fori_loop(0, b, step, 0)

    def block_steps(pt):
        """One block (n, b, r), column ``j`` pivoting at active row ``j``:
        the factored block and its compact-WY ``T`` (n, b, b),
        ``H_0 .. H_{b-1} = I - V T V^T``."""
        from .pallas_kernels import _pallas, pl

        n, b, r = pt.shape
        g = math.gcd(n, 4)
        blk, tt = _pallas(
            None, (pt,), block_kernel,
            out_shape=(jax.ShapeDtypeStruct((n, b, r), pt.dtype),
                       jax.ShapeDtypeStruct((n, b, b), pt.dtype)),
            grid=(n // g,),
            in_specs=[pl.BlockSpec((g, b, r), lambda i: (i, 0, 0))],
            out_specs=(pl.BlockSpec((g, b, r), lambda i: (i, 0, 0)),
                       pl.BlockSpec((g, b, b), lambda i: (i, 0, 0))))
        return blk, jnp.swapaxes(tt, 1, 2)

    def reflectors(pt):
        """The unit-lower-trapezoidal V (transposed) of a factored block."""
        _n, b, r = pt.shape
        lane = lax.broadcasted_iota(jnp.int32, (b, r), 1)
        piv = lax.broadcasted_iota(jnp.int32, (b, r), 0)
        return jnp.where(lane > piv, pt, jnp.where(lane == piv, 1.0, 0.0))

    def householder_stack(at, tri):
        """``at``: (n, c, m), the matrices transposed, m >= c.  ``tri``:
        rows c .. m-1 are dense and the c x c block above them is upper
        triangular (a TS / TT kill: reflector j lives on row j and the
        dense rows alone); else the matrices are dense.  Returns R
        transposed (n, c, c) and the complete Q (n, m, m), LAPACK's
        signs."""
        n, c, m = at.shape
        q = jnp.broadcast_to(jnp.eye(m, dtype=at.dtype), (n, m, m))
        for jb in range(0, c, wo):
            b = min(wo, c - jb)
            # the active rows, in pieces: the block's own and the dense
            # ones (the big arrays are read and written piece by piece,
            # where they stand)
            rows = [(lo, hi) for lo, hi in
                    ((jb, jb + b), (c, m) if tri else (jb + b, m))
                    if hi > lo]
            pt, T = block_steps(jnp.concatenate(
                [at[:, jb:jb + b, lo:hi] for lo, hi in rows], -1))
            vt = reflectors(pt)
            cuts = np.cumsum([0] + [hi - lo for lo, hi in rows])
            pieces = []
            for (lo, hi), s0, s1 in zip(rows, cuts, cuts[1:]):
                at = at.at[:, jb:jb + b, lo:hi].set(pt[..., s0:s1])
                pieces.append((lo, hi, vt[..., s0:s1]))

            def reflect(x, pieces=pieces, T=T):
                """x <- x (I - V T V^T) on the active columns of x."""
                w = sum(hdot("nmr,nkr->nmk", x[..., lo:hi], v)
                        for lo, hi, v in pieces)
                w = hdot("nmk,nkj->nmj", w, T)
                for lo, hi, v in pieces:
                    x = x.at[..., lo:hi].add(-hdot("nmj,njr->nmr", w, v))
                return x

            if jb + b < c:  # the trailing columns: A <- (I - V T^T V^T) A
                at = at.at[:, jb + b:].set(reflect(at[:, jb + b:]))
            q = reflect(q)
        return at[:, :, :c], q

    return householder_stack


def _kills(stack_qr):
    """The three kill bodies over ``stack_qr`` (:func:`_lockstep_qr`).
    Each names as ``_batched`` the form a wave program calls ONCE for all
    its tasks: the body's tile keywords stacked task-major ((n, nb, nb)
    each; the ``NEW`` Q block is nobody's argument), the body's outputs
    stacked alike; and each body IS that form over a stack of one, so a
    kill runs one kernel whether it goes out alone or in a wave.
    Householder QR of the stack, complete Q, R upper triangular, the
    killed tile exact zeros, every product at ``highest``."""
    def geqrt_wave(T, **_):
        rt, q = stack_qr(jnp.swapaxes(T, 1, 2), False)
        return jnp.triu(jnp.swapaxes(rt, 1, 2)), q

    def tsqrt_wave(R, B, **_):
        at = jnp.concatenate([jnp.swapaxes(jnp.triu(R), 1, 2),
                              jnp.swapaxes(B, 1, 2)], -1)
        rt, q = stack_qr(at, True)
        return jnp.triu(jnp.swapaxes(rt, 1, 2)), jnp.zeros_like(B), q

    # The TT kill is the TS kill on two triangles (the dense-Q
    # representation takes no advantage of the second triangle's zeros)
    def ttqrt_wave(R, B, **_):
        return tsqrt_wave(R, jnp.triu(B))

    def geqrt_tpu(T, Q, **_):
        return tuple(o[0] for o in geqrt_wave(T[None]))

    def tsqrt_tpu(R, B, Q, **_):
        return tuple(o[0] for o in tsqrt_wave(R[None], B[None]))

    def ttqrt_tpu(R, B, Q, **_):
        return tuple(o[0] for o in ttqrt_wave(R[None], B[None]))

    geqrt_tpu._batched = geqrt_wave
    tsqrt_tpu._batched = tsqrt_wave
    ttqrt_tpu._batched = ttqrt_wave
    # the killed tile is exact zeros whatever went in: its home tile
    # needs no copy from the chip (``TpuDevice._land_zeros``)
    tsqrt_tpu._zeros = ttqrt_tpu._zeros = (1,)
    return geqrt_tpu, tsqrt_tpu, ttqrt_tpu


geqrt_tpu, tsqrt_tpu, ttqrt_tpu = _kills(_lockstep_qr(128))


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

def _kill_of(arrow, cond, flow, stem, k, row, tail=""):
    """The task that kills ``row`` in panel ``k`` (``ts<stem>`` or
    ``tt<stem>`` by the row's type): one guarded dependency for each."""
    ref = f"({k}, TREE.getikill({k}, {row}){tail})"
    return (f"{arrow} ({cond} and TREE.gettype({k}, {row}) == 0) "
            f"? {flow} ts{stem}{ref}",
            f"{arrow} ({cond} and TREE.gettype({k}, {row}) != 0) "
            f"? {flow} tt{stem}{ref}")


def _from_panel_before(n):
    """Row m's tile of column ``n`` as panel k-1 left it: its kill's
    update there was its last."""
    return (f"<- (k == 0) ? A(m, {n})",
            *_kill_of("<-", "k > 0", "C2", "mqr", "k-1", "m", f", {n}"))


def _pivot_from(flow, first, stem, prev, row, tail=""):
    """A killer's tile as its kill before this one left it; before its
    first, as its ``geqrt`` / ``unmqr`` (``first``) did."""
    return (f"<- ({prev} == MT) ? {first}(k, TREE.geti(k, {row}){tail})",
            *_kill_of("<-", f"{prev} != MT", flow, stem, "k", prev, tail))


def _pivot_to(flow, victim, stem, nxt, row, home, tail=""):
    """... and where it goes: to the killer's next kill; after its last,
    home if it is the panel's root, else into its own kill."""
    return (*_kill_of("->", f"{nxt} != MT", flow, stem, "k", nxt, tail),
            f"-> ({nxt} == MT and {row} == k) ? {home}",
            f"-> ({nxt} == MT and {row} != k) "
            f"? {victim} tt{stem}(k, TREE.getikill(k, {row}){tail})")


#: row m's tile of column n goes on to panel k+1, where the row is a head
#: or a TS victim (a TT victim is a head first)
_TO_NEXT_PANEL = (
    "-> (n == k+1 and TREE.gettype(k+1, m) != 0) "
    "? T geqrt(k+1, TREE.geti(k+1, m))",
    "-> (n > k+1 and TREE.gettype(k+1, m) != 0) "
    "? C unmqr(k+1, TREE.geti(k+1, m), n)",
    "-> (n == k+1 and TREE.gettype(k+1, m) == 0) "
    "? B tsqrt(k+1, TREE.getikill(k+1, m))",
    "-> (n > k+1 and TREE.gettype(k+1, m) == 0) "
    "? C2 tsmqr(k+1, TREE.getikill(k+1, m), n)",
    "-> A(m, n)")


def qr_ptg(tree: QRTree = None, *, use_tpu: bool = True,
           use_cpu: bool = True, use_pallas: bool = False,
           bf16_updates: bool = False) -> PTG:
    """Build the tile-QR PTG over ``tree`` (:class:`.qr_tree.QRTree`;
    None: the flat tree of the taskpool's grid, the classic tile QR).
    Instantiate with ``.taskpool(NT=A.nt, A=A, TILE_SHAPE=(nb, nb),
    TILE_DTYPE=..., QSHAPE2=(dtype, (2*nb, 2*nb)))`` — ``MT`` (tile rows,
    and the tree's "none") and ``TREE`` default to ``A.mt`` and the
    tree.  The NEW-flow Q blocks are allocated from ``TILE_SHAPE`` except
    tsqrt's and ttqrt's, whose ``[type=QSHAPE2]`` dep property resolves
    the (2nb, 2nb) stacked-Q shape through the constants (device chores
    are functional and ignore the scratch; the shapes matter for the
    in-place CPU path).  :func:`run_qr` fills these in.

    A kill task is ``(k, i)``: the ``i``-th TS (or TT) kill of panel k in
    row order, ``m = TREE.getmkill(k, tt, i)`` its row; a ``geqrt`` is
    the ``i``-th head's, ``m = TREE.getm(k, i)``, as in DPLASMA's JDF.

    ``bf16_updates`` runs the unmqr/tsmqr/ttmqr updates with bf16
    operands and f32 accumulation (one MXU pass for the six of
    ``highest``): the lower-precision path the benchmark's check is held
    against.

    MT >= NT tile rows and columns of uniform square tiles."""
    ptg = PTG("geqrf")
    ptg.default("MT", lambda c: c["A"].mt)
    ptg.default("TREE", lambda c: tree if tree is not None
                else flat_tree(c["MT"], c["NT"]))
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

    heads = "0 .. TREE.getnbgeqrf(k)-1"

    geqrt = ptg.task_class("geqrt", k="0 .. NT-1", i=heads)
    geqrt.define("m", "TREE.getm(k, i)")
    geqrt.define("nextm", "TREE.nextpiv(k, m, MT)")
    geqrt.affinity("A(m, k)")
    geqrt.priority("(NT - k) * 1000")
    geqrt.flow("T", INOUT,
               *_from_panel_before("k"),
               *_pivot_to("R", "B", "qrt", "nextm", "m", "A(k, k)"))
    geqrt.flow("Q", INOUT,
               "<- NEW",
               "-> Q unmqr(k, i, k+1 .. NT-1)")
    geqrt.body(**bodies(geqrt_cpu, geqrt_tpu))

    def kill_class(name, tt, rows):
        c = ptg.task_class(name, k=rows, i=f"0 .. TREE.getnbkill(k, {tt})-1")
        c.define("m", f"TREE.getmkill(k, {tt}, i)")
        c.define("p", "TREE.currpiv(k, m)")
        c.define("prevp", "TREE.prevpiv(k, p, m)")
        c.define("nextp", "TREE.nextpiv(k, p, m)")
        if tt:
            c.define("prevm", "TREE.prevpiv(k, m, m)")
        return c

    def qrt_class(tt, cpu, tpu):
        """``tsqrt`` (tt = 0: the victim is a square, as the panel before
        left it) or ``ttqrt`` (1: a head's triangle, as its own kills
        left it)."""
        ts = "tt" if tt else "ts"
        c = kill_class(f"{ts}qrt", tt, "0 .. NT-1")
        c.affinity("A(m, k)")
        c.priority("(NT - k - TREE.level(k, m)) * 100 + 500")
        c.flow("R", INOUT,
               *_pivot_from("R", "T geqrt", "qrt", "prevp", "p"),
               *_pivot_to("R", "B", "qrt", "nextp", "p", "A(k, k)"))
        c.flow("B", INOUT,
               *(_pivot_from("R", "T geqrt", "qrt", "prevm", "m") if tt
                 else _from_panel_before("k")),
               "-> A(m, k)")
        c.flow("Q", INOUT,
               "<- NEW [type=QSHAPE2]",  # (2nb, 2nb): taskpool constant
               f"-> Q {ts}mqr(k, i, k+1 .. NT-1)")
        c.body(**bodies(cpu, tpu))

    def mqr_class(tt):
        """``tsmqr`` / ``ttmqr``: the kill's update of a trailing column."""
        ts = "tt" if tt else "ts"
        c = kill_class(f"{ts}mqr", tt, "0 .. NT-2").param("n", "k+1 .. NT-1")
        c.affinity("A(m, n)")
        c.priority("(NT - k - TREE.level(k, m)) * 10")
        c.flow("Q", IN, f"<- Q {ts}qrt(k, i)")
        c.flow("C1", INOUT,
               *_pivot_from("C1", "C unmqr", "mqr", "prevp", "p", ", n"),
               *_pivot_to("C1", "C2", "mqr", "nextp", "p", "A(k, n)",
                          ", n"))
        c.flow("C2", INOUT,
               *(_pivot_from("C1", "C unmqr", "mqr", "prevm", "m", ", n")
                 if tt else _from_panel_before("n")),
               *_TO_NEXT_PANEL)
        c.body(**bodies(tsmqr_cpu, tsmqr_dev))

    # (declared in the order of the classic tile QR's four classes: the
    # flat tree's attach plan is that PTG's, to the byte)
    qrt_class(0, tsqrt_cpu, tsqrt_tpu)

    unmqr = ptg.task_class("unmqr", k="0 .. NT-2", i=heads, n="k+1 .. NT-1")
    unmqr.define("m", "TREE.getm(k, i)")
    unmqr.define("nextm", "TREE.nextpiv(k, m, MT)")
    unmqr.affinity("A(m, n)")
    unmqr.priority("(NT - n) * 100 + 400")
    unmqr.flow("Q", IN, "<- Q geqrt(k, i)")
    unmqr.flow("C", INOUT,
               *_from_panel_before("n"),
               *_pivot_to("C1", "C2", "mqr", "nextm", "m", "A(k, n)",
                          ", n"))
    unmqr.body(**bodies(unmqr_cpu, unmqr_dev))

    mqr_class(0)
    qrt_class(1, ttqrt_cpu, ttqrt_tpu)
    mqr_class(1)

    return ptg


def run_qr(context, A, *, tree: QRTree = None, use_tpu: bool = True,
           use_cpu: bool = True) -> None:
    """Factorize TiledMatrix ``A`` (M x N, M >= N) in place: A := R in
    the upper triangle of its first N rows, zeros everywhere else.
    ``tree``: the reduction tree of its ``A.mt x A.nt`` grid (None: the
    flat tree)."""
    if A.m < A.n or A.mb != A.nb or A.m % A.mb or A.n % A.nb:
        raise ValueError(
            f"tiled QR needs M >= N and uniform square tiles (M and N "
            f"divisible by nb); got {A.m}x{A.n}, tiles {A.mb}x{A.nb}")
    if tree is not None and (tree.mt, tree.nt) != (A.mt, A.nt):
        raise ValueError(f"{tree!r} is not a tree of a {A.mt} x {A.nt} "
                         f"grid of tiles")
    nb = A.mb
    tp = qr_ptg(tree, use_tpu=use_tpu, use_cpu=use_cpu).taskpool(
        NT=A.nt, A=A, TILE_SHAPE=(nb, nb), TILE_DTYPE=A.default_dtype,
        QSHAPE2=(A.default_dtype, (2 * nb, 2 * nb)))
    context.add_taskpool(tp)
    ok = tp.wait(timeout=None)
    if not ok:
        raise RuntimeError("qr taskpool did not quiesce")
