"""One Gaussian log-likelihood evaluation of a Matérn model, in two
precisions, as ONE PTG: the covariance matrix is generated, factored in
place, solved against and reduced to two scalars without a tile of it
leaving the device it was born on.

Source: ExaGeoStat's exact maximum-likelihood loop
(https://github.com/ecrc/exageostat, ``exageostat_exact``, ``MLE_alg``;
Abdulah et al., TPDS 2018): an optimizer proposes ``theta = (sigma^2,
beta, nu)`` and each evaluation generates ``Sigma(theta)`` over the N
locations (``dcmg``), factors it (``dpotrf``), solves ``L y = z``
(``dtrsm``) and returns

    loglik = -1/2 (y . y  +  2 sum log L_ii  +  N log 2 pi),

in the mixed-precision form of Abdulah et al. (HiPC 2019; TPDS 2021,
over PaRSEC): tiles near the diagonal in the high precision, the others
in a lower one, by band.  Re-derived here, not copied.

**The task classes** (``NT`` tile rows of ``nb``)::

    dcmg(m, n)      m >= n   tile (m, n) of Sigma from X(m), X(n), theta
    potrf / trsm / syrk / gemm       ``ops/cholesky.py``'s, unchanged
    convert(k, n)   a float32 panel tile's bfloat16 twin, made once
    trsv(k)         y_k = L_kk^-1 (z_k - sum_j<k L_kj y_j)
    gemv(k, m)      the sum's term of column k for row m
    logdet(k), dot(k)   the two reductions, a chain each

One taskpool and not the source's four: column k of the solve starts
when column k of the factor is final, and no pool's end flushes the
matrix home between two stages (``NativeExecutor.close`` detaches the
device).  ``theta`` is a TILE (1 x 3 float32, collection ``TH``), not a
taskpool constant: a constant is part of the attach plan's key by value,
and every evaluation has another theta.

**The precision rule** (``band_f32``; :func:`band_dtype` is the map the
matrix is built with).  Tile (m, n) is float32 where ``m - n <
band_f32`` and bfloat16 elsewhere; ``potrf`` and every diagonal tile are
float32 (for ``band_f32 >= 1``).  An update computes in the precision of
the tile it WRITES (:func:`_update`): a float32 tile accumulates in
float32 — operands that are both bfloat16 multiply exactly in one MXU
pass, any float32 operand makes it ``highest`` — and a bfloat16 tile
multiplies bfloat16 operands in one pass, accumulates in float32 and
rounds once on the store.  An operand stored lower than the update wants
is used as it is.  An operand stored HIGHER is converted down once,
where it is produced, by a ``convert`` task whose output (a ``NEW``
tile, ``device/scratch.py``) all its readers share and which dies with
the last of them: a float32 panel tile L(n, k), ``n - k < band_f32``, is
read as ``B2`` by the ``gemm(k, m, n)`` of its column, of which those
with ``m - n >= band_f32`` write bfloat16.  The bodies REFUSE a float32
operand for a bfloat16 tile, so no reader converts on its own.  A
``trsm`` solves in float32 whatever its tile (the MXU has no triangular
solve below it) and rounds once on the store.

``band_f32 >= NT`` is the whole evaluation in float32; ``band_f32 = 0``
stores every tile, the diagonal too, in bfloat16: the lower-precision
path a check is held against, and nothing else.

nu = 1/2 only (``sigma^2 exp(-d / beta)``): a general nu needs a
modified Bessel function the chip has no body for.
"""

from __future__ import annotations

import math

import numpy as np

from ..core.lifecycle import AccessMode
from ..dsl.ptg import PTG
from . import tiles
from .cholesky import add_dpotrf_classes

IN = AccessMode.IN
OUT = AccessMode.OUT
INOUT = AccessMode.INOUT

try:
    import jax.numpy as jnp
    from jax.scipy.linalg import solve_triangular as _jsolve
except Exception:  # pragma: no cover
    jnp = None

F32 = np.dtype(np.float32)
BF16 = np.dtype(jnp.bfloat16) if jnp is not None else None


# -- the precision map -------------------------------------------------------

def band_dtype(band_f32: int):
    """``(m, n) -> dtype`` of the band rule, for ``TiledMatrix(
    tile_dtype=...)``."""
    band = int(band_f32)

    def dtype_of(m: int, n: int):
        return F32 if abs(m - n) < band else BF16

    return dtype_of


def mle_matrix(n: int, nb: int, band_f32: int, *, name: str = "A"):
    """The covariance matrix's collection: lower tiles, each in the
    precision of the band rule, born on the device (no host value before,
    during or after)."""
    from ..datadist.matrix import LOWER, TiledMatrix

    tiles.check_tiling(n, nb, what="N", op="mle")
    return TiledMatrix(n, n, nb, nb, name=name, dtype=np.float32,
                       uplo=LOWER, tile_dtype=band_dtype(band_f32),
                       device_born=True)


def converted_tiles(nt: int, band_f32: int) -> int:
    """Panel tiles that have a lower-precision reader: (k, n) with ``0 <
    n - k < band_f32`` and a row ``m >= n + band_f32`` below."""
    return sum(1 for k in range(nt) for n in range(k + 1, nt)
               if n - k < band_f32 and n + band_f32 <= nt - 1)


# -- tile bodies (device) ----------------------------------------------------

def dcmg_tpu(XM, XN, TH, C, **_):
    """Tile of the exponential covariance: ``sigma^2 exp(-d / beta)``,
    distances in float32, written in the tile's own precision (``C`` is
    the unwritten tile: only its dtype is read)."""
    dx = XM[:, 0:1] - XN[:, 0][None, :]
    dy = XM[:, 1:2] - XN[:, 1][None, :]
    d = jnp.sqrt(dx * dx + dy * dy)
    return (TH[0, 0] * jnp.exp(-d / TH[0, 1])).astype(C.dtype)


def potrf_tpu(T, **_):
    return jnp.linalg.cholesky(T.astype(jnp.float32)).astype(T.dtype)


def trsm_tpu(T, C, **_):
    x = _jsolve(T.astype(jnp.float32), C.astype(jnp.float32).T,
                lower=True, trans=0).T
    return x.astype(C.dtype)


def _update(A, B1, B2):
    """``A - B1 B2^T`` in the precision of ``A``."""
    if A.dtype == jnp.float32:
        if B1.dtype == B2.dtype == jnp.bfloat16:
            # bf16 x bf16 products are exact in float32: one MXU pass
            # gives what six would
            return A - jnp.dot(B1, B2.T, preferred_element_type=jnp.float32)
        return A - jnp.dot(B1.astype(jnp.float32), B2.astype(jnp.float32).T,
                           precision="highest")
    for b in (B1, B2):
        if b.dtype != A.dtype:
            raise TypeError(
                f"a {b.dtype} operand for a {A.dtype} tile: an operand "
                "stored higher than the update wants is converted once, "
                "where it is produced (convert), never by its reader")
    acc = jnp.dot(B1, B2.T, preferred_element_type=jnp.float32)
    return (A.astype(jnp.float32) - acc).astype(A.dtype)


def syrk_tpu(A, B, **_):
    return _update(A, B, B)


def gemm_tpu(A, B1, B2, **_):
    return _update(A, B1, B2)


def convert_tpu(H, LO, **_):
    return H.astype(LO.dtype)


#: the device module counts the tiles this body writes as conversions
#: (``convert_tiles``, ``convert_bytes``) and the reads of them as
#: ``convert_shared_hits``
convert_tpu._converts = True


def trsv_tpu(D, B, S, Y, **_):
    """``y_k``: ``S`` is minus the sum of the columns before (None in
    column 0)."""
    r = B if S is None else B + S
    return tiles.trsv_fwd_tpu(D.astype(jnp.float32), r, Y)


def gemv_tpu(L, X, R, **_):
    return tiles.gemm_sub_tpu(L.astype(jnp.float32), X, R)


def _two_sum(S, x):
    """``S = [[sum, compensation]]`` plus ``x``, the rounding error of the
    addition kept (Knuth's two-sum): the chain of NT additions loses
    nothing a float64 accumulator would keep."""
    s, c = S[0, 0], S[0, 1]
    t = s + x
    bp = t - s
    e = (s - (t - bp)) + (x - bp)
    return jnp.stack([t, c + e]).reshape(1, 2)


def logdet_tpu(T, S, **_):
    d = jnp.diagonal(T).astype(jnp.float32)
    return _two_sum(S, 2.0 * jnp.sum(jnp.log(d)))


def dot_tpu(V, S, **_):
    return _two_sum(S, jnp.sum(V * V))


# -- the PTG -----------------------------------------------------------------

def mle_ptg() -> PTG:
    """The evaluation's PTG, device bodies only (they are jnp: on a host
    without an accelerator they run on JAX's CPU backend).  Instantiate
    through :func:`mle_taskpool`."""
    ptg = PTG("smle")

    dcmg = ptg.task_class("dcmg", m="0 .. NT-1", n="0 .. m")
    dcmg.affinity("A(m, n)")
    # the matrix first, column by column: the source's order (a pool that
    # generates, then a pool that factors)
    dcmg.priority("(2 * NT - n) * 1000 + NT - m")
    dcmg.flow("XM", IN, "<- X(m, 0)")
    dcmg.flow("XN", IN, "<- X(n, 0)")
    dcmg.flow("TH", IN, "<- TH(0, 0)")
    dcmg.flow("C", OUT,
              "<- A(m, n)",
              "-> (m == 0) ? T potrf(0)",
              "-> (m == n and m > 0) ? A syrk(0, m)",
              "-> (m > n and n == 0) ? C trsm(0, m)",
              "-> (m > n and n > 0) ? A gemm(0, m, n)")
    dcmg.body(tpu=dcmg_tpu)

    add_dpotrf_classes(
        ptg,
        {"potrf": {"tpu": potrf_tpu}, "trsm": {"tpu": trsm_tpu},
         "syrk": {"tpu": syrk_tpu}, "gemm": {"tpu": gemm_tpu}},
        first="C dcmg({m}, {n})",
        potrf_out=("-> D trsv(k)", "-> T logdet(k)"),
        trsm_out=("-> L gemv(k, m)",
                  "-> (m - k < BAND and m + BAND <= NT-1) ? H convert(k, m)"),
        # the gemm tasks of column m that write a bfloat16 tile take a
        # float32 panel tile's twin, the others the tile itself
        trsm_b2_out=(
            "-> (m - k >= BAND) ? B2 gemm(k, m+1 .. NT-1, m)",
            "-> (m - k < BAND) ? B2 gemm(k, m+1 .. min(m+BAND-1, NT-1), m)"),
        gemm_b2=("<- (m - n >= BAND and n - k < BAND) "
                 "? LO convert(k, n) : C trsm(k, n)",))

    convert = ptg.task_class("convert", k="0 .. NT-2",
                             n="k+1 .. min(k+BAND-1, NT-1-BAND)")
    convert.affinity("A(n, k)")
    convert.priority("(NT - n) * 100 - 1")  # right behind its trsm
    convert.flow("H", IN, "<- C trsm(k, n)")
    convert.flow("LO", OUT,
                 "<- NEW [type=LOTILE]",
                 "-> B2 gemm(k, n+BAND .. NT-1, n)")
    convert.body(tpu=convert_tpu)

    trsv = ptg.task_class("trsv", k="0 .. NT-1")
    trsv.affinity("Y(k, 0)")
    trsv.priority("(NT - k) * 1000 - 2")
    trsv.flow("D", IN, "<- T potrf(k)")
    trsv.flow("B", IN, "<- Z(k, 0)")
    trsv.flow("S", IN,
              "<- (k > 0) ? R gemv(k-1, k)",
              "<- NONE")
    trsv.flow("Y", OUT,
              "<- Y(k, 0)",
              "-> X gemv(k, k+1 .. NT-1)",
              "-> V dot(k)",
              "-> Y(k, 0)")
    trsv.body(tpu=trsv_tpu)

    gemv = ptg.task_class("gemv", k="0 .. NT-2", m="k+1 .. NT-1")
    gemv.affinity("A(m, k)")
    gemv.priority("(NT - m) * 100 - 5")
    gemv.flow("L", IN, "<- C trsm(k, m)")
    gemv.flow("X", IN, "<- Y trsv(k)")
    gemv.flow("R", INOUT,
              "<- (k == 0) ? NEW : R gemv(k-1, m)",  # TILE_SHAPE, TILE_DTYPE
              "-> (k == m-1) ? S trsv(m) : R gemv(k+1, m)")
    gemv.body(tpu=gemv_tpu)

    logdet = ptg.task_class("logdet", k="0 .. NT-1")
    logdet.affinity("SC(0, 0)")
    logdet.priority("(NT - k) * 1000 - 3")
    logdet.flow("T", IN, "<- T potrf(k)")
    logdet.flow("S", INOUT,
                "<- (k == 0) ? SC(0, 0) : S logdet(k-1)",
                "-> (k < NT-1) ? S logdet(k+1)",
                "-> SC(0, 0)")
    logdet.body(tpu=logdet_tpu)

    dot = ptg.task_class("dot", k="0 .. NT-1")
    dot.affinity("SC(1, 0)")
    dot.priority("(NT - k) * 1000 - 4")
    dot.flow("V", IN, "<- Y trsv(k)")
    dot.flow("S", INOUT,
             "<- (k == 0) ? SC(1, 0) : S dot(k-1)",
             "-> (k < NT-1) ? S dot(k+1)",
             "-> SC(1, 0)")
    dot.body(tpu=dot_tpu)
    return ptg


def mle_ntasks(nt: int, band_f32: int) -> int:
    """Tasks of one evaluation on nt tile rows."""
    lower = nt * (nt + 1) // 2
    dpotrf = nt + nt * (nt - 1) + nt * (nt - 1) * (nt - 2) // 6
    solve = nt + nt * (nt - 1) // 2
    return lower + dpotrf + converted_tiles(nt, band_f32) + solve + 2 * nt


def mle_collections(n: int, nb: int, band_f32: int, x, z, theta):
    """The evaluation's collections over host values: locations ``x``
    (n x 2), observations ``z`` (n), ``theta = (sigma^2, beta)`` (nu is
    1/2): ``dict(A=, X=, Z=, TH=, Y=, SC=)``.  ``x``, ``z`` are cut into
    tiles that the runtime only reads: they are the caller's, not
    copies."""
    from ..datadist.matrix import TiledMatrix

    nt = tiles.check_tiling(n, nb, what="N", op="mle")
    x = np.ascontiguousarray(x, np.float32).reshape(n, 2)
    z = np.ascontiguousarray(z, np.float32).reshape(n, 1)

    def filled(M, host, rows):
        for i in range(M.mt):
            tile = host[i * rows:(i + 1) * rows]
            d = M.data_of(i, 0)
            (d.get_copy(0) or d.attach_copy(0, tile)).payload = tile
        return M

    th = np.array([[theta[0], theta[1], 0.5]], np.float32)
    f32 = dict(dtype=np.float32)
    return dict(
        A=mle_matrix(n, nb, band_f32),
        X=filled(TiledMatrix(n, 2, nb, 2, name="X", **f32), x, nb),
        Z=filled(TiledMatrix(n, 1, nb, 1, name="Z", **f32), z, nb),
        TH=filled(TiledMatrix(1, 3, 1, 3, name="TH", **f32), th, 1),
        Y=TiledMatrix(n, 1, nb, 1, name="Y", **f32),
        SC=TiledMatrix(2, 2, 1, 2, name="SC", **f32))


def mle_taskpool(A, X, Z, TH, Y, SC, *, band_f32: int):
    """The taskpool of one evaluation over :func:`mle_collections`'s
    collections.  ``A``'s precision map has to BE the band rule of
    ``band_f32``: the dependencies that hand a converted twin to a reader
    are written from the band, and a map that says otherwise would run
    them against tiles of another precision."""
    band = int(band_f32)
    if band < 0:
        raise ValueError(f"mle: band_f32 = {band_f32} is negative")
    if A.m != A.n or A.mb != A.nb or A.m % A.mb:
        raise ValueError(f"mle: the matrix is {A.m} x {A.n} in tiles of "
                         f"{A.mb} x {A.nb}: square, in square tiles that "
                         "divide it")
    want = band_dtype(band)
    for (m, n) in A.tiles():
        if A.dtype_of(m, n) != want(m, n):
            raise ValueError(
                f"mle: tile ({m}, {n}) of {A.name} is {A.dtype_of(m, n)}, "
                f"the band rule of band_f32 = {band} says {want(m, n)}")
    nb = A.mb
    return mle_ptg().taskpool(
        NT=A.mt, BAND=band, A=A, X=X, Z=Z, TH=TH, Y=Y, SC=SC,
        LOTILE=(BF16, (nb, nb)), TILE_SHAPE=(nb, 1), TILE_DTYPE=F32)


def loglik_parts(SC):
    """``(logdet, dot)`` in float64 from the two reduction tiles (sum and
    compensation each), once they are host values."""
    vals = []
    for i in (0, 1):
        t = np.asarray(SC.data_of(i, 0).newest_copy().payload, np.float64)
        vals.append(float(t[0, 0] + t[0, 1]))
    return vals[0], vals[1]


def loglik(logdet: float, dot: float, n: int) -> float:
    return -0.5 * (dot + logdet + n * math.log(2.0 * math.pi))
