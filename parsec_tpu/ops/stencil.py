"""Iterative 2D 5-point Jacobi stencil over a tile grid, as a PTG.

Source: PaRSEC's stencil application, ``tests/apps/stencil/``
(``testing_stencil_1D.c``, ``stencil_1D.jdf``) in
https://github.com/ICLDisco/parsec, and Pei, Cao, Bosilca et al.,
"Communication Avoiding 2D Stencil Implementations over PaRSEC
Task-Based Runtime" (IPDPSW 2020): one task a tile a sweep,
``stencil(t, i, j)`` for T sweeps over an MT x NT tile grid, the
neighbours' edges as dataflow, so that the runtime overlaps the halo
traffic with interior compute.  The operator is this port's:
``new = 0.25 * (up + down + left + right)``, zero outside the grid.

The generations are the dataflow itself.  Generation 0 is read from the
tiled matrix ``A``; each task writes a tile of its own (``<- NEW``: a
scratch tile, ``device/scratch.py``), which is read by the next sweep's
task of the same position and of its four neighbours and is dead when
the last of them has retired; the tasks of the LAST sweep write the
tiles of ``B`` instead, which is all that ever goes home.  Nothing is
written twice, so there is no write-after-read to order by hand.

``B`` may be ``A`` itself (the sweeps in place, :func:`stencil_taskpool`'s
default) when T >= 2: the last sweep's task of a position depends,
through sweep 1, on every reader of that position's generation 0.  With
T = 1 on more than one tile it may not: ``stencil(0, i, j)`` would write
the tile its neighbours are reading.
"""

from __future__ import annotations

import numpy as np

from ..core.lifecycle import AccessMode
from ..dsl.ptg import PTG

IN = AccessMode.IN
OUT = AccessMode.OUT

try:
    import jax.numpy as jnp
    from jax import lax
except Exception:  # pragma: no cover
    jnp = lax = None

#: the largest tile ``stencil_pallas`` takes: its kernel keeps the tile,
#: four shifted copies of it and the result in VMEM at once (1 MiB is
#: the 512 x 512 f32 tile ``chip_smoke.py`` compiles on a v5e)
PALLAS_TILE_BYTES = 1 << 20


def stencil_grid(grid: np.ndarray, mt: int, nt: int, *, p: int = 1,
                 q: int = 1, myrank: int = 0, name: str = "A"):
    """``grid`` cut into ``mt x nt`` tiles: a ``TiledMatrix`` (block-cyclic
    over ``p x q`` ranks where there are several).  EVERY tile is filled
    on every rank: generation 0 is read where its reader runs
    (``<- A(i-1, j)`` is a memory reference, not a message)."""
    from ..datadist.matrix import TiledMatrix, TwoDimBlockCyclic
    from .tiles import check_tiling

    h, w = grid.shape
    check_tiling(h, mt, what="grid rows", op="stencil")
    check_tiling(w, nt, what="grid cols", op="stencil")
    th, tw = h // mt, w // nt
    kw = dict(name=name, dtype=grid.dtype, myrank=myrank)
    A = TiledMatrix(h, w, th, tw, **kw) if p * q == 1 \
        else TwoDimBlockCyclic(h, w, th, tw, p=p, q=q, **kw)
    for (i, j) in A.tiles():
        # a copy: the runtime may write into a tile it is given
        tile = grid[i * th:(i + 1) * th, j * tw:(j + 1) * tw].copy()
        d = A.data_of(i, j)
        copy = d.get_copy(0) or d.attach_copy(0, tile)
        copy.payload = tile
    return A


def _apply_5pt(OLD, UP, DOWN, LEFT, RIGHT):
    """The sweep of one tile on the host: the zero-padded formula."""
    h, w = OLD.shape
    pad = np.zeros((h + 2, w + 2), OLD.dtype)
    pad[1:-1, 1:-1] = OLD
    if UP is not None:
        pad[0, 1:-1] = UP[-1, :]
    if DOWN is not None:
        pad[-1, 1:-1] = DOWN[0, :]
    if LEFT is not None:
        pad[1:-1, 0] = LEFT[:, -1]
    if RIGHT is not None:
        pad[1:-1, -1] = RIGHT[:, 0]
    return 0.25 * (pad[:-2, 1:-1] + pad[2:, 1:-1] + pad[1:-1, :-2] + pad[1:-1, 2:])


def _edges(OLD, UP, DOWN, LEFT, RIGHT):
    """The facing edge of each neighbour, as a ``(1, w)`` row or an
    ``(h, 1)`` column; zeros where the grid ends."""
    h, w = OLD.shape
    return (jnp.zeros((1, w), OLD.dtype) if UP is None else UP[-1:, :],
            jnp.zeros((1, w), OLD.dtype) if DOWN is None else DOWN[:1, :],
            jnp.zeros((h, 1), OLD.dtype) if LEFT is None else LEFT[:, -1:],
            jnp.zeros((h, 1), OLD.dtype) if RIGHT is None else RIGHT[:, :1])


def _sweep(OLD, up, down, left, right):
    """The sweep of one tile on the device, memory-bound: each neighbour
    term is the tile shifted by one point (``lax.pad`` with one negative
    edge: zeros come in, nothing is copied) plus the neighbour's edge
    strip padded to the tile, summed in the order of
    :func:`reference_stencil`.  XLA makes ONE fusion of it, one read and
    one write of the tile (compiled for a v5e at 4096 x 4096: no
    temporary; four ``concatenate``s of slices cost four, 256 MiB)."""
    h, w = OLD.shape
    zero = jnp.zeros((), OLD.dtype)

    def shifted(lo_r, hi_r, lo_c, hi_c):
        return lax.pad(OLD, zero, ((lo_r, hi_r, 0), (lo_c, hi_c, 0)))

    north = shifted(1, -1, 0, 0) + lax.pad(up, zero, ((0, h - 1, 0), (0, 0, 0)))
    south = shifted(-1, 1, 0, 0) + lax.pad(down, zero, ((h - 1, 0, 0), (0, 0, 0)))
    west = shifted(0, 0, 1, -1) + lax.pad(left, zero, ((0, 0, 0), (0, w - 1, 0)))
    east = shifted(0, 0, -1, 1) + lax.pad(right, zero, ((0, 0, 0), (w - 1, 0, 0)))
    return 0.25 * (north + south + west + east)


def stencil_cpu(OLD, UP, DOWN, LEFT, RIGHT, NEW, **_):
    NEW[:] = _apply_5pt(OLD, UP, DOWN, LEFT, RIGHT)


def stencil_tpu(OLD, UP, DOWN, LEFT, RIGHT, NEW, **_):
    return _sweep(OLD, *_edges(OLD, UP, DOWN, LEFT, RIGHT))


def stencil_bf16(OLD, UP, DOWN, LEFT, RIGHT, NEW, **_):
    """The sweep with operands and sums in bfloat16: the lower-precision
    path the benchmark's check is held against, and nothing else."""
    lo = [x.astype(jnp.bfloat16)
          for x in (OLD, *_edges(OLD, UP, DOWN, LEFT, RIGHT))]
    return _sweep(*lo).astype(OLD.dtype)


def stencil_pallas(OLD, UP, DOWN, LEFT, RIGHT, NEW, **_):
    """Pallas chore: the sweep as one kernel that holds the whole tile in
    VMEM (:func:`parsec_tpu.ops.pallas_kernels.stencil_5pt`), for tiles
    of at most :data:`PALLAS_TILE_BYTES`; a larger tile is refused here,
    not inside Mosaic."""
    from .pallas_kernels import stencil_5pt

    nbytes = OLD.size * OLD.dtype.itemsize
    if nbytes > PALLAS_TILE_BYTES:
        raise ValueError(
            f"stencil_pallas: a {OLD.shape} {OLD.dtype} tile is {nbytes} "
            f"bytes; the kernel holds a whole tile in VMEM and takes at "
            f"most {PALLAS_TILE_BYTES} (use the jnp body, use_tpu=True)")
    return stencil_5pt(OLD, *_edges(OLD, UP, DOWN, LEFT, RIGHT))


def stencil_ptg(*, use_tpu: bool = False, use_pallas: bool = False,
                use_cpu: bool = True, bf16_updates: bool = False) -> PTG:
    """Build the 2D 5-point stencil PTG; instantiate with
    ``taskpool(T=iters, MT=A.mt, NT=A.nt, A=A, B=B, TILE_SHAPE=(A.mb,
    A.nb), TILE_DTYPE=A.default_dtype)`` over tiled matrices, or through
    :func:`stencil_taskpool`, which fills these in.

    ``bf16_updates`` runs the device sweep in bfloat16 (one rounding to 8
    bits a sum): the benchmark's control, as for dpotrf and the tile QR."""
    ptg = PTG("stencil2d")
    st = ptg.task_class("stencil", t="0 .. T-1", i="0 .. MT-1", j="0 .. NT-1")
    st.affinity("A(i, j)")
    st.priority("T - t")
    # previous generation: own tile + four halos (guarded at boundaries)
    st.flow("OLD", IN,
            "<- (t == 0) ? A(i, j) : NEW stencil(t-1, i, j)")
    # halo flows end in an explicit `<- NONE` fallback: a flow with *no*
    # matched input dep is "route not decided yet" (dynamic guards,
    # reference jdf2c.c:3008 startup rules), while the boundary tiles here
    # statically have no neighbor — which must be said explicitly (the
    # reference stencil writes `(...)? A task(...): NULL` the same way)
    st.flow("UP", IN,
            "<- (t == 0 and i > 0) ? A(i-1, j)",
            "<- (t > 0 and i > 0) ? NEW stencil(t-1, i-1, j)",
            "<- NONE")
    st.flow("DOWN", IN,
            "<- (t == 0 and i < MT-1) ? A(i+1, j)",
            "<- (t > 0 and i < MT-1) ? NEW stencil(t-1, i+1, j)",
            "<- NONE")
    st.flow("LEFT", IN,
            "<- (t == 0 and j > 0) ? A(i, j-1)",
            "<- (t > 0 and j > 0) ? NEW stencil(t-1, i, j-1)",
            "<- NONE")
    st.flow("RIGHT", IN,
            "<- (t == 0 and j < NT-1) ? A(i, j+1)",
            "<- (t > 0 and j < NT-1) ? NEW stencil(t-1, i, j+1)",
            "<- NONE")
    # this generation: written, never read by its own task.  A tile born
    # where the task runs and dead with its last reader; the last sweep
    # writes the result's tile, the only one with a home to go to
    st.flow("NEW", OUT,
            "<- (t == T-1) ? B(i, j) : NEW",
            "-> (t < T-1) ? OLD stencil(t+1, i, j)",
            "-> (t < T-1 and i > 0) ? DOWN stencil(t+1, i-1, j)",
            "-> (t < T-1 and i < MT-1) ? UP stencil(t+1, i+1, j)",
            "-> (t < T-1 and j > 0) ? RIGHT stencil(t+1, i, j-1)",
            "-> (t < T-1 and j < NT-1) ? LEFT stencil(t+1, i, j+1)",
            "-> (t == T-1) ? B(i, j)")
    kw = {}
    if use_cpu:
        kw["cpu"] = stencil_cpu
    if use_tpu or use_pallas or bf16_updates:
        kw["tpu"] = stencil_bf16 if bf16_updates \
            else stencil_pallas if use_pallas else stencil_tpu
    if not kw:
        raise ValueError(
            "stencil_ptg: no BODY selected (use_cpu, use_tpu and "
            "use_pallas are all False)")
    st.body(**kw)
    return ptg


def stencil_taskpool(A, iters: int, *, B=None, **bodies):
    """The taskpool of ``iters`` sweeps over the tiled matrix ``A`` (see
    :func:`stencil_grid`); the result is in ``B``'s tiles when it has
    quiesced.  ``B`` defaults to ``A``, the sweeps in place, which one
    sweep over several tiles cannot be (module docstring).  ``bodies``
    are :func:`stencil_ptg`'s keywords."""
    if B is None:
        B = A
    if B is A and iters < 2 and A.mt * A.nt > 1:
        raise ValueError(
            "stencil_taskpool: one sweep in place would write tiles that "
            "its neighbours' tasks are still reading; pass a second "
            "matrix B for the result")
    return stencil_ptg(**bodies).taskpool(
        T=iters, MT=A.mt, NT=A.nt, A=A, B=B, TILE_SHAPE=(A.mb, A.nb),
        TILE_DTYPE=A.default_dtype)


def reference_stencil(grid: np.ndarray, iters: int) -> np.ndarray:
    """Dense numpy model for verification."""
    g = grid.copy()
    for _ in range(iters):
        pad = np.zeros((g.shape[0] + 2, g.shape[1] + 2), g.dtype)
        pad[1:-1, 1:-1] = g
        g = 0.25 * (pad[:-2, 1:-1] + pad[2:, 1:-1] + pad[1:-1, :-2] + pad[1:-1, 2:])
    return g
