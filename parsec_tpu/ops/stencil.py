"""Iterative 5-point stencil over a tile grid, as a PTG.

Reference: ``/root/reference/tests/apps/stencil/`` (stencil test app,
``testing_stencil_1D.c``) and the BASELINE.json "Stencil 2D5pt, comm/compute
overlap" config. Each iteration's tile task consumes its own previous
value plus the four neighbours' previous values (halo exchange expressed
purely as dataflow), so the runtime overlaps neighbour communication with
interior compute automatically — the property the reference measures.

WAR safety: iteration t writes the parity-((t+1)%2) buffer while reading
the parity-(t%2) buffers. A tile's generation-t value is read only by
generation t+1 of itself and its 4 neighbours, and the next writer of the
same physical buffer is generation t+2 of the same tile — which depends on
exactly those t+1 readers, so two-generation separation makes the in-place
write race-free (the classic double-buffered stencil dataflow).

Task space: stencil(t, i, j), T iterations over an MT×NT tile grid.
The backing collection ``A`` is keyed (parity, i, j); the result after T
iterations lives at parity ``T % 2``.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from ..core.lifecycle import AccessMode
from ..data.collection import DataCollection
from ..data.data import Data, data_create
from ..dsl.ptg import PTG

IN = AccessMode.IN
INOUT = AccessMode.INOUT

try:
    import jax.numpy as jnp
except Exception:  # pragma: no cover
    jnp = None


class StencilBuffers(DataCollection):
    """Double-buffered tile grid: keys are (parity, i, j); parity 0 holds
    the initial state, parity 1 is scratch."""

    def __init__(self, grid: np.ndarray, mt: int, nt: int, *, nodes: int = 1,
                 myrank: int = 0, rank_of=None, name: str = "A"):
        super().__init__(name, nodes=nodes, myrank=myrank)
        self.mt, self.nt = mt, nt
        h, w = grid.shape
        # shared tiling check (ops.tiles.check_tiling): a non-dividing
        # grid used to be a bare assert — silently truncated under -O
        from .tiles import check_tiling

        check_tiling(h, mt, what="grid rows", op="stencil")
        check_tiling(w, nt, what="grid cols", op="stencil")
        self.th, self.tw = h // mt, w // nt
        self.dtype = grid.dtype
        self._rank_of = rank_of
        self._store = {}
        import threading

        self._lock = threading.Lock()
        self._grid0 = grid

    def data_key(self, *key):
        if len(key) == 1:
            key = key[0]
        p, i, j = key
        return (int(p), int(i), int(j))

    def rank_of(self, *key):
        p, i, j = self.data_key(*key)
        if self._rank_of is not None:
            return self._rank_of(i, j)
        return 0

    def data_of(self, *key) -> Data:
        k = self.data_key(*key)
        with self._lock:
            d = self._store.get(k)
            if d is None:
                p, i, j = k
                if p == 0:
                    # copy (not a view): the runtime mutates tiles in place
                    # and must never alias the caller's array
                    tile = self._grid0[i * self.th:(i + 1) * self.th,
                                       j * self.tw:(j + 1) * self.tw].copy()
                else:
                    tile = np.zeros((self.th, self.tw), self.dtype)
                d = data_create(k, self, payload=tile)
                self._store[k] = d
            return d

    def to_array(self, parity: int) -> np.ndarray:
        out = np.zeros((self.mt * self.th, self.nt * self.tw), self.dtype)
        for i in range(self.mt):
            for j in range(self.nt):
                c = self.data_of(parity, i, j).newest_copy()
                out[i * self.th:(i + 1) * self.th, j * self.tw:(j + 1) * self.tw] = \
                    np.asarray(c.payload)
        return out


def _apply_5pt(xp, OLD, UP, DOWN, LEFT, RIGHT):
    h, w = OLD.shape
    pad = xp.zeros((h + 2, w + 2), OLD.dtype)
    if xp is np:
        pad[1:-1, 1:-1] = OLD
        if UP is not None:
            pad[0, 1:-1] = UP[-1, :]
        if DOWN is not None:
            pad[-1, 1:-1] = DOWN[0, :]
        if LEFT is not None:
            pad[1:-1, 0] = LEFT[:, -1]
        if RIGHT is not None:
            pad[1:-1, -1] = RIGHT[:, 0]
    else:
        pad = pad.at[1:-1, 1:-1].set(OLD)
        if UP is not None:
            pad = pad.at[0, 1:-1].set(UP[-1, :])
        if DOWN is not None:
            pad = pad.at[-1, 1:-1].set(DOWN[0, :])
        if LEFT is not None:
            pad = pad.at[1:-1, 0].set(LEFT[:, -1])
        if RIGHT is not None:
            pad = pad.at[1:-1, -1].set(RIGHT[:, 0])
    return 0.25 * (pad[:-2, 1:-1] + pad[2:, 1:-1] + pad[1:-1, :-2] + pad[1:-1, 2:])


def stencil_cpu(OLD, UP, DOWN, LEFT, RIGHT, NEW, **_):
    NEW[:] = _apply_5pt(np, OLD, UP, DOWN, LEFT, RIGHT)


def stencil_tpu(OLD, UP, DOWN, LEFT, RIGHT, NEW, **_):
    return _apply_5pt(jnp, OLD, UP, DOWN, LEFT, RIGHT)


def stencil_pallas(OLD, UP, DOWN, LEFT, RIGHT, NEW, **_):
    """Pallas chore: the 5-point step as one VMEM-resident kernel
    (:func:`parsec_tpu.ops.pallas_kernels.stencil_5pt`); halo tiles are
    reduced to their facing edge rows/columns before the call."""
    from .pallas_kernels import stencil_5pt

    h, w = OLD.shape
    up = jnp.zeros((1, w), OLD.dtype) if UP is None else UP[-1:, :]
    down = jnp.zeros((1, w), OLD.dtype) if DOWN is None else DOWN[:1, :]
    left = jnp.zeros((h, 1), OLD.dtype) if LEFT is None else LEFT[:, -1:]
    right = jnp.zeros((h, 1), OLD.dtype) if RIGHT is None else RIGHT[:, :1]
    return stencil_5pt(OLD, up, down, left, right)


def stencil_ptg(*, use_tpu: bool = False, use_pallas: bool = False,
                use_cpu: bool = True) -> PTG:
    """Build the 2D 5-point stencil PTG; instantiate with
    ``taskpool(T=iters, MT=..., NT=..., A=StencilBuffers(...))``."""
    ptg = PTG("stencil2d")
    st = ptg.task_class("stencil", t="0 .. T-1", i="0 .. MT-1", j="0 .. NT-1")
    st.affinity("A(0, i, j)")
    st.priority("T - t")
    # previous generation: own tile + four halos (guarded at boundaries)
    st.flow("OLD", IN,
            "<- (t == 0) ? A(0, i, j) : NEW stencil(t-1, i, j)")
    # halo flows end in an explicit `<- NONE` fallback: a flow with *no*
    # matched input dep is "route not decided yet" (dynamic guards,
    # reference jdf2c.c:3008 startup rules), while the boundary tiles here
    # statically have no neighbor — which must be said explicitly (the
    # reference stencil writes `(...)? A task(...): NULL` the same way)
    st.flow("UP", IN,
            "<- (t == 0 and i > 0) ? A(0, i-1, j)",
            "<- (t > 0 and i > 0) ? NEW stencil(t-1, i-1, j)",
            "<- NONE")
    st.flow("DOWN", IN,
            "<- (t == 0 and i < MT-1) ? A(0, i+1, j)",
            "<- (t > 0 and i < MT-1) ? NEW stencil(t-1, i+1, j)",
            "<- NONE")
    st.flow("LEFT", IN,
            "<- (t == 0 and j > 0) ? A(0, i, j-1)",
            "<- (t > 0 and j > 0) ? NEW stencil(t-1, i, j-1)",
            "<- NONE")
    st.flow("RIGHT", IN,
            "<- (t == 0 and j < NT-1) ? A(0, i, j+1)",
            "<- (t > 0 and j < NT-1) ? NEW stencil(t-1, i, j+1)",
            "<- NONE")
    # the write buffer: the opposite-parity tile, WAR-safe (see module doc)
    st.flow("NEW", INOUT,
            "<- A((t+1) % 2, i, j)",
            "-> (t < T-1) ? OLD stencil(t+1, i, j)",
            "-> (t < T-1 and i > 0) ? DOWN stencil(t+1, i-1, j)",
            "-> (t < T-1 and i < MT-1) ? UP stencil(t+1, i+1, j)",
            "-> (t < T-1 and j > 0) ? RIGHT stencil(t+1, i, j-1)",
            "-> (t < T-1 and j < NT-1) ? LEFT stencil(t+1, i, j+1)",
            "-> A((t+1) % 2, i, j)")
    kw = {}
    if use_cpu:
        kw["cpu"] = stencil_cpu
    if use_tpu or use_pallas:
        kw["tpu"] = stencil_pallas if use_pallas else stencil_tpu
    if not kw:
        raise ValueError(
            "stencil_ptg: no BODY selected (use_cpu, use_tpu and "
            "use_pallas are all False)")
    st.body(**kw)
    return ptg


def reference_stencil(grid: np.ndarray, iters: int) -> np.ndarray:
    """Dense numpy model for verification."""
    g = grid.copy()
    for _ in range(iters):
        pad = np.zeros((g.shape[0] + 2, g.shape[1] + 2), g.dtype)
        pad[1:-1, 1:-1] = g
        g = 0.25 * (pad[:-2, 1:-1] + pad[2:, 1:-1] + pad[1:-1, :-2] + pad[1:-1, 2:])
    return g
