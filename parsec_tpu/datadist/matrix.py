"""Tiled-matrix descriptors and distributions.

Reference: ``/root/reference/parsec/data_dist/matrix/`` —
``parsec_tiled_matrix_t`` base descriptor (``matrix.h``: mb/nb tile sizes,
lm/ln full sizes, mt/nt tile counts, uplo storage) and the workhorse
ScaLAPACK-style two-dimensional block-cyclic distribution with k-cyclic
super-tiling (``two_dim_rectangle_cyclic.{c,h}``, init ``:73``; placement:
row rank = (m / kp) %% P, col rank = (n / kq) %% Q), plus the symmetric
(lower/upper) variant (``sym_two_dim_rectangle_cyclic.c``) and the tabular
arbitrary-rank-table distribution (``two_dim_tabular.c``).
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

from ..data.collection import DataCollection
from ..data.data import KEPT, Data, data_create

LOWER = "lower"
UPPER = "upper"
FULL = "full"


class TiledMatrix(DataCollection):
    """Base tiled-matrix collection: an ``m×n`` matrix cut into ``mb×nb``
    tiles (ragged edge tiles allowed), keys are ``(i, j)`` tile indices.

    ``tile_dtype`` gives a tile a precision of its own: a callable
    ``(i, j) -> dtype`` (None: the matrix's ``dtype``), which answers the
    same for the matrix's whole life.  ``dtype`` stays the matrix's widest
    precision, the one :meth:`to_array` gathers into; :meth:`dtype_of` is
    a tile's, which :meth:`data_of` and :meth:`from_array` store it in.
    The map is data: two matrices that differ only in it are two shapes
    to whoever fingerprints a collection (``dsl/attach_plan.py``).

    ``device_born=True``: no tile has a host value until somebody asks
    for one.  :meth:`data_of` makes a ``Data`` that knows its shape and
    dtype and holds no copy; the tile is born where its first writer
    runs, lives as long as the matrix, and the device module never writes
    it home (``device/scratch.py``, ``data.KEPT``): whoever wants rows of
    it reads them where they live (``newest_copy().payload``), and
    :meth:`to_array` fetches what it gathers.  The lazily created zero
    tile of an ordinary matrix is a host array a tile: 990 of them are
    16 GB for a matrix whose first task overwrites every one."""

    def __init__(
        self,
        m: int,
        n: int,
        mb: int,
        nb: int,
        *,
        name: str = "A",
        dtype=np.float64,
        nodes: int = 1,
        myrank: int = 0,
        uplo: str = FULL,
        init: Optional[Callable[[int, int, Tuple[int, int]], np.ndarray]] = None,
        tile_dtype=None,
        device_born: bool = False,
    ):
        super().__init__(name, nodes=nodes, myrank=myrank)
        self.m, self.n, self.mb, self.nb = m, n, mb, nb
        self.mt = (m + mb - 1) // mb
        self.nt = (n + nb - 1) // nb
        self.default_dtype = np.dtype(dtype)
        self.uplo = uplo
        if device_born and init is not None:
            raise ValueError(f"matrix {name}: a device-born matrix has no "
                             "host value to initialise")
        self._init = init
        self._tile_dtype = tile_dtype
        self._dtype_map = None
        self.device_born = bool(device_born)
        self._store: Dict[Tuple[int, int], Data] = {}
        self._lock = threading.Lock()

    # -- geometry ---------------------------------------------------------
    def tile_shape(self, i: int, j: int) -> Tuple[int, int]:
        return (
            min(self.mb, self.m - i * self.mb),
            min(self.nb, self.n - j * self.nb),
        )

    def dtype_of(self, i: int, j: int) -> np.dtype:
        """The precision tile ``(i, j)`` is stored in."""
        pick = self._tile_dtype
        if pick is None:
            return self.default_dtype
        dt = pick(i, j)
        return self.default_dtype if dt is None else np.dtype(dt)

    def dtype_map(self) -> Optional[Tuple[Tuple[str, int], ...]]:
        """The precision map as run lengths over :meth:`tiles` — what a
        fingerprint of the collection reads of it; None without a map."""
        if self._tile_dtype is None:
            return None
        if self._dtype_map is None:  # (asked at every attach)
            runs = []
            for key in self.tiles():
                name = self.dtype_of(*key).name
                if runs and runs[-1][0] == name:
                    runs[-1][1] += 1
                else:
                    runs.append([name, 1])
            self._dtype_map = tuple((name, n) for name, n in runs)
        return self._dtype_map

    def stored(self, i: int, j: int) -> bool:
        if not (0 <= i < self.mt and 0 <= j < self.nt):
            return False
        if self.uplo == LOWER:
            return i >= j
        if self.uplo == UPPER:
            return i <= j
        return True

    def tiles(self):
        """All stored (i, j) keys."""
        for i in range(self.mt):
            for j in range(self.nt):
                if self.stored(i, j):
                    yield (i, j)

    def local_tiles(self):
        for key in self.tiles():
            if self.rank_of(*key) == self.myrank:
                yield key

    # -- vtable -----------------------------------------------------------
    def data_key(self, *key) -> Tuple[int, int]:
        if len(key) == 1:
            key = key[0]
        i, j = key
        return (int(i), int(j))

    def data_of(self, *key) -> Data:
        k = self.data_key(*key)
        if not self.stored(*k):
            raise KeyError(f"tile {k} not stored in {self.uplo} matrix {self.name}")
        with self._lock:
            d = self._store.get(k)
            if d is None:
                shape = self.tile_shape(*k)
                dtype = self.dtype_of(*k)
                if self.device_born:
                    d = Data(k, self, shape=shape, dtype=dtype)
                    d.scratch = KEPT
                elif self._init is not None:
                    d = data_create(k, self, payload=np.asarray(
                        self._init(k[0], k[1], shape), dtype=dtype))
                else:
                    d = data_create(k, self,
                                    payload=np.zeros(shape, dtype))
                self._store[k] = d
            return d

    def materialized_keys(self):
        """Tile keys whose Data exists right now (no lazy creation)."""
        with self._lock:
            return list(self._store)

    # -- whole-matrix helpers (tests / verification) ----------------------
    def to_array(self) -> np.ndarray:
        """Gather the local tiles into a dense array (single-rank use)."""
        out = np.zeros((self.m, self.n), self.default_dtype)
        for (i, j) in self.tiles():
            if self.rank_of(i, j) != self.myrank:
                continue
            c = self.data_of(i, j).newest_copy()
            if c is None or c.payload is None:
                continue  # a device-born tile nobody has written
            h, w = self.tile_shape(i, j)
            out[i * self.mb : i * self.mb + h, j * self.nb : j * self.nb + w] = np.asarray(c.payload)[:h, :w]
        return out

    def from_array(self, a: np.ndarray) -> "TiledMatrix":
        if self.device_born:
            raise ValueError(f"matrix {self.name}: a device-born matrix "
                             "takes no host value")
        for (i, j) in self.tiles():
            if self.rank_of(i, j) != self.myrank:
                continue
            h, w = self.tile_shape(i, j)
            # copy (not a view): the runtime mutates tiles in place and must
            # never alias the caller's array
            tile = a[i * self.mb : i * self.mb + h, j * self.nb : j * self.nb + w].astype(
                self.dtype_of(i, j), copy=True)
            d = self.data_of(i, j)
            copy = d.get_copy(0) or d.attach_copy(0, tile)
            copy.payload = tile
        return self


def advise_data_on_devices(A: TiledMatrix, accelerators, grid,
                           uplo: Optional[str] = None) -> Dict[int, int]:
    """DPLASMA's ``dplasma_advise_data_on_device`` with its 2D-cyclic
    map: every local tile ``(m, n)`` of the ``uplo`` triangle of ``A``
    (``A``'s own storage where not given) is advised to accelerator
    ``(m mod p) * q + (n mod q)`` of ``accelerators``, the ``p x q``
    device ``grid`` — ``data_advise(tile, ADVICE_PREFERRED_DEVICE)``, the
    reference's ``PARSEC_DEV_DATA_ADVICE_PREFERRED_DEVICE``.  A task goes
    to the accelerator that holds, or failing that is advised to hold,
    the tile it writes (``device.select_best_device``).  Returns
    ``{device index: tiles advised to it}``."""
    from ..device.device import ADVICE_PREFERRED_DEVICE

    p, q = grid
    accelerators = list(accelerators)
    if p * q != len(accelerators):
        raise ValueError(f"a {p} x {q} device grid takes {p * q} "
                         f"accelerators, got {len(accelerators)}")
    uplo = A.uplo if uplo is None else uplo
    advised = {dev.index: 0 for dev in accelerators}
    for (m, n) in A.local_tiles():
        if (uplo == LOWER and m < n) or (uplo == UPPER and m > n):
            continue
        dev = accelerators[(m % p) * q + (n % q)]
        dev.data_advise(A.data_of(m, n), ADVICE_PREFERRED_DEVICE)
        advised[dev.index] += 1
    return advised


class TwoDimBlockCyclic(TiledMatrix):
    """ScaLAPACK-style 2D block-cyclic placement over a P×Q process grid
    with kp/kq k-cyclic super-tiling (reference
    ``two_dim_rectangle_cyclic.h:24-95``)."""

    def __init__(self, m, n, mb, nb, *, p: int = 1, q: int = 1, kp: int = 1, kq: int = 1, **kw):
        kw.setdefault("nodes", p * q)
        super().__init__(m, n, mb, nb, **kw)
        if p * q != self.nodes:
            raise ValueError(f"grid {p}x{q} incompatible with {self.nodes} nodes")
        self.p, self.q, self.kp, self.kq = p, q, kp, kq

    def rank_of(self, *key) -> int:
        i, j = self.data_key(*key)
        rrow = (i // self.kp) % self.p
        rcol = (j // self.kq) % self.q
        return rrow * self.q + rcol

    def vpid_of(self, *key) -> int:
        return 0


class SymTwoDimBlockCyclic(TwoDimBlockCyclic):
    """Symmetric (triangular-storage) block-cyclic matrix (reference
    ``sym_two_dim_rectangle_cyclic.c``)."""

    def __init__(self, m, n, mb, nb, *, uplo: str = LOWER, **kw):
        if uplo not in (LOWER, UPPER):
            raise ValueError("sym matrix needs uplo lower|upper")
        super().__init__(m, n, mb, nb, uplo=uplo, **kw)


class VectorTwoDimCyclic(TiledMatrix):
    """Distributed vector: ``m`` elements in ``mb``-sized segments, placed
    cyclically over the process grid (reference
    ``vector_two_dim_cyclic.{c,h}``).  Keys are single segment indices
    ``(i,)``; placement follows the row dimension of a P×Q grid so a vector
    aligns with the rows of a matching :class:`TwoDimBlockCyclic` matrix."""

    def __init__(self, m, mb, *, p: int = 1, q: int = 1, kp: int = 1, **kw):
        kw.setdefault("nodes", p * q)
        super().__init__(m, 1, mb, 1, **kw)
        if p * q != self.nodes:
            raise ValueError(f"grid {p}x{q} incompatible with {self.nodes} nodes")
        self.p, self.q, self.kp = p, q, kp

    def data_key(self, *key) -> Tuple[int, int]:
        if len(key) == 1 and not isinstance(key[0], tuple):
            return (int(key[0]), 0)
        return super().data_key(*key)

    def tile_shape(self, i: int, j: int = 0) -> Tuple[int, int]:
        return (min(self.mb, self.m - i * self.mb), 1)

    def rank_of(self, *key) -> int:
        i, _ = self.data_key(*key)
        return ((i // self.kp) % self.p) * self.q

    def vpid_of(self, *key) -> int:
        return 0


class TwoDimTabular(TiledMatrix):
    """Arbitrary rank table (reference ``two_dim_tabular.c``): placement
    comes from a user table or callable over tile keys."""

    def __init__(self, m, n, mb, nb, *, rank_table, **kw):
        super().__init__(m, n, mb, nb, **kw)
        self._rank_table = rank_table

    def rank_of(self, *key) -> int:
        k = self.data_key(*key)
        if callable(self._rank_table):
            return int(self._rank_table(*k))
        return int(self._rank_table[k])


class TwoDimBlockCyclicBand(TiledMatrix):
    """Composite band distribution (reference
    ``two_dim_rectangle_cyclic_band.{c,h}``): tiles within
    ``|i - j| < band_size`` of the diagonal delegate to the ``band``
    sub-distribution with the remapped row ``i - j + band_size - 1``
    (so the band is stored as a (2*band_size-1, NT) rectangle); all
    other tiles delegate to ``off_band``.  Storage lives in the
    sub-collections — this wrapper only routes."""

    def __init__(self, band: TiledMatrix, off_band: TiledMatrix,
                 band_size: int):
        super().__init__(off_band.m, off_band.n, off_band.mb, off_band.nb,
                         name=f"{off_band.name}_band",
                         nodes=off_band.nodes, myrank=off_band.myrank,
                         dtype=off_band.default_dtype)
        if band_size < 1:
            raise ValueError("band_size must be >= 1")
        self.band, self.off_band, self.band_size = band, off_band, band_size

    def _band_row(self, i: int, j: int) -> int:
        return i - j + self.band_size - 1

    def _in_band(self, i: int, j: int) -> bool:
        return abs(i - j) < self.band_size

    def rank_of(self, *key) -> int:
        i, j = self.data_key(*key)
        if self._in_band(i, j):
            return self.band.rank_of(self._band_row(i, j), j)
        return self.off_band.rank_of(i, j)

    def vpid_of(self, *key) -> int:
        i, j = self.data_key(*key)
        if self._in_band(i, j):
            return self.band.vpid_of(self._band_row(i, j), j)
        return self.off_band.vpid_of(i, j)

    def data_of(self, *key):
        i, j = self.data_key(*key)
        if self._in_band(i, j):
            return self.band.data_of(self._band_row(i, j), j)
        return self.off_band.data_of(i, j)


class SymTwoDimBlockCyclicBand(TiledMatrix):
    """Symmetric band composite (reference
    ``sym_two_dim_rectangle_cyclic_band.{c,h}``): band tiles remap to
    row ``|i - j|`` of the ``band`` sub-distribution (band stored as a
    (band_size, NT) rectangle); off-band tiles delegate to the
    symmetric ``off_band`` distribution."""

    def __init__(self, band: TiledMatrix, off_band: TiledMatrix,
                 band_size: int):
        super().__init__(off_band.m, off_band.n, off_band.mb, off_band.nb,
                         name=f"{off_band.name}_symband",
                         nodes=off_band.nodes, myrank=off_band.myrank,
                         dtype=off_band.default_dtype)
        if band_size < 1:
            raise ValueError("band_size must be >= 1")
        self.band, self.off_band, self.band_size = band, off_band, band_size

    def rank_of(self, *key) -> int:
        i, j = self.data_key(*key)
        if abs(i - j) < self.band_size:
            return self.band.rank_of(abs(i - j), j)
        return self.off_band.rank_of(i, j)

    def vpid_of(self, *key) -> int:
        i, j = self.data_key(*key)
        if abs(i - j) < self.band_size:
            return self.band.vpid_of(abs(i - j), j)
        return self.off_band.vpid_of(i, j)

    def data_of(self, *key):
        i, j = self.data_key(*key)
        if abs(i - j) < self.band_size:
            return self.band.data_of(abs(i - j), j)
        return self.off_band.data_of(i, j)
