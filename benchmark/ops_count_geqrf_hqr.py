"""Tasks, operations and bytes of the hierarchical tile QR (DPLASMA's
``dgeqrf_param`` over TS domains of ``a`` tile rows and a binary TT tree
over the domain heads) on mt x nt tiles of nb x nb, from shapes and the
domain convention alone.  Imports nothing of the program.

The convention (the configuration's ``assumed.domains``): in panel k the
rows are k .. mt-1; row m belongs to domain m // a by global row index;
a domain's head is its first row present, so the heads of panel k are
row k and every later multiple of a.  Every head gets a geqrt (and an
unmqr a trailing column); every other row is killed by its head (tsqrt,
tsmqr); every head but row k is killed by another head (ttqrt, ttmqr).

Two operation counts, kept apart as for the square tile QR
(``ops_count_geqrf``): LAPACK's, which is what a user of dgeqrf asked
for, and what the program EXECUTES with its dense Q blocks (nb x nb from
geqrt, 2nb x 2nb from a kill): an update is one plain product."""

#: the DAG's task classes, as the device programs' module names carry them
CLASSES = ("geqrt", "unmqr", "tsqrt", "tsmqr", "ttqrt", "ttmqr")
KILLS = ("geqrt", "tsqrt", "ttqrt")
UPDATES = ("unmqr", "tsmqr", "ttmqr")


def grid_of(size) -> tuple:
    """(mt, nt, a) from a configuration's sizes: ``size(key)`` gives
    ``m``, ``n``, ``nb`` and ``qr_a``."""
    nb = size("nb")
    return size("m") // nb, size("n") // nb, size("qr_a")


def heads(mt: int, a: int, k: int) -> int:
    """Domain heads of panel k: row k and the multiples of a after it."""
    return 1 + (mt - 1) // a - k // a


def hqr_tasks(mt: int, nt: int, a: int) -> dict:
    """Tasks of each class."""
    t = dict.fromkeys(CLASSES, 0)
    for k in range(nt):
        h = heads(mt, a, k)
        ts = mt - k - h
        cols = nt - 1 - k
        t["geqrt"] += h
        t["tsqrt"] += ts
        t["ttqrt"] += h - 1
        t["unmqr"] += h * cols
        t["tsmqr"] += ts * cols
        t["ttmqr"] += (h - 1) * cols
    return t


def hqr_ntasks(mt: int, nt: int, a: int) -> int:
    return sum(hqr_tasks(mt, nt, a).values())


def geqrf_flops(m: int, n: int) -> float:
    """Householder QR of an m x n matrix (m >= n), R alone: 2 m n^2 -
    2 n^3 / 3 (LAPACK's count, lower-order terms dropped)."""
    return 2.0 * m * float(n) ** 2 - 2.0 * float(n) ** 3 / 3.0


def update_flops_executed(mt: int, nt: int, a: int, nb: int) -> float:
    """What the unmqr, tsmqr and ttmqr programs execute: Q^T C with Q
    dense, (nb x nb)(nb x nb) for unmqr and (2nb x 2nb)(2nb x nb) for
    the two kills' updates."""
    t = hqr_tasks(mt, nt, a)
    return 2.0 * nb ** 3 * t["unmqr"] \
        + 8.0 * nb ** 3 * (t["tsmqr"] + t["ttmqr"])


def kill_tasks(mt: int, nt: int, a: int) -> int:
    """geqrt + tsqrt + ttqrt: the Householder kernels."""
    t = hqr_tasks(mt, nt, a)
    return sum(t[c] for c in KILLS)


def matrix_bytes(m: int, n: int, itemsize: int = 4) -> int:
    """All mt x nt tiles of A: what one solve stages in once, and (R in
    the upper tiles of the first nt rows, zeros everywhere else) brings
    home once."""
    return m * n * itemsize


def r_bytes(n: int, nb: int, itemsize: int = 4) -> int:
    """R as upper tiles."""
    nt = n // nb
    return nt * (nt + 1) // 2 * nb * nb * itemsize


def scratch_bytes(mt: int, nt: int, a: int, nb: int,
                  itemsize: int = 4) -> int:
    """The dense Q blocks of one solve (nb x nb a geqrt, 2nb x 2nb a
    kill): born on the chip, and none of them should cross the host."""
    t = hqr_tasks(mt, nt, a)
    return (t["geqrt"] + 4 * (t["tsqrt"] + t["ttqrt"])) * nb * nb * itemsize
