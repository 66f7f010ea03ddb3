"""Tasks, operations and bytes of the tile QR (PLASMA's TS kernels:
geqrt, unmqr, tsqrt, tsmqr) on nt x nt tiles of nb x nb, from shapes
alone.

Two operation counts, kept apart: LAPACK's, which is what a user of
dgeqrf asked for, and what the program EXECUTES.  The program passes the
orthogonal factors along as dense blocks (nb x nb from geqrt, 2nb x 2nb
from tsqrt) instead of compact-WY (V, T), so an update is one plain
product and costs twice LAPACK's count."""

#: the DAG's task classes, as the device programs' module names carry them
CLASSES = ("geqrt", "unmqr", "tsqrt", "tsmqr")


def geqrf_tasks(nt: int) -> dict:
    """Tasks of each class."""
    return {"geqrt": nt, "unmqr": nt * (nt - 1) // 2,
            "tsqrt": nt * (nt - 1) // 2,
            "tsmqr": (nt - 1) * nt * (2 * nt - 1) // 6}


def geqrf_ntasks(nt: int) -> int:
    return sum(geqrf_tasks(nt).values())


def geqrf_flops(n: int) -> float:
    """Householder QR of an n x n matrix, R alone: 4 n^3 / 3 (LAPACK's
    count, lower-order terms dropped)."""
    return 4.0 * float(n) ** 3 / 3.0


def update_flops_executed(nt: int, nb: int) -> float:
    """What the unmqr and tsmqr programs execute: Q^T C with Q dense,
    (nb x nb)(nb x nb) for unmqr and (2nb x 2nb)(2nb x nb) for tsmqr."""
    t = geqrf_tasks(nt)
    return 2.0 * nb ** 3 * t["unmqr"] + 8.0 * nb ** 3 * t["tsmqr"]


def panel_tasks(nt: int) -> int:
    """geqrt + tsqrt: the tasks on the DAG's critical path."""
    t = geqrf_tasks(nt)
    return t["geqrt"] + t["tsqrt"]


def matrix_bytes(n: int, itemsize: int = 4) -> int:
    """All nt x nt tiles of A: what one solve stages in once, and (R
    above, the zeros that took A's place below) brings home once."""
    return n * n * itemsize


def r_bytes(n: int, nb: int, itemsize: int = 4) -> int:
    """R as upper tiles."""
    nt = n // nb
    return nt * (nt + 1) // 2 * nb * nb * itemsize


def scratch_bytes(n: int, nb: int, itemsize: int = 4) -> int:
    """The dense Q blocks of one solve: nt of nb x nb, nt (nt - 1) / 2
    of 2nb x 2nb.  They are born on the chip and die there: none of
    these bytes should cross to or from the host."""
    t = geqrf_tasks(n // nb)
    return (t["geqrt"] + 4 * t["tsqrt"]) * nb * nb * itemsize
