"""Tasks and bytes of the iterative 2D 5-point stencil on an n x n grid
of float32 cut into nb x nb tiles, from shapes alone."""

#: the DAG's task classes, as the device programs' module names carry them
CLASSES = ("stencil",)


def stencil_ntasks(n: int, nb: int, iters: int) -> int:
    """One task a tile a sweep."""
    return iters * (n // nb) ** 2


def grid_bytes(n: int, itemsize: int = 4) -> int:
    """The grid: what one solve stages in once and brings home once, and
    what one live generation holds on the device."""
    return n * n * itemsize


def sweep_hbm_bytes(n: int, iters: int, itemsize: int = 4) -> int:
    """The least bytes ``iters`` sweeps move through the device's memory
    when a program makes ONE sweep a pass: every point read once and
    written once a sweep (the neighbours' edges are a share of 1/nb of
    that and are left out).  A program that blocks several sweeps in one
    pass over a tile moves fewer: this count is not its."""
    return 2 * itemsize * n * n * iters
