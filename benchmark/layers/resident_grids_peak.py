"""layer: device.  source: ``memory_stats()["peak_bytes_in_use"]`` of the
fullest chip after the window.  moves: ``tile_solve_s``.  The most the
device held, in grids: 3.0 is generation 0 and two live generations; more
is a generation released late (or generation 0 kept while there was room
under the budget: up to budget / grid)."""

from benchmark import ops_count_stencil


def read(run):
    peak = run.memory.get("peak_bytes")
    if not peak:
        return None
    return peak / ops_count_stencil.grid_bytes(run.size("n"))
