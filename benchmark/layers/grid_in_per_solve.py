"""layer: device.  source: the device module's ``bytes_in``.  moves:
``tile_solve_s``.  Bytes staged host->device per solve over the bytes of
the grid: 1.00 is generation 0 staged once and no later generation ever;
2 was a second, zeroed buffer staged beside it."""

from benchmark import ops_count_stencil


def read(run):
    moved = run.per_solve("bytes_in")
    if moved is None:
        return None
    return moved / ops_count_stencil.grid_bytes(run.size("n"))
