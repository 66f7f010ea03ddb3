"""layer: device.  source: the device module's ``bytes_in``.  moves:
``tile_solve_s``.  Bytes staged host->device per solve over the bytes of
the lower matrix: 1 is every tile staged once; what is over 1 came back
after an eviction (the out-of-core cell: the matrix is larger than the
device's budget)."""

from benchmark import ops_count


def read(run):
    moved = run.per_solve("bytes_in")
    if moved is None:
        return None
    return moved / ops_count.lower_tiles_bytes(run.size("n"), run.size("nb"))
