"""layer: device.  source: the device module's ``bytes_out``.  moves:
``tile_home_s``.  Bytes written home per solve over the bytes of the
factor: 1 is every tile once."""

from benchmark import ops_count


def read(run):
    out = run.per_solve("bytes_out")
    if out is None:
        return None
    return out / ops_count.lower_tiles_bytes(run.size("n"), run.size("nb"))
