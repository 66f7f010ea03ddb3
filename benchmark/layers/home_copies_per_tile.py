"""layer: device.  source: the device module's ``bytes_out``.  moves:
``tile_home_s``.  Bytes written home per solve over the bytes of the
matrix (R above the diagonal, the zeros that took A's place below): 1 is
every tile once.  What ``d2h_per_result`` is for dpotrf."""

from benchmark import ops_count_geqrf


def read(run):
    out = run.per_solve("bytes_out")
    if out is None:
        return None
    return out / ops_count_geqrf.matrix_bytes(run.size("n"))
