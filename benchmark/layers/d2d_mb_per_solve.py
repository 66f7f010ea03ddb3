"""layer: comm.  source: the device modules' ``bytes_d2d``.  moves:
``tile_solve_s``.  Megabytes of tiles landed device-to-device per solve,
all ranks."""


def read(run):
    v = run.per_solve("bytes_d2d")
    return v / 1e6 if v else None
