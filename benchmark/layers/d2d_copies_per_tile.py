"""layer: device.  source: the device modules' ``d2d_tiles``.  moves:
``tile_solve_s``.  Tiles landed chip to chip per solve (a peer module's
newest copy taken with a direct ``device_put``, never over the host) over
the tiles of the lower matrix: how many other chips read a tile, on
average.  Nothing to read from a program without the counter."""


def read(run):
    landed = run.per_solve("d2d_tiles")
    if landed is None:
        return None
    nt = run.size("n") // run.size("nb")
    return landed / (nt * (nt + 1) // 2)
