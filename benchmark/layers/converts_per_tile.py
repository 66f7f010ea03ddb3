"""layer: device.  source: the device module's ``convert_tiles`` (tiles
written by a body marked ``_converts``).  moves: ``tile_solve_s``.
Conversions a solve over the float32 tiles that a bfloat16 update reads
(``ops_count_mle.converted_tiles``): 1.00 is "once, where the tile is
produced, its readers share the twin"; a program whose readers convert on
their own would read their number a tile.  Nothing to read from a program
without the counter or a map without such a tile."""

from benchmark import ops_count_mle


def read(run):
    made = run.per_solve("convert_tiles")
    if made is None or "band_f32" not in run.cell.config:
        return None
    tiles = ops_count_mle.converted_tiles(
        run.size("n") // run.size("nb"), run.size("band_f32"))
    return made / tiles if tiles else None
