"""layer: device.  source: the residency's ``tiles_by_dtype`` (bytes
charged by precision at the peak of a solve), summed by the driver.
moves: ``tile_solve_s``.  Bytes resident at the peak over the bytes of
the lower matrix in float32, N (N + nb) / 2 x 4: what mixing the
precisions buys in memory, the converted twins and the vectors counted
in (0.586 is the matrix alone at band_f32 = 4; 1.0 does not fit the
chip).  Nothing to read from a program without the counter."""

from benchmark import ops_count


def read(run):
    peak = run.per_solve("resident_peak_bytes")
    if not peak:
        return None
    return peak / ops_count.lower_tiles_bytes(run.size("n"), run.size("nb"))
