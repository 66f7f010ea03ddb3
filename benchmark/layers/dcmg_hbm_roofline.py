"""layer: ops kernels.  source: the profiler's trace, ``XLA Modules``
line.  moves: ``tile_solve_s``.  The least time the chip's memory could
take for the bytes the covariance generator writes (every lower tile
once, in the precision the map stores it: ``ops_count_mle.matrix_bytes``;
the locations it reads are a share of 1/nb of that and are left out) at
its published bandwidth, over the device seconds a solve of the programs
that carry ``dcmg`` in their names.  The body is bound by ``exp`` and the
square root, not by the store: this share says by how much.  Nothing to
read from a program whose modules carry no class."""

from benchmark import ops_count_mle
from benchmark.trace import modules


def read(run):
    m = modules.of_run(run)
    if m is None or not run.peaks or "band_f32" not in run.cell.config:
        return None
    busy = m.seconds_of(("dcmg",), ops_count_mle.CLASSES)
    if not busy:
        return None
    least = ops_count_mle.matrix_bytes(
        run.size("n"), run.size("nb"), run.size("band_f32")) \
        / (run.peaks["hbm_bytes_per_s"] * run.cell.chips)
    return 100.0 * least / busy
