"""layer: ops kernels.  source: the profiler's trace, ``XLA Modules``
line.  moves: ``tile_solve_s``.  The least time the chip could take for
LAPACK's 2MN^2 - 2N^3/3 operations at its published bf16 peak, over the
device seconds a solve of the programs of all six classes of the
hierarchical tile QR (geqrt, unmqr, tsqrt, tsmqr, ttqrt, ttmqr).  The
program EXECUTES about twice that count in its updates alone (dense Q
blocks in place of compact-WY: ``ops_count_geqrf_hqr``), f32 at
``highest`` is six bf16 passes, and the Householder kernels are bound by
latency: the ceiling of this share is a twelfth, not 100.  Nothing to
read from a program whose modules carry none of these classes."""

from benchmark import ops_count, ops_count_geqrf_hqr as hqr
from benchmark.trace import modules


def read(run):
    m = modules.of_run(run)
    if m is None or not run.peaks:
        return None
    busy = m.seconds_of(hqr.CLASSES, hqr.CLASSES)
    if not busy:
        return None
    return ops_count.roofline_pct(
        hqr.geqrf_flops(run.size("m"), run.size("n")),
        run.peaks["bf16_flops_per_s"], run.cell.chips, busy)
