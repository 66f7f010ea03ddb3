"""layer: ops kernels.  source: the profiler's trace, ``XLA Modules``
line.  moves: ``tile_solve_s``.  Device milliseconds of the geqrt, tsqrt
and ttqrt programs of the hierarchical tile QR per such task (1,020 +
3,048 + 1,012 a solve at 512 x 8 tiles, domains of 4): the Householder
kernels, bound by latency, so a time and not a share of a peak.  Where a
wave of them runs as a batch on the chip this falls with the wave's
width (``kill_wave_width``); where they run one after another it does
not.  Nothing to read from a program whose modules carry none of these
classes."""

from benchmark import ops_count_geqrf_hqr as hqr
from benchmark.trace import modules


def read(run):
    m = modules.of_run(run)
    if m is None:
        return None
    busy = m.seconds_of(hqr.KILLS, hqr.CLASSES)
    if busy is None:
        return None
    return 1e3 * busy / hqr.kill_tasks(*hqr.grid_of(run.size))
