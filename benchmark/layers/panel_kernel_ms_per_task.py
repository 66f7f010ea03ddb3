"""layer: ops kernels.  source: the profiler's trace, ``XLA Modules``
line.  moves: ``tile_solve_s``.  Device milliseconds of the geqrt and
tsqrt programs per such task (32 + 496 a solve at NT=32): the Householder
panel kernels, which chain along the DAG's critical path and are bound
by latency, so a time and not a share of a peak.  Nothing to read from a
program whose modules carry no class."""

from benchmark import ops_count_geqrf
from benchmark.trace import modules


def read(run):
    m = modules.of_run(run)
    if m is None:
        return None
    busy = m.seconds_of(("geqrt", "tsqrt"), ops_count_geqrf.CLASSES)
    if busy is None:
        return None
    nt = run.size("n") // run.size("nb")
    return 1e3 * busy / ops_count_geqrf.panel_tasks(nt)
