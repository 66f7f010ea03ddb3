"""layer: ops kernels.  source: the profiler's trace, ``XLA Modules``
line.  moves: ``tile_solve_s``.  The operations the unmqr, tsmqr and
ttmqr programs of the hierarchical tile QR EXECUTE (``Q^T C`` with dense
Q: ``ops_count_geqrf_hqr.update_flops_executed``) at the chip's bf16
peak, over the device seconds of those programs a solve, found by the
task class in their module names (``trace/modules.py``).  f32 at
``highest`` is six bf16 passes: the ceiling is a sixth.  Nothing to read
from a program whose modules carry none of these classes."""

from benchmark import ops_count, ops_count_geqrf_hqr as hqr
from benchmark.trace import modules


def read(run):
    m = modules.of_run(run)
    if m is None or not run.peaks:
        return None
    busy = m.seconds_of(hqr.UPDATES, hqr.CLASSES)
    if not busy:
        return None
    return ops_count.roofline_pct(
        hqr.update_flops_executed(*hqr.grid_of(run.size), run.size("nb")),
        run.peaks["bf16_flops_per_s"], run.cell.chips, busy)
