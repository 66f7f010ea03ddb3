"""layer: ops kernels.  source: the profiler's trace, ``XLA Modules``
line.  moves: ``tile_solve_s``.  The operations the unmqr and tsmqr
programs EXECUTE (``Q^T C`` with dense Q: ``ops_count_geqrf.
update_flops_executed``) at the chip's bf16 peak, over the device seconds
of those programs a solve, found by the task class in their module names
(``trace/modules.py``).  f32 at ``highest`` is six bf16 passes: the
ceiling is a sixth.  Nothing to read from a program whose modules carry
no class."""

from benchmark import ops_count, ops_count_geqrf
from benchmark.trace import modules


def read(run):
    m = modules.of_run(run)
    if m is None or not run.peaks:
        return None
    busy = m.seconds_of(("unmqr", "tsmqr"), ops_count_geqrf.CLASSES)
    if not busy:
        return None
    nt = run.size("n") // run.size("nb")
    return ops_count.roofline_pct(
        ops_count_geqrf.update_flops_executed(nt, run.size("nb")),
        run.peaks["bf16_flops_per_s"], run.cell.chips, busy)
