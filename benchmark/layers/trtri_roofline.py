"""layer: ops kernels.  source: the profiler's trace, ``XLA Modules``
line.  moves: ``tile_solve_s``.  The least time the chip could take for
dtrtri's N^3 / 3 operations at its published bf16 peak, over the device
seconds a solve of the programs of the four ``trtri_*`` classes.  f32 at
``highest`` is six bf16 passes and the triangular solves have no MXU
path: the ceiling is a sixth.  Nothing to read from a program whose
modules carry none of these classes."""

from benchmark import ops_count, ops_count_poinv as poinv
from benchmark.trace import modules


def read(run):
    m = modules.of_run(run)
    if m is None or not run.peaks:
        return None
    busy = m.seconds_of(poinv.TRTRI_CLASSES, poinv.CLASSES)
    if not busy:
        return None
    return ops_count.roofline_pct(
        poinv.member_flops(run.size("n")),
        run.peaks["bf16_flops_per_s"], run.cell.chips, busy)
