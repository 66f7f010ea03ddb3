"""layer: ops kernels.  source: the profiler's trace, ``XLA Modules``
line.  moves: ``tile_solve_s``.  The syrk and gemm tasks' mathematical
operations (nb^3 and 2 nb^3 each, whatever implements them:
``ops_count_mle.update_flops``) at the chip's bf16 peak, over the device
seconds a solve of the programs that carry ``syrk`` or ``gemm`` in their
names (``trace/modules.py``).  A bfloat16 tile's update is one MXU pass, a
float32 tile's one pass where both operands are bfloat16 and six
(``highest``) otherwise: it cannot pass 100.  Nothing to read from a
program whose modules carry no class."""

from benchmark import ops_count, ops_count_mle
from benchmark.trace import modules


def read(run):
    m = modules.of_run(run)
    if m is None or not run.peaks:
        return None
    busy = m.seconds_of(("syrk", "gemm"), ops_count_mle.CLASSES)
    if not busy:
        return None
    nt = run.size("n") // run.size("nb")
    return ops_count.roofline_pct(
        ops_count_mle.update_flops(nt, run.size("nb")),
        run.peaks["bf16_flops_per_s"], run.cell.chips, busy)
