"""layer: ops kernels.  source: the profiler's trace.  moves:
``tile_solve_s`` as ``geqrf_roofline.tile``.  The least time the chip
could take for LAPACK's 4 N^3 / 3 operations at its published bf16 peak,
over the seconds an operation ran on the device per solve.  The program
EXECUTES about twice that count (dense Q blocks in place of compact-WY:
``ops_count_geqrf``), and f32 at ``highest`` is six bf16 passes, so the
ceiling of this share is a twelfth, not 100."""

from benchmark import ops_count, ops_count_geqrf


def read(run):
    if not run.trace or not run.peaks:
        return None
    return ops_count.roofline_pct(
        ops_count_geqrf.geqrf_flops(run.size("n")),
        run.peaks["bf16_flops_per_s"], run.cell.chips,
        run.trace.busy_s / run.trace.solves)
