"""layer: ops kernels.  source: the profiler's trace.  moves:
``tile_solve_s``.  The least time the chip could take for the N^3
operations of potrf + trtri + lauum at its published bf16 peak, over the
seconds an operation ran on the device per solve.  f32 at ``highest`` is
six bf16 passes: the ceiling of this share is a sixth."""

from benchmark import ops_count, ops_count_poinv


def read(run):
    if not run.trace or not run.peaks:
        return None
    return ops_count.roofline_pct(
        ops_count_poinv.poinv_flops(run.size("n")),
        run.peaks["bf16_flops_per_s"], run.cell.chips,
        run.trace.busy_s / run.trace.solves)
