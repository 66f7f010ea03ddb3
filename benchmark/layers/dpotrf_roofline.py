"""layer: ops kernels.  source: the profiler's trace.  moves:
``panel_solve_s`` as ``dpotrf_roofline.panel``, ``tile_solve_s`` as
``dpotrf_roofline.tile``.  The least time the chip could take for N^3/3
operations at its published bf16 peak, over the seconds an operation ran
on the device per solve.  The bound is compute at bf16 peak: f32 by
three or six bf16 passes has a ceiling of a third or a sixth."""

from benchmark import ops_count


def read(run):
    if not run.trace or not run.peaks:
        return None
    return ops_count.roofline_pct(
        ops_count.dpotrf_flops(run.size("n")),
        run.peaks["bf16_flops_per_s"], run.cell.chips,
        run.trace.busy_s / run.trace.solves)
