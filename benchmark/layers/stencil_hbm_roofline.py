"""layer: ops kernels.  source: the profiler's trace, ``XLA Modules``
line.  moves: ``tile_solve_s``.  The least time the chip's memory could
take for the bytes a sweep-a-pass program moves, 8 n^2 T (one read and
one write of a float32 a point a sweep: ``ops_count_stencil.
sweep_hbm_bytes``) at its published bandwidth (``hbm_bytes_per_s`` in
``peaks.json``), over the device seconds a solve of the programs that
carry the stencil's task class in their names (``jit__wave_stencil``, and
a task that went out alone under its body's name; ``trace/modules.py``).
It cannot pass 100 while a program makes one sweep a pass.  A program that
blocks several sweeps into one pass over a tile moves fewer bytes than
this count: it needs a ``benchmark`` issue for its count first.  Nothing
to read from a program whose modules carry no class."""

from benchmark import ops_count_stencil
from benchmark.trace import modules


def read(run):
    m = modules.of_run(run)
    if m is None or not run.peaks:
        return None
    busy = m.seconds_of(ops_count_stencil.CLASSES, ops_count_stencil.CLASSES)
    if not busy:
        return None
    least = ops_count_stencil.sweep_hbm_bytes(
        run.size("n"), run.size("iters")) \
        / (run.peaks["hbm_bytes_per_s"] * run.cell.chips)
    return 100.0 * least / busy
