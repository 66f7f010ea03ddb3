"""layer: device.  source: the device module's ``scratch_bytes_peak``
(its high-water mark of the bytes of scratch tiles born and not yet
freed), which the driver puts back to 0 before a solve and sums after
it.  moves: ``tile_solve_s``.  MiB of dense Q blocks alive at once, the
mean over the window's solves: what the DAG's width costs in device
memory (a kill's block lives until the last of its row's updates).
Nothing to read from a program without the counter."""


def read(run):
    peak = run.per_solve("scratch_peak_sum")
    return peak / 2 ** 20 if peak else None
