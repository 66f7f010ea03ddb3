"""layer: device.  source: the profiler's trace.  moves: ``tile_solve_s``,
and ``panel_solve_s`` as ``device_idle_pct.panel``.
1 - (union of device-operation intervals) / (traced solves), on the
idlest chip."""


def read(run):
    return run.trace.idle_pct_worst if run.trace else None
