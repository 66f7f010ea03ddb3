"""layer: scheduler.  source: the program's ``parsec:*`` spans in the
profiler's trace.  moves: ``tile_solve_s``.
self time of the pump's ``pump:*`` spans other than ``pump:stage_wait``
(pump cell) or of the scheduling core's ``core:*`` spans on every thread
(context, mesh2x2), per task taken by the device module."""

from benchmark.trace import spans


def read(run):
    s = spans.of_run(run)
    return None if s is None else s.sched_us_per_task
