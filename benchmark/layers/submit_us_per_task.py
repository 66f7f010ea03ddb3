"""layer: device.  source: the program's ``parsec:*`` spans in the
profiler's trace.  moves: ``tile_solve_s``.
self time of ``dev:submit_batch``, ``dev:wave``, ``dev:submit_one``,
``dev:stage_args``, ``dev:jit`` and ``dev:epilog`` per task: the device
module's host work around each program, without the call into the
program and without the scheduling core's spans nested in the epilog."""

from benchmark.trace import spans


def read(run):
    s = spans.of_run(run)
    return None if s is None else s.submit_us_per_task
