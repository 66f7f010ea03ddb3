"""layer: device.  source: the program's ``parsec:*`` spans in the
profiler's trace.  moves: ``tile_solve_s``.
time the submitting thread waits for tiles, per task: ``pump:stage_wait``
(the pump waiting for the transfer lane) plus the ``dev:h2d`` spans under
``dev:stage_args`` (tiles that were still host arrays when their chunk
was staged, ``host_tiles``)."""

from benchmark.trace import spans


def read(run):
    s = spans.of_run(run)
    return None if s is None else s.stage_wait_us_per_task
