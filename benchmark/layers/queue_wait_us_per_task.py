"""layer: scheduler.  source: the program's ``parsec:*`` spans in the
profiler's trace.  moves: ``tile_solve_s``.
the ``waited_us`` of the ``dev:wave`` / ``dev:submit_one`` spans (time
between ``kernel_scheduler`` queueing a task and the device manager
draining it), summed and divided by the tasks.  The pump has no such
queue."""

from benchmark.trace import spans


def read(run):
    s = spans.of_run(run)
    return None if s is None else s.queue_wait_us_per_task
