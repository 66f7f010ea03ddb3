"""layer: device.  source: the program's ``parsec:*`` spans in the
profiler's trace.  moves: ``tile_solve_s``.
time of the ``dev:dispatch`` spans (the one call of the jitted wave
program: the host's enqueue, not the chip's execution) per device
program."""

from benchmark.trace import spans


def read(run):
    s = spans.of_run(run)
    return None if s is None else s.dispatch_us_per_program
