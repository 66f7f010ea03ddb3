"""layer: device.  source: the program's ``parsec:*`` spans in the
profiler's trace.  moves: ``tile_solve_s``.
time of the ``dev:d2d`` spans (the host's enqueue of a staging walk's
chip-to-chip landings, not the copies' own time on the link) per task
taken by the device modules.  Nothing to read from a program without
the span."""

from benchmark.trace import spans


def read(run):
    s = spans.of_run(run)
    if s is None or "dev:d2d" not in s.total_ns:
        return None
    return s.total_ns["dev:d2d"] / 1e3 / (s.tasks * s.solves)
