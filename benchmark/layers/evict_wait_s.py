"""layer: device.  source: the program's ``parsec:dev:evict`` spans in
the profiler's trace.  moves: ``tile_solve_s``.  Seconds a solve of
``dev:evict`` spans (a ``reserve`` / ``account`` that had to evict), on
the thread that waited for the room: the staging walk's or the transfer
lane's.  Nothing to read from a program without the span."""

from benchmark.trace import evict


def read(run):
    e = evict.of_run(run)
    return None if e is None else e.total_ns / 1e9 / e.solves
