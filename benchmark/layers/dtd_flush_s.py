"""layer: device.  source: the program's ``parsec:*`` spans in the
profiler's trace.  moves: ``tile_home_s``.
time of the ``core:dtd_flush`` spans per solve: ``flush_all`` from the
start of the first copy home (``wait:d2h_start`` under it, on the
flushing thread) to the landing of the last (the committer's
``dev:writeback`` beside it): the one moment a DTD tile goes home.
Nothing to read from a program whose DTD carries no span."""

from benchmark.trace import spans


def read(run):
    s = spans.of_run(run)
    if s is None or "core:dtd_flush" not in s.total_ns:
        return None
    return s.total_ns["core:dtd_flush"] / 1e9 / s.solves
