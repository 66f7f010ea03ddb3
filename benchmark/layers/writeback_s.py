"""layer: device.  source: the program's ``parsec:*`` spans in the
profiler's trace.  moves: ``tile_home_s``.
time of the ``dev:writeback`` spans per solve, on the committer thread
and in the batched flush of ``detach``: where the copies of the factor
that ``d2h_per_result`` counts are paid."""

from benchmark.trace import spans


def read(run):
    s = spans.of_run(run)
    return None if s is None else s.writeback_s
