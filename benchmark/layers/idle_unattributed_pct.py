"""layer: device.  source: the program's ``parsec:*`` spans in the
profiler's trace.  moves: ``tile_solve_s``.
share of the idlest chip's idle time that no span of the program
covers on any thread: the benchmark's own wait for the last tiles
(``c.sync``), ``np.asarray`` of the factor, and what the program has not
instrumented.  The five ``idle_*_pct`` sum to 100."""

from benchmark.trace import spans


def read(run):
    s = spans.of_run(run)
    return None if s is None else s.idle_pct("unattributed")
