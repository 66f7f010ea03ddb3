"""layer: device.  source: the program's ``parsec:*`` spans in the
profiler's trace.  moves: ``tile_solve_s``.
Share of the idlest chip's idle time that lies under staging, write-back
and waiting for either (``dev:stage_in``, ``dev:writeback``, ``dev:h2d``,
``pump:stage_wait``, ``comm:*``).  On each thread the innermost span
counts; where threads disagree the classes win in the order dispatch,
submit, transfer, scheduler (``benchmark/trace/spans.py``).  The five
``idle_*_pct`` sum to 100."""

from benchmark.trace import spans


def read(run):
    s = spans.of_run(run)
    return None if s is None else s.idle_pct("transfer")
