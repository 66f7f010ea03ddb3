"""layer: device.  source: the ``laps`` of the program's ``parsec:*``
spans in the profiler's trace (``benchmark/trace/phases.py``;
``docs/TRACING.md`` "Laps").  moves: ``tile_solve_s``.
The laps that lie under the six spans of ``spans.SUBMIT_SPANS``,
summed with the waits left in (so neither ``units_us`` nor ``hand_us``,
which lie under none), over those spans' self time from the same trace,
``submit_us_per_task``'s numerator, in percent: the split is whole at 95
or more.  What is missing is each span's head and tail around its laps
and ``dev:jit``'s self time (``python3 -m benchmark.trace.phases`` prints
it by span).
Nothing to read from a program whose spans carry no ``laps`` (every
commit before PR 48)."""

from benchmark.trace import phases


def read(run):
    p = phases.of_run(run)
    return None if p is None else p.submit_laps_cover_pct
