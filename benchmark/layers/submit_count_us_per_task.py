"""layer: device.  source: the ``laps`` of the program's ``parsec:*``
spans in the profiler's trace (``benchmark/trace/phases.py``;
``docs/TRACING.md`` "Laps").  moves: ``tile_solve_s``.
Per task: the lap ``count`` of ``dev:wave`` / ``dev:submit_one``:
``_count_values`` (the ``id`` of every tile operand, the repeats),
``_count_converts``, the counters, between the call's return and the
commit.
Nothing to read from a program whose spans carry no ``laps`` (every
commit before PR 48)."""

from benchmark.trace import phases


def read(run):
    p = phases.of_run(run)
    return None if p is None else p.submit_count_us_per_task
