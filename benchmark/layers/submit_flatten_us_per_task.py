"""layer: device.  source: the ``laps`` of the program's ``parsec:*``
spans in the profiler's trace (``benchmark/trace/phases.py``;
``docs/TRACING.md`` "Laps").  moves: ``tile_solve_s``.
Per task: the lap ``flatten`` of ``dev:wave`` / ``dev:submit_one``:
``ValuePlan.flatten``, the program's argument list spelled from the
tasks' staged arguments.
Nothing to read from a program whose spans carry no ``laps`` (every
commit before PR 48)."""

from benchmark.trace import phases


def read(run):
    p = phases.of_run(run)
    return None if p is None else p.submit_flatten_us_per_task
