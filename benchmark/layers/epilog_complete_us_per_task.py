"""layer: device.  source: the ``laps`` of the program's ``parsec:*``
spans in the profiler's trace (``benchmark/trace/phases.py``;
``docs/TRACING.md`` "Laps").  moves: ``tile_solve_s``.
Per task: the lap ``complete`` of ``dev:epilog``, its
``core:complete_exec`` children taken out (they are the scheduler's and
stay ``sched_us_per_task``'s): the loop over the committed tasks; on the
pump path, where nothing completes here, the loop alone.
Nothing to read from a program whose spans carry no ``laps`` (every
commit before PR 48)."""

from benchmark.trace import phases


def read(run):
    p = phases.of_run(run)
    return None if p is None else p.epilog_complete_us_per_task
