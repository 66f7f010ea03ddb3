"""layer: device.  source: the ``laps`` of the program's ``parsec:*``
spans in the profiler's trace (``benchmark/trace/phases.py``;
``docs/TRACING.md`` "Laps").  moves: ``tile_solve_s``.
Per task: the lap ``walk`` of ``dev:stage_args``: the loop over the
chunk's tasks and their flows under the residency lock (``_stage_chunk``:
``current_copy``, the LRU touch, the pin, the list of what is missing),
the wait for the lock (``wait:res_lock``) taken out.
Nothing to read from a program whose spans carry no ``laps`` (every
commit before PR 48)."""

from benchmark.trace import phases


def read(run):
    p = phases.of_run(run)
    return None if p is None else p.stage_walk_us_per_task
