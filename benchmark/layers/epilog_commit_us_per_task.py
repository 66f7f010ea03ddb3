"""layer: device.  source: the ``laps`` of the program's ``parsec:*``
spans in the profiler's trace (``benchmark/trace/phases.py``;
``docs/TRACING.md`` "Laps").  moves: ``tile_solve_s``.
Per task: the laps ``hooks`` (the ``stage_out`` hooks, the wait for
the residency lock taken out), ``commit`` (the ONE loop of
``_commit_output`` / ``_release_scratch`` over the chunk's tasks) and
``settle`` (``Residency.next_uses`` + ``settle()``, an eviction's
``dev:evict`` taken out) of ``dev:epilog``: the commit under the
residency lock.
Nothing to read from a program whose spans carry no ``laps`` (every
commit before PR 48)."""

from benchmark.trace import phases


def read(run):
    p = phases.of_run(run)
    return None if p is None else p.epilog_commit_us_per_task
