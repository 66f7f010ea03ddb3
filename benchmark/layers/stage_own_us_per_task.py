"""layer: device.  source: the ``laps`` of the program's ``parsec:*``
spans in the profiler's trace (``benchmark/trace/phases.py``;
``docs/TRACING.md`` "Laps").  moves: ``tile_solve_s``.
Per task: the laps ``put`` (``StageIn.batch`` and the arguments it
fills; its ``dev:h2d`` child, evictions and waits taken out), ``sole``
(``_not_sole``: may the chunk donate) and ``own`` (``transfer_ownership``
of every tile, ``Residency.next_uses``, the tally) of ``dev:stage_args``:
the staging walk's bookkeeping after the loop.
Nothing to read from a program whose spans carry no ``laps`` (every
commit before PR 48)."""

from benchmark.trace import phases


def read(run):
    p = phases.of_run(run)
    return None if p is None else p.stage_own_us_per_task
