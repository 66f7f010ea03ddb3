"""layer: device.  source: the ``laps`` of the program's ``parsec:*``
spans in the profiler's trace (``benchmark/trace/phases.py``;
``docs/TRACING.md`` "Laps").  moves: ``tile_solve_s``.
``dev:submit_batch``'s own time per task, by its laps: ``units``
(``TpuDevice._units_of``: one ``_signature_of`` a task and the bucketing)
and what ``waves`` and ``retry`` keep for themselves once the
``dev:wave`` / ``dev:submit_one`` children are taken out
(``_submit_wave``'s preamble with ``_born_here``, the unpin of what the
lane staged ahead); on the ``Context`` path, where no span lies over the
drain, the ``units_us`` that the manager's loop stamps on the first task
span of every drain.  ``python3 -m benchmark.trace.phases`` prints
``units`` apart.
Nothing to read from a program whose spans carry no ``laps`` (every
commit before PR 48)."""

from benchmark.trace import phases


def read(run):
    p = phases.of_run(run)
    return None if p is None else p.submit_units_us_per_task
