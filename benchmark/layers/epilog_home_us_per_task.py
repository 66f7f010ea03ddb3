"""layer: device.  source: the ``laps`` of the program's ``parsec:*``
spans in the profiler's trace (``benchmark/trace/phases.py``;
``docs/TRACING.md`` "Laps").  moves: ``tile_solve_s``.
Per task: the laps ``home`` (``_send_home``: the hand-over to the
write-back committer, its ``wait:d2h_start`` and ``wait:wb_capacity``
taken out) and ``zeros`` (``HostWriter.land_zeros``) of ``dev:epilog``.
Nothing to read from a program whose spans carry no ``laps`` (every
commit before PR 48)."""

from benchmark.trace import phases


def read(run):
    p = phases.of_run(run)
    return None if p is None else p.epilog_home_us_per_task
