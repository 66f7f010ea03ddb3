"""layer: device.  source: the ``laps`` of the program's ``parsec:*``
spans in the profiler's trace (``benchmark/trace/phases.py``;
``docs/TRACING.md`` "Laps").  moves: ``tile_solve_s``.
Per task: the laps ``room`` (``Residency.wait_for`` less its
``wait:chip_lead``) and ``key`` (``argsig``, ``_placeholders_at``, the
local key, ``_cached_jit``'s look-up) of ``dev:wave`` / ``dev:submit_one``,
children and waits taken out, plus the self time of the ``dev:jit`` span
that lies inside ``key``: what it costs to find a chunk's program.
Nothing to read from a program whose spans carry no ``laps`` (every
commit before PR 48)."""

from benchmark.trace import phases


def read(run):
    p = phases.of_run(run)
    return None if p is None else p.submit_key_us_per_task
