"""layer: device.  source: the ``laps`` of the program's ``parsec:*``
spans in the profiler's trace (``benchmark/trace/phases.py``;
``docs/TRACING.md`` "Laps").  moves: ``tile_solve_s``.
Σ ``hand_us`` over Σ ``handed`` of the ``dev:wave`` / ``dev:submit_one``
spans: the time a device manager's ``_hand_over`` took between two
drains (``Context._run_task`` and its ``core:prepare_input`` included, to
the moment the queue was taken), per task it progressed.  It runs under
no span (``idle_unattributed_pct``), so the manager's loop stamps it on
the first task span of the next drain.  Nothing to read on the pump path
(no manager, no stamp).
Nothing to read from a program whose spans carry no ``laps`` (every
commit before PR 48)."""

from benchmark.trace import phases


def read(run):
    p = phases.of_run(run)
    return None if p is None else p.handover_us_per_task
