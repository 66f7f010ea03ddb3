"""layer: device.  source: the ``cpu_us`` of the program's ``parsec:*``
spans and its ``parsec-wait:*`` events in the profiler's trace
(``benchmark/trace/waits.py``).  moves: ``tile_solve_s``.
Share of the idlest chip's idle time during which the innermost event
of a submitting thread of its rank is a ``wait:*`` of any kind.  A cut
ACROSS the five ``idle_in_*`` / ``idle_unattributed`` shares, which go on
summing to 100 without it.
Nothing to read from a program whose spans carry no ``cpu_us``; 0.0 where
the program has the code and nothing waited."""

from benchmark.trace import waits


def read(run):
    w = waits.of_run(run)
    return None if w is None else w.idle_in_wait_pct
