"""layer: device.  source: the ``cpu_us`` of the program's ``parsec:*``
spans and its ``parsec-wait:*`` events in the profiler's trace
(``benchmark/trace/waits.py``).  moves: ``tile_solve_s``.
Duration minus ``cpu_us`` of the ``dev:dispatch`` spans that carry it
(the thread-CPU clock is read within a budget, ``waits.py``), per such
program: the part of the call into a program during which its thread was
off the CPU: PJRT holding the enqueue back (for memory, for a full
queue) plus the wait to get the GIL back.  ``dispatch_us_per_program``
minus this is the CPU work of the call.
Nothing to read from a program whose spans carry no ``cpu_us``; 0.0 where
the program has the code and nothing waited."""

from benchmark.trace import waits


def read(run):
    w = waits.of_run(run)
    return None if w is None else w.dispatch_blocked_us_per_program
