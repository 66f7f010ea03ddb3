"""layer: device.  source: the ``cpu_us`` of the program's ``parsec:*``
spans and its ``parsec-wait:*`` events in the profiler's trace
(``benchmark/trace/waits.py``).  moves: ``tile_solve_s``.
Time the submitting threads (those that carry ``dev:submit_batch``,
``dev:wave`` or ``dev:submit_one``) spent in ``wait:res_lock`` inside the
``bench:solve`` windows, per task: how long the pump, or the worker that
is device manager, waited for the residency lock that the transfer lane
(or an eviction on it) held.  The event's ``holder`` says for whom
(``python3 -m benchmark.trace.waits``).
Nothing to read from a program whose spans carry no ``cpu_us``; 0.0 where
the program has the code and nothing waited."""

from benchmark.trace import waits


def read(run):
    w = waits.of_run(run)
    return None if w is None else w.res_lock_wait_us_per_task
