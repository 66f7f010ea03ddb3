"""layer: device.  source: the ``cpu_us`` of the program's ``parsec:*``
spans and its ``parsec-wait:*`` events in the profiler's trace
(``benchmark/trace/waits.py``).  moves: ``tile_solve_s``.
Seconds a solve that the submitting threads of the idlest chip's rank
spent in ``wait:d2h_start``: the calls of ``copy_to_host_async`` made at
hand-over under ``dev:epilog`` (and by an eviction under
``dev:stage_args``), during which the thread is inside the runtime and
submits nothing.  What starting the copies home off the pump's thread
could take off it.
Nothing to read from a program whose spans carry no ``cpu_us``; 0.0 where
the program has the code and nothing waited."""

from benchmark.trace import waits


def read(run):
    w = waits.of_run(run)
    return None if w is None else w.copy_start_s
