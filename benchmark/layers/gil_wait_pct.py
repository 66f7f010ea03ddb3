"""layer: device.  source: the ``cpu_us`` of the program's ``parsec:*``
spans and its ``parsec-wait:*`` events in the profiler's trace
(``benchmark/trace/waits.py``).  moves: ``tile_solve_s``.
On the submitting threads of the idlest chip's rank: the self time OFF
the CPU over the self time of the spans that carry ``cpu_us`` (the
thread-CPU clock is read within a budget, ``waits.py``: a sample, of a
10 ms clock on the benchmark's machine) and in which the thread calls nothing
that blocks by design (every ``parsec:*`` span but ``dev:dispatch``,
``dev:h2d``, ``dev:block``, ``dev:poll``, ``dev:writeback``, ``dev:flush``,
``dev:detach``, ``pump:stage_wait``, ``comm:*``, ``cc:compile``), every
``wait:*`` child taken out: Python work that waited, so the wait for
the GIL (and whatever else took the CPU from the thread).
Nothing to read from a program whose spans carry no ``cpu_us``; 0.0 where
the program has the code and nothing waited."""

from benchmark.trace import waits


def read(run):
    w = waits.of_run(run)
    return None if w is None else w.gil_wait_pct
