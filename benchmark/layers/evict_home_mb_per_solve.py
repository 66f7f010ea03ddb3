"""layer: device.  source: the program's ``parsec:dev:evict`` spans in
the profiler's trace.  moves: ``tile_solve_s``.  MiB a solve that an
eviction had to write home first because the chip held the only valid
copy (the spans' ``bytes_home``), beside the last versions that
``d2h_per_result`` counts.  Nothing to read from a program without the
span."""

from benchmark.trace import evict


def read(run):
    e = evict.of_run(run)
    return None if e is None else e.bytes_home / 2 ** 20 / e.solves
