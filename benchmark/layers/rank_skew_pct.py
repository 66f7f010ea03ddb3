"""layer: comm.  source: host clock in the rank threads.  moves:
``tile_solve_s``.  (slowest - fastest rank's ``tp.wait`` return) over the
solve."""


def read(run):
    return run.median("rank_skew_pct")
