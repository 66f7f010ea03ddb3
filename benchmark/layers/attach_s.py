"""layer: dsl.  source: host clock around executor construction /
``add_taskpool`` (the drivers' ``bench:attach`` span).  moves:
``tile_solve_s``.  Median over the solves; slowest rank on four chips."""


def read(run):
    return run.median("attach_s")
