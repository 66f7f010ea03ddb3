"""layer: compile_cache.  source: JAX's monitoring events.  moves:
``tile_solve_s``.  Backend compiles inside the window: the Context
path's wave sizes depend on the schedule, so one can appear; reported,
not hidden."""


def read(run):
    return run.compiles["window"]
