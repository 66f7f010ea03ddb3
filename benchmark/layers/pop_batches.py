"""layer: scheduler.  source: ``NativeExecutor.stats``.  moves:
``tile_solve_s``.  Batches the pump popped from the native ready queue,
per solve; only the pump driver counts them."""


def read(run):
    return run.per_solve("pop_batches")
