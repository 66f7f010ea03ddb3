"""layer: compile_cache.  source: JAX's monitoring events.  moves:
``setup_s``.  Backend compiles before the window (persistent-cache
misses included): 0 once a checkout's first run has filled the cache."""


def read(run):
    return run.compiles["setup"]
