"""layer: device.  source: ``memory_stats()["bytes_in_use"]`` after the
window.  moves: ``peak_hbm_gb``.  What the run still holds when no solve
is in flight."""


def read(run):
    return run.memory["bytes_in_use"] / 1e6 or None
