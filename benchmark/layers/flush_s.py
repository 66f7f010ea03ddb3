"""layer: device.  source: host clock around ``ex.close()`` /
``dev.flush()`` (the drivers' ``bench:flush`` span).  moves:
``tile_home_s``."""


def read(run):
    return run.median("flush_s")
