"""layer: device.  source: the device module's ``scratch_bytes_in`` and
``scratch_bytes_out``.  moves: ``tile_home_s``.  MiB a solve that crossed
between host and chip for tiles of ``NEW`` flows, which have no value to
stage and no home to go to (the dense Q blocks: 2,016 MiB of them a solve
at N=16384).  0 is right.  Nothing to read from a program without the
counters."""


def read(run):
    moved = [run.per_solve(k) for k in ("scratch_bytes_in",
                                        "scratch_bytes_out")]
    if None in moved:
        return None
    return sum(moved) / 2 ** 20
