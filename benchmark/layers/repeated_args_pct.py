"""layer: device.  source: the device module's ``tile_args_repeated`` and
``tile_args_passed``.  moves: ``tile_solve_s``.  Of the tile operands
handed to device programs, the share that was the same resident array as
an earlier operand of the same program: what passing each array once
(ROADMAP S3a) would take off the per-argument cost of the call into a
program.  Nothing to read from a program without the counters."""


def read(run):
    rep = run.per_solve("tile_args_repeated")
    passed = run.per_solve("tile_args_passed")
    if rep is None or not passed:
        return None
    return 100.0 * rep / passed
