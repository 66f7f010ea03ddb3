"""layer: device.  source: the device module's ``wave_signatures``.
moves: ``tile_solve_s``.  Distinct wave signatures a solve: a flow's
dtype is part of a task's signature, so a class whose tiles come in two
precisions has up to 2^(its tile flows) of them; only tasks of one
signature share a program.  What the precisions cost the batching,
beside ``tasks_per_program``.  Nothing to read from a program without the
counter."""


def read(run):
    return run.per_solve("wave_signatures")
